"""Work a round needs, counted from shapes (never from the program's own
claims about its passes): FLOPs of the fleet's local training and
evaluation, and the least bytes one WFAgg gossip round must move."""
from __future__ import annotations

import numpy as np


def fleet_round_flops(model, cfg, n_nodes: int, n_test: int) -> float:
    """FLOPs of one fleet round: every node trains ``batches_per_round``
    batches (forward + backward = 3 forwards per image) and evaluates
    ``n_test`` test images.  Aggregation FLOPs are O(N K d) and left
    out."""
    fwd = model.forward_flops(cfg)
    train = cfg["batches_per_round"] * cfg["batch_size"] * 3 * fwd
    return float(n_nodes) * (train + n_test * fwd)


def wfagg_round_bytes(idx: np.ndarray, valid: np.ndarray, d: int,
                      itemsize: int = 4) -> float:
    """Least HBM bytes of one WFAgg round over an (N, K) neighbor table:
    each distinct model row referenced (a valid neighbor, or a node's own
    row, which the combine reads) is read once, each distinct previous
    row a valid edge compares against is read once, and the N new rows
    are written once."""
    N = idx.shape[0]
    senders = np.unique(idx[valid])
    model_rows = np.union1d(senders, np.arange(N)).size
    return float((model_rows + senders.size + N) * d * itemsize)
