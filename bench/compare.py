"""The numbers the output check compares, each against a limit of its
own (``limits`` of the traffic mix).

``worst_leaf_norm_gap`` is the measure the benchmark's contract fixes
for training: for every leaf, the gap between the program's norm and
the reference's, over the larger of the reference's norm of that leaf
and of the median leaf; the worst leaf counts.  Leaves whose reference
norm is under a thousandth of the median leaf's are left out (a leaf
that only round-off moves).
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

import numpy as np


def worst_leaf_norm_gap(pairs: Iterable[Tuple[str, np.ndarray, np.ndarray]],
                        median: bool = False):
    """pairs of (name, program leaf, reference leaf) -> (gap, name of the
    worst leaf, number of leaves compared).  ``median`` gives the median
    leaf's gap instead of the worst (name empty)."""
    names, prog, ref = [], [], []
    for name, p, r in pairs:
        names.append(name)
        prog.append(float(np.linalg.norm(np.asarray(p, np.float64).ravel())))
        ref.append(float(np.linalg.norm(np.asarray(r, np.float64).ravel())))
    prog, ref = np.array(prog), np.array(ref)
    med = float(np.median(ref))
    keep = ref >= 1e-3 * med
    if not keep.any():
        return float("nan"), "", 0
    gap = np.abs(prog - ref) / np.maximum(ref, med)
    gap = np.where(np.isnan(gap), np.inf, gap)   # a non-finite program leaf
    if median:
        return float(np.median(gap[keep])), "", int(keep.sum())
    gap = np.where(keep, gap, -1.0)
    i = int(np.argmax(gap))
    return float(gap[i]), names[i], int(keep.sum())


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict[str, dict]:
    """{name: {"value", "limit", "ok"}} for every number with a limit; a
    missing or non-finite number fails."""
    out = {}
    for name, limit in limits.items():
        v = numbers.get(name, float("nan"))
        ok = bool(np.isfinite(v) and v <= limit)
        out[name] = {"value": v, "limit": limit, "ok": ok}
    return out
