"""Work one robust-DP training step needs, counted from shapes (never
from the program): the model FLOPs of every worker's forward and
backward pass, and the least bytes one WFAgg aggregation of the K
candidate gradients must move."""
from __future__ import annotations


def param_count(c) -> int:
    """Parameters of a Qwen1.5-style decoder with tied embeddings and
    QKV bias, from its configuration (``bench/configs/qwen*.json`` keys)."""
    d, ff, V, L = c["hidden_size"], c["intermediate_size"], c["vocab_size"], c["num_hidden_layers"]
    q, kv = c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"]
    attn = d * q + 2 * d * kv + q * d + q + 2 * kv      # projections and QKV bias
    return V * d + L * (attn + 3 * d * ff + 2 * d) + d


def forward_flops(c, seq_len: int) -> float:
    """FLOPs of one sequence's forward pass (2 per multiply-add): the
    projections and the SwiGLU MLP of every layer for each of the
    ``seq_len`` tokens, the attention's scores and weighted values over
    all ``seq_len`` keys (the program computes the masked square), and
    the tied head for the ``seq_len - 1`` predicted positions.  Norms,
    biases, rotary embedding and softmax are left out."""
    d, ff, V, L = c["hidden_size"], c["intermediate_size"], c["vocab_size"], c["num_hidden_layers"]
    q, kv = c["num_attention_heads"] * c["head_dim"], c["num_key_value_heads"] * c["head_dim"]
    per_token = 2 * (d * q + 2 * d * kv + q * d + 3 * d * ff) + 2 * 2 * seq_len * q
    return float(L * per_token * seq_len + 2 * d * V * (seq_len - 1))


def train_step_flops(c, mix) -> float:
    """Model FLOPs of one step: forward and backward (3 forwards) of
    every sequence of every worker.  Recomputation (remat) is not
    counted."""
    n_seqs = mix["workers"] * mix["seqs_per_worker"]
    return 3.0 * n_seqs * forward_flops(c, mix["seq_len"])


def wfagg_step_bytes(c, workers: int, itemsize: int = 4) -> float:
    """Least HBM bytes of one aggregation of ``workers`` candidate
    gradients of P parameters: the K candidates read once, the K
    previous candidates of the WFAgg-T history read once, the aggregate
    written once: (2 K + 1) P floats."""
    return float((2 * workers + 1) * param_count(c) * itemsize)
