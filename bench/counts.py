"""The operations and bytes the benchmark's shares are taken over.

Worked out from a configuration's sizes alone, never from the compiled
program: a recomputed operation (remat) or a padded vocabulary column is
work the program chose, not work the model needs.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Weights that enter a matrix multiplication once per token: every
    layer's attention and MLP projections, and the LM head.  The embedding
    is a gather, norms and biases are elementwise: none counts."""
    m, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg.get("head_dim") or m // h
    per_layer = m * h * dh + 2 * m * kv * dh + h * dh * m + 3 * m * f
    return cfg["num_hidden_layers"] * per_layer + m * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward FLOPs per trained token: 6 per matmul weight,
    plus causal attention's scores and weighted sum (2 matmuls of 2 FLOPs
    per key and head dimension, ×3 for the backward) over the (S + 1) / 2
    keys a query sees on average."""
    h = cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or cfg["hidden_size"] // h
    attn = 12.0 * cfg["num_hidden_layers"] * h * dh * (seq_len + 1) / 2.0
    return 6.0 * matmul_params(cfg) + attn


def whatif_event_bytes(width: int, c: int, ring_bytes: int,
                       stateful: bool, residue: bool) -> int:
    """HBM bytes one what-if ring event moves over a ring of ``width``
    columns: the c pulled rows, the previous row and the written row in
    the ring's dtype, the fp32 curvature and target read once, and the
    fp32 optimizer state and error-feedback residue each read and
    written."""
    per_col = (c + 2) * ring_bytes + 8
    if stateful:
        per_col += 8
    if residue:
        per_col += 8
    return width * per_col
