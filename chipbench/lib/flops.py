"""Model FLOPs of a dense decoder LM, from its shapes.

Training counts 6 FLOPs per matmul parameter per token (forward 2, backward
4) plus attention's score and value matmuls, 12 * layers * d_model * seq per
token (causal masking is not subtracted, as is usual for MFU).  Recompute,
and the LM head that every pipeline stage evaluates, do not count: they are
work the system chose, not work the model needs.
"""
from __future__ import annotations


def matmul_params(arch: dict) -> int:
    """Parameters that enter a matmul: per layer Q, K, V, O and the MLP's
    up and down projections (a gated MLP adds a gate), plus the LM head.
    The embedding is a gather and does not count."""
    d, f, L = arch["d_model"], arch["d_ff"], arch["n_layers"]
    head_dim = arch.get("head_dim") or d // arch["n_heads"]
    q = arch["n_heads"] * head_dim
    kv = arch["n_kv_heads"] * head_dim
    attn = d * q + 2 * d * kv + q * d
    mlp = (3 if arch["activation"] in ("swiglu", "geglu") else 2) * d * f
    return L * (attn + mlp) + d * arch["vocab_size"]


def train_flops_per_token(arch: dict, seq_len: int) -> float:
    attn = 12 * arch["n_layers"] * arch["d_model"] * seq_len
    return 6.0 * matmul_params(arch) + attn
