"""Shared functional building blocks: norms, linears, embeddings, RoPE,
dtype policy and logical-axis activation sharding.

All models are pure functions over explicit parameter pytrees (nested dicts of
``jnp.ndarray``).  Repeated blocks store parameters *stacked* along a leading
layer axis so the forward pass is a ``lax.scan`` — this keeps the HLO compact
enough to SPMD-partition for 512 devices and is the idiomatic TPU pattern.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# Logical-axis activation sharding context
# ---------------------------------------------------------------------------

_CTX = threading.local()


@contextlib.contextmanager
def activation_sharding(rules: Dict[str, Optional[object]]):
    """Enable ``shard_act`` constraints inside the context.

    ``rules`` maps logical axis names (e.g. ``'batch'``, ``'embed'``,
    ``'heads'``, ``'ff'``, ``'vocab'``, ``'seq'``, ``'kv_seq'``, ``'expert'``)
    to physical mesh axis names — a string, a tuple of axis names, or None
    for replicated.  Requires an ambient mesh (``jax.set_mesh``); constraints
    use bare PartitionSpecs so they also work inside partial-manual
    ``shard_map`` bodies (pipeline stages).
    """
    prev = getattr(_CTX, "val", None)
    _CTX.val = dict(rules)
    try:
        yield
    finally:
        _CTX.val = prev


def shard_act(x: jnp.ndarray, names: Sequence[Optional[str]]) -> jnp.ndarray:
    """Apply a with_sharding_constraint from logical axis names (no-op outside
    an :func:`activation_sharding` context)."""
    rules = getattr(_CTX, "val", None)
    if rules is None:
        return x
    if jax.sharding.get_abstract_mesh().empty:
        return x  # no ambient mesh (single-device tests): no-op
    spec = P(*[rules.get(n) if n is not None else None for n in names])
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def _normal(rng, shape, scale, dtype):
    return (scale * jax.random.normal(rng, shape, dtype=jnp.float32)).astype(dtype)


def dense_init(rng, d_in: int, d_out: int, dtype=jnp.float32,
               stack: Tuple[int, ...] = ()) -> jnp.ndarray:
    """Fan-in scaled normal init; optional leading stack dims."""
    scale = d_in ** -0.5
    return _normal(rng, (*stack, d_in, d_out), scale, dtype)


def embed_init(rng, vocab: int, d: int, dtype=jnp.float32) -> jnp.ndarray:
    return _normal(rng, (vocab, d), 0.02, dtype)


def ones_init(shape, dtype=jnp.float32) -> jnp.ndarray:
    return jnp.ones(shape, dtype)


def zeros_init(shape, dtype=jnp.float32) -> jnp.ndarray:
    return jnp.zeros(shape, dtype)


# ---------------------------------------------------------------------------
# Core ops
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def linear(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """x: (..., d_in) @ w: (d_in, d_out) in the compute dtype of x."""
    return jnp.einsum("...i,io->...o", x, w.astype(x.dtype))


def activate(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "swiglu" or kind == "silu":
        return jax.nn.silu(x)
    if kind == "geglu" or kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if kind == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(f"unknown activation {kind!r}")


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)                    # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]                        # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


@jax.named_scope("head")
def softmax_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                          z_loss: float = 0.0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Token-level CE with f32 accumulation but NO materialized f32 copy of
    the logits: the upcast happens inside the reductions (XLA fuses
    cast+sub+exp into the reduce), which matters at 256k vocab where an f32
    logits copy is 2x the bf16 activation itself.

    logits: (..., V); labels: (...,) int. Returns (loss, correct@1)."""
    m = jnp.max(logits.astype(jnp.float32), axis=-1)          # fused reduce
    shifted_sum = jnp.sum(
        jnp.exp(logits.astype(jnp.float32) - m[..., None]), axis=-1)
    lse = m + jnp.log(shifted_sum)
    ll = jnp.take_along_axis(logits, labels[..., None],
                             axis=-1)[..., 0].astype(jnp.float32)
    loss = lse - ll
    if z_loss:
        loss = loss + z_loss * jnp.square(lse)
    acc = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
    return loss, acc


# ---------------------------------------------------------------------------
# Analysis (unroll) mode — the dry-run's cost-analysis pass
# ---------------------------------------------------------------------------
# XLA's cost_analysis counts a while-loop body ONCE, so scan-based models
# under-report FLOPs/collective bytes by the trip count.  The dry-run lowers
# a second "analysis" variant with every scan unrolled (exact costs); the
# production scanned variant provides memory analysis + the compile proof.

_UNROLL = False


def set_unroll(v: bool) -> None:
    global _UNROLL
    _UNROLL = bool(v)


def scan_unroll() -> bool:
    """Pass as ``unroll=`` to every structural lax.scan."""
    return _UNROLL


# ---------------------------------------------------------------------------
# Activation compute dtype policy
# ---------------------------------------------------------------------------
# Parameters may be stored f32 (optimizer master copies) while compute runs
# bf16 (the TPU-native policy): the cast happens once at the embedding;
# ``linear`` already casts weights to the activation dtype per use.

_ACT_DTYPE = None


def set_act_dtype(dt) -> None:
    global _ACT_DTYPE
    _ACT_DTYPE = dt


def act_dtype_cast(x: jnp.ndarray) -> jnp.ndarray:
    if _ACT_DTYPE is not None and x.dtype != _ACT_DTYPE:
        return x.astype(_ACT_DTYPE)
    return x
