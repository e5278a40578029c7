"""Attention: GQA/MQA/MHA self-attention (full / sliding-window / causal /
bidirectional), cross-attention, and single-token decode against a KV cache.

The jnp path here is the reference implementation; perf-critical paths
dispatch to the Pallas flash kernel (``repro.kernels.ops``) when enabled.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import apply_rope, dense_init, linear, shard_act

NEG_INF = -2.0 ** 30


def attn_init(rng, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
              dtype=jnp.float32, stack: Tuple[int, ...] = ()) -> Dict[str, Any]:
    ks = jax.random.split(rng, 4)
    q_dim, kv_dim = n_heads * head_dim, n_kv_heads * head_dim
    return {
        "wq": dense_init(ks[0], d_model, q_dim, dtype, stack),
        "wk": dense_init(ks[1], d_model, kv_dim, dtype, stack),
        "wv": dense_init(ks[2], d_model, kv_dim, dtype, stack),
        "wo": dense_init(ks[3], q_dim, d_model, dtype, stack),
    }


def _split_heads(x: jnp.ndarray, n_heads: int, head_dim: int) -> jnp.ndarray:
    return x.reshape(*x.shape[:-1], n_heads, head_dim)


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool, window: int = 0,
                  q_offset: Any = 0,
                  kv_valid_len: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Pure-jnp attention oracle.

    q: (B, Tq, H, D); k, v: (B, Tk, KV, D). ``q_offset`` positions queries
    within the kv axis (decode: Tq=1, q_offset=pos). ``kv_valid_len`` masks
    cache slots >= length. ``window`` > 0 limits lookback (sliding window).
    """
    B, Tq, H, D = q.shape
    Tk, KV = k.shape[1], k.shape[2]
    k = _repeat_kv(k, H // KV)
    v = _repeat_kv(v, H // KV)
    scale = D ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(Tq)[:, None] + q_offset            # (Tq, 1)
    k_pos = jnp.arange(Tk)[None, :]                        # (1, Tk)
    valid = jnp.broadcast_to(jnp.ones((), bool), (Tq, Tk))
    if causal:
        valid = valid & (k_pos <= q_pos)
    if window:
        valid = valid & (k_pos > q_pos - window)
    if kv_valid_len is not None:
        # (B,) valid lengths -> (B, 1, 1, Tk)
        lv = jnp.arange(Tk)[None, :] < kv_valid_len[:, None]
        scores = jnp.where(lv[:, None, None, :], scores, NEG_INF)
    scores = jnp.where(valid[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


CHUNK_THRESHOLD = 8192  # q-chunk the jnp path beyond this (memory: O(T*chunk))


def attention_chunked(q, k, v, *, causal, window, chunk: int = 1024):
    """Memory-efficient jnp attention: scores materialized per q-chunk only
    (the XLA-path analogue of flash tiling; the Pallas kernel is the TPU
    fast path)."""
    B, T, H, D = q.shape
    pad = (-T) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nch = q.shape[1] // chunk
    qs = jnp.moveaxis(q.reshape(B, nch, chunk, H, D), 1, 0)
    offs = jnp.arange(nch) * chunk

    def one(args):
        qc, off = args
        return attention_ref(qc, k, v, causal=causal, window=window,
                             q_offset=off)

    from repro.models.common import scan_unroll
    _, outs = jax.lax.scan(lambda c, x: (c, one(x)), None, (qs, offs),
                           unroll=scan_unroll())
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nch * chunk, H, D)
    return out[:, :T]


def _attention(q, k, v, *, causal, window, use_pallas):
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal, window=window)
    if q.shape[1] >= CHUNK_THRESHOLD and q.shape[1] == k.shape[1]:
        return attention_chunked(q, k, v, causal=causal, window=window)
    return attention_ref(q, k, v, causal=causal, window=window)


@jax.named_scope("attention")
def self_attention(p: Dict[str, Any], h: jnp.ndarray, *,
                   n_heads: int, n_kv_heads: int, head_dim: int,
                   rope_theta: float, causal: bool = True, window: int = 0,
                   positions: Optional[jnp.ndarray] = None,
                   use_pallas: bool = False,
                   return_kv: bool = False):
    """Full-sequence self attention (train / prefill)."""
    B, T, _ = h.shape
    q = _split_heads(linear(h, p["wq"]), n_heads, head_dim)
    k = _split_heads(linear(h, p["wk"]), n_kv_heads, head_dim)
    v = _split_heads(linear(h, p["wv"]), n_kv_heads, head_dim)
    if rope_theta:
        pos = jnp.arange(T) if positions is None else positions
        q = apply_rope(q, pos, rope_theta)
        k = apply_rope(k, pos, rope_theta)
    q = shard_act(q, ("batch", "seq", "heads", None))
    k = shard_act(k, ("batch", "seq", "kv_heads", None))
    v = shard_act(v, ("batch", "seq", "kv_heads", None))
    out = _attention(q, k, v, causal=causal, window=window, use_pallas=use_pallas)
    out = linear(out.reshape(B, T, -1), p["wo"])
    if return_kv:
        return out, (k, v)
    return out


def cross_attention(p: Dict[str, Any], h: jnp.ndarray, memory: jnp.ndarray, *,
                    n_heads: int, n_kv_heads: int, head_dim: int,
                    use_pallas: bool = False,
                    memory_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                    return_kv: bool = False):
    """Cross attention over an encoder/image memory (non-causal)."""
    B, T, _ = h.shape
    q = _split_heads(linear(h, p["wq"]), n_heads, head_dim)
    if memory_kv is None:
        k = _split_heads(linear(memory, p["wk"]), n_kv_heads, head_dim)
        v = _split_heads(linear(memory, p["wv"]), n_kv_heads, head_dim)
    else:
        k, v = memory_kv
    out = _attention(q, k, v, causal=False, window=0, use_pallas=use_pallas)
    out = linear(out.reshape(B, T, -1), p["wo"])
    if return_kv:
        return out, (k, v)
    return out


# ---------------------------------------------------------------------------
# Decode (single new token against a KV cache)
# ---------------------------------------------------------------------------


def decode_self_attention(p: Dict[str, Any], h: jnp.ndarray,
                          cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                          pos: jnp.ndarray, *,
                          n_heads: int, n_kv_heads: int, head_dim: int,
                          rope_theta: float, window: int = 0):
    """h: (B, 1, d); cache_k/v: (B, S, KV, D); pos: scalar int32 — the index
    of the new token. Returns (out, (cache_k, cache_v)) with the new KV
    written at ``pos`` (ring-buffered modulo S for sliding windows)."""
    B = h.shape[0]
    S = cache_k.shape[1]
    q = _split_heads(linear(h, p["wq"]), n_heads, head_dim)
    k_new = _split_heads(linear(h, p["wk"]), n_kv_heads, head_dim)
    v_new = _split_heads(linear(h, p["wv"]), n_kv_heads, head_dim)
    if rope_theta:
        pvec = jnp.full((1,), 0, jnp.int32) + pos
        q = apply_rope(q, pvec, rope_theta)
        k_new = apply_rope(k_new, pvec, rope_theta)
    slot = jnp.mod(pos, S) if window else jnp.minimum(pos, S - 1)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new.astype(cache_k.dtype), slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new.astype(cache_v.dtype), slot, axis=1)
    cache_k = shard_act(cache_k, ("batch", "kv_seq", None, None))
    cache_v = shard_act(cache_v, ("batch", "kv_seq", None, None))
    k = _repeat_kv(cache_k, n_heads // n_kv_heads)
    v = _repeat_kv(cache_v, n_heads // n_kv_heads)
    scale = head_dim ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale      # (B, H, 1, S)
    k_idx = jnp.arange(S)
    if window:
        # ring buffer: valid slots are the last min(pos+1, window) writes
        age = jnp.mod(pos - k_idx, S)                        # steps since write
        valid = jnp.where(pos >= S, age < window, (k_idx <= pos) & (age < window))
    else:
        valid = k_idx <= jnp.minimum(pos, S - 1)
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32)).astype(h.dtype)
    out = linear(out.reshape(B, 1, -1), p["wo"])
    return out, (cache_k, cache_v)


def decode_cross_attention(p: Dict[str, Any], h: jnp.ndarray,
                           mem_k: jnp.ndarray, mem_v: jnp.ndarray, *,
                           n_heads: int, n_kv_heads: int, head_dim: int):
    """Decode-time cross attention over a precomputed memory KV."""
    B = h.shape[0]
    q = _split_heads(linear(h, p["wq"]), n_heads, head_dim)
    out = attention_ref(q, mem_k, mem_v, causal=False)
    return linear(out.reshape(B, 1, -1), p["wo"])


def init_kv_cache(batch: int, seq_len: int, n_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16, window: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sliding-window layers only need ``window`` slots (ring buffer)."""
    S = min(seq_len, window) if window else seq_len
    shape = (batch, S, n_kv_heads, head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
