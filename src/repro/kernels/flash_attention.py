"""Blocked (flash) attention Pallas TPU kernels: forward + backward.

TPU adaptation notes (vs. the CUDA flash-attention algorithm):
  - Tiling targets VMEM (not shared memory): BlockSpecs stage (block_q, D) /
    (block_k, D) tiles HBM->VMEM; the online-softmax running stats live in
    VMEM scratch across the innermost (kv) grid dimension.
  - Block sizes default to 128 so the (bq, bk) score matmul and the
    (bq, D) accumulate matmul are MXU-aligned (128x128 systolic tiles).
  - The kv grid dimension is innermost ("arbitrary" semantics) so scratch
    accumulators persist across it; batch/head/q dims are parallel.
  - GQA is handled in the BlockSpec index_map (kv head = q head // rep) —
    no materialized head repetition in HBM.
  - Per-row statistics (lse, delta) travel as (B, H, T, 1) arrays: Mosaic
    requires a block's last two dims to be multiples of (8, 128) or the full
    array dims, and a (block_q, 1) tile satisfies that for any head count.

Layout: all kernels operate on (B, H, T, D) arrays (wrappers in ``ops.py``
transpose from the model's (B, T, H, D)).  Both entry points accept lengths
that are not block multiples: inputs are zero-padded here and the padded
tail is excluded by the q_len/kv_len masks.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _pad_len(x: jnp.ndarray, axis: int, block: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % block
    if not pad:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def _mask_block(iq, ik, *, causal, window, q_len, kv_len, block_q, block_k):
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = (q_pos < q_len) & (k_pos < kv_len)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window:
        mask = mask & (k_pos > q_pos - window)
    return mask


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, window: int,
                q_len: int, kv_len: int, block_q: int, block_k: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                  # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (bq, bk)
    mask = _mask_block(iq, ik, causal=causal, window=window, q_len=q_len,
                       kv_len=kv_len, block_q=block_q, block_k=block_k)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                  # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.exp(s - m_safe)                              # masked -> exp(-inf)=0
    alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_safe))

    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = alpha * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(l == 0.0, NEG_INF,
                                  m_scr[...] + jnp.log(l_safe))


def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """q: (B, H, Tq, D); k, v: (B, KV, Tk, D). Returns (out, lse) with lse
    (B, H, Tq) float32."""
    B, H, Tq, D = q.shape
    KV, Tk = k.shape[1], k.shape[2]
    rep = H // KV
    q = _pad_len(q, 2, block_q)
    k = _pad_len(k, 2, block_k)
    v = _pad_len(v, 2, block_k)
    nq, nk = q.shape[2] // block_q, k.shape[2] // block_k

    kernel = functools.partial(
        _fwd_kernel, scale=D ** -0.5, causal=causal, window=window,
        q_len=Tq, kv_len=Tk, block_q=block_q, block_k=block_k, nk=nk)

    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // rep, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, nq * block_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Tq], lse[:, :, :Tq, 0]


# ---------------------------------------------------------------------------
# Backward: dq kernel (grid over q blocks; kv innermost) and
#           dkv kernel (grid over kv blocks; q innermost)
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *,
               scale, causal, window, q_len, kv_len, block_q, block_k, nk):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                                  # (bq, 1)
    delta = delta_ref[0, 0]                              # (bq, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    mask = _mask_block(iq, ik, causal=causal, window=window, q_len=q_len,
                       kv_len=kv_len, block_q=block_q, block_k=block_k)
    lse_safe = jnp.where(lse == NEG_INF, 0.0, lse)
    p = jnp.where(mask, jnp.exp(s - lse_safe), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))   # (bq, bk)
    ds = p * (dp - delta) * scale
    dq_scr[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())))

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *,
                scale, causal, window, q_len, kv_len, block_q, block_k, nq):
    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    mask = _mask_block(iq, ik, causal=causal, window=window, q_len=q_len,
                       kv_len=kv_len, block_q=block_q, block_k=block_k)
    lse_safe = jnp.where(lse == NEG_INF, 0.0, lse)
    p = jnp.where(mask, jnp.exp(s - lse_safe), 0.0)              # (bq, bk)
    dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - delta) * scale                                # (bq, bk)
    dk_scr[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, do, *, causal: bool, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """Shapes as :func:`flash_attention_fwd` (``out``/``do`` like q, ``lse``
    (B, H, Tq)).  Returns (dq, dk, dv) with dk/dv in expanded-head layout
    (B, H, Tk, D); the ops.py wrapper reduces over GQA groups."""
    B, H, Tq, D = q.shape
    KV, Tk = k.shape[1], k.shape[2]
    rep = H // KV
    scale = D ** -0.5
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    # padded q rows are masked out, so their lse/delta values never matter
    q, do = _pad_len(q, 2, block_q), _pad_len(do, 2, block_q)
    lse = _pad_len(lse, 2, block_q)[..., None]
    delta = _pad_len(delta, 2, block_q)[..., None]
    k, v = _pad_len(k, 2, block_k), _pad_len(v, 2, block_k)
    nq, nk = q.shape[2] // block_q, k.shape[2] // block_k
    row_stat = functools.partial(pl.BlockSpec, (1, 1, block_q, 1))
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, window=window,
                          q_len=Tq, kv_len=Tk, block_q=block_q, block_k=block_k,
                          nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            row_stat(lambda b, h, iq, ik: (b, h, iq, 0)),
            row_stat(lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, window=window,
                          q_len=Tq, kv_len=Tk, block_q=block_q, block_k=block_k,
                          nq=nq),
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, ik, iq: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ik, iq: (b, h // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ik, iq: (b, h // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, ik, iq: (b, h, iq, 0)),
            row_stat(lambda b, h, ik, iq: (b, h, iq, 0)),
            row_stat(lambda b, h, ik, iq: (b, h, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ik, iq: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ik, iq: (b, h, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, k.shape[2], D), q.dtype),
            jax.ShapeDtypeStruct((B, H, k.shape[2], D), q.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq[:, :, :Tq], dk[:, :, :Tk], dv[:, :, :Tk]
