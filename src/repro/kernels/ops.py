"""Jit'd public wrappers around the Pallas kernels.

Handles: model<->kernel layout transposes, padding to block multiples,
GQA gradient reduction, custom_vjp wiring, and interpret-mode dispatch
(``interpret=None`` -> auto: Python interpretation of the kernel body on
non-TPU backends, compiled Mosaic on TPU).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ssd


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


# ---------------------------------------------------------------------------
# Tuned-block registry
#
# ``repro.kbench.autotune`` sweeps tiling grids per (device, op, shape) and
# installs the winners here; entry points called with block sizes of ``None``
# resolve through this table (exact shape, else nearest same-rank shape by
# log-distance) and fall back to the defaults when nothing is installed.
# Shape keys per op: flash_attention (B, T, S, H, KV, D); rmsnorm (rows, D).
# ---------------------------------------------------------------------------

_TUNED_BLOCKS: dict = {}


def set_tuned_blocks(op: str, shape, blocks) -> None:
    _TUNED_BLOCKS.setdefault(op, {})[tuple(int(d) for d in shape)] = tuple(
        int(b) for b in blocks)


def clear_tuned_blocks(op: Optional[str] = None) -> None:
    if op is None:
        _TUNED_BLOCKS.clear()
    else:
        _TUNED_BLOCKS.pop(op, None)


def tuned_blocks(op: str, shape):
    """Best-known block config for ``op`` at ``shape`` (None if untuned)."""
    entries = _TUNED_BLOCKS.get(op)
    if not entries:
        return None
    shape = tuple(int(d) for d in shape)
    hit = entries.get(shape)
    if hit is not None:
        return hit
    same_rank = [s for s in entries if len(s) == len(shape)]
    if not same_rank:
        return None

    def dist(s):
        return sum(abs(math.log2(max(a, 1)) - math.log2(max(b, 1)))
                   for a, b in zip(s, shape))

    best = min(same_rank, key=lambda s: (dist(s), s))
    return entries[best]


# ---------------------------------------------------------------------------
# Flash attention (custom_vjp)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret)
    return out


def _kernel_layout(x: jnp.ndarray) -> jnp.ndarray:
    """Model layout (B, T, H, D) <-> kernel layout (B, H, T, D)."""
    return jnp.transpose(x, (0, 2, 1, 3))


def _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    ot, lse = _fa.flash_attention_fwd(
        _kernel_layout(q), _kernel_layout(k), _kernel_layout(v),
        causal=causal, window=window, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return _kernel_layout(ot), (q, k, v, ot, lse)


def _flash_bwd(causal, window, block_q, block_k, interpret, res, g):
    q, k, v, ot, lse = res
    B, T, H, D = q.shape
    S, KV = k.shape[1], k.shape[2]
    dq, dk, dv = _fa.flash_attention_bwd(
        _kernel_layout(q), _kernel_layout(k), _kernel_layout(v), ot, lse,
        _kernel_layout(g), causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret)
    # reduce expanded heads back to KV groups
    dk = dk.reshape(B, KV, H // KV, S, D).sum(axis=2)
    dv = dv.reshape(B, KV, H // KV, S, D).sum(axis=2)
    return (_kernel_layout(dq).astype(q.dtype),
            _kernel_layout(dk).astype(k.dtype),
            _kernel_layout(dv).astype(v.dtype))


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Flash attention in model layout. q: (B, T, H, D); k, v: (B, S, KV, D).

    ``block_q``/``block_k`` of None resolve through the tuned-block registry
    (see ``set_tuned_blocks``) and default to 128 when untuned."""
    if block_q is None or block_k is None:
        B, T, H, D = q.shape
        S, KV = k.shape[1], k.shape[2]
        tuned = tuned_blocks("flash_attention", (B, T, S, H, KV, D))
        tq, tk = tuned if tuned else (128, 128)
        block_q = tq if block_q is None else block_q
        block_k = tk if block_k is None else block_k
    return _flash(q, k, v, causal, window, block_q, block_k,
                  _auto_interpret(interpret))


# ---------------------------------------------------------------------------
# SSD intra-chunk
# ---------------------------------------------------------------------------


def ssd_intra(xc, dtc, cum, Bc, Cc, *, interpret: Optional[bool] = None):
    """Differentiable via recomputation (the term is a closed-form polynomial
    of its inputs; jax.grad falls back to the jnp oracle under the hood)."""
    interpret = _auto_interpret(interpret)

    @jax.custom_vjp
    def call(xc, dtc, cum, Bc, Cc):
        return _ssd.ssd_intra(xc, dtc, cum, Bc, Cc, interpret=interpret)

    def fwd(xc, dtc, cum, Bc, Cc):
        return call(xc, dtc, cum, Bc, Cc), (xc, dtc, cum, Bc, Cc)

    def bwd(res, g):
        from repro.kernels.ref import ssd_intra_oracle
        _, vjp = jax.vjp(ssd_intra_oracle, *res)
        return vjp(g)

    call.defvjp(fwd, bwd)
    return call(xc, dtc, cum, Bc, Cc)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rmsnorm(x, w, *, eps: float = 1e-6, block_rows: Optional[int] = None,
            interpret: Optional[bool] = None):
    """x: (..., D) any leading dims; w: (D,).

    ``block_rows`` of None resolves through the tuned-block registry.  Either
    way the block is rounded down to a multiple of 8 (Mosaic tiles rows in
    sublanes of 8), capped at the row count rounded up to 8, and the rows are
    zero-padded to a whole number of blocks."""
    interpret = _auto_interpret(interpret)
    shape = x.shape
    rows = math.prod(shape[:-1])
    if block_rows is None:
        tuned = tuned_blocks("rmsnorm", (rows, shape[-1]))
        block_rows = tuned[0] if tuned else 128
    block = min(max(8, block_rows - block_rows % 8), rows + (-rows) % 8)
    x2 = jnp.pad(x.reshape(rows, shape[-1]), ((0, (-rows) % block), (0, 0)))
    out = _rn.rmsnorm(x2, w, eps=eps, block_rows=block, interpret=interpret)
    return out[:rows].reshape(shape)
