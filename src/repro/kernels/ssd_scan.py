"""Mamba-2 SSD intra-chunk Pallas TPU kernel.

The chunked SSD algorithm splits the sequence into chunks of length Q: the
cross-chunk state recurrence is linear (handled by a cheap ``lax.scan`` in
``models/ssm.py``) while the *intra-chunk* term is quadratic in Q and
dominates compute — that term is this kernel.

Per (batch, chunk, head) grid cell it computes::

    CB[q, j]  = sum_n C[q, n] * B[j, n]                      (Q x Q matmul)
    L[q, j]   = exp(cum[q] - cum[j]) for j <= q else 0       (decay matrix)
    M         = CB * L * dt[j]
    y[q, p]   = sum_j M[q, j] * x[j, p]                      (Q x P matmul)

TPU adaptation: chunk length Q defaults to 256 and the state dim N is 128 on
mamba2-2.7b, so both matmuls are MXU-aligned; x/B/C tiles are staged in VMEM
by the BlockSpecs.  The head dim is the innermost *parallel* grid axis —
there is no cross-cell state, so no scratch is needed.

Mosaic requires a block's last two dims to be multiples of (8, 128) or the
full array dims, so the wrapper moves the head axis in front of the chunk
axis: x tiles are (Q, P), and the per-position scalars dt/cum arrive as a
(1, Q) row (and cum also as a (Q, 1) column) — free reshapes of one
head-major array that spare the kernel any in-register transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_intra_kernel(x_ref, dt_ref, cum_col_ref, cum_row_ref, b_ref, c_ref,
                      y_ref, *, chunk: int):
    x = x_ref[0, 0, 0].astype(jnp.float32)             # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)           # (1, Q)
    cum_q = cum_col_ref[0, 0, 0].astype(jnp.float32)   # (Q, 1)
    cum_j = cum_row_ref[0, 0, 0].astype(jnp.float32)   # (1, Q)
    b = b_ref[0, 0].astype(jnp.float32)                # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)                # (Q, N)

    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())))        # (Q, Q)
    dec = cum_q - cum_j                                              # (Q, Q)
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    l = jnp.where(kj <= qi, jnp.exp(dec), 0.0)
    m = cb * l * dt
    y = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())))          # (Q, P)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)


def ssd_intra(xc: jnp.ndarray, dtc: jnp.ndarray, cum: jnp.ndarray,
              Bc: jnp.ndarray, Cc: jnp.ndarray, *,
              interpret: bool = False) -> jnp.ndarray:
    """Intra-chunk SSD term.

    xc: (B, nc, Q, H, P); dtc, cum: (B, nc, Q, H); Bc, Cc: (B, nc, Q, N).
    Returns (B, nc, Q, H, P) float32."""
    Bsz, nc, Q, H, P = xc.shape
    N = Bc.shape[-1]
    x_h = jnp.transpose(xc, (0, 1, 3, 2, 4))            # (B, nc, H, Q, P)
    dt_h = jnp.transpose(dtc, (0, 1, 3, 2))             # (B, nc, H, Q)
    cum_h = jnp.transpose(cum, (0, 1, 3, 2))
    row = pl.BlockSpec((1, 1, 1, 1, Q), lambda b, c, h: (b, c, h, 0, 0))
    kernel = functools.partial(_ssd_intra_kernel, chunk=Q)
    y = pl.pallas_call(
        kernel,
        grid=(Bsz, nc, H),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, c, h: (b, c, h, 0, 0)),
            row,
            pl.BlockSpec((1, 1, 1, Q, 1), lambda b, c, h: (b, c, h, 0, 0)),
            row,
            pl.BlockSpec((1, 1, Q, N), lambda b, c, h: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c, h: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, Q, P), lambda b, c, h: (b, c, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, nc, H, Q, P), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(x_h, dt_h[..., None, :], cum_h[..., None], cum_h[..., None, :], Bc, Cc)
    return jnp.transpose(y, (0, 1, 3, 2, 4))
