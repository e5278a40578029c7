"""SPMD pipeline parallelism over the multi-pod mesh's ``pod`` axis.

The paper's rule 1 confines intra-op parallelism (DP/TP) inside a pod; the
``pod`` axis carries only inter-op (pipeline) traffic — microbatch activation
``collective-permute``s, the TPU-idiomatic equivalent of HAPT's cross-cluster
P2P sends.

Mechanics (collective-permute pipelining):
  - every stage's parameters are stacked along a leading stage dim sharded
    over ``pod``; ``shard_map`` is *manual* over ``pod`` only, with ``data``/
    ``model`` staying auto (GSPMD does DP/TP inside the stage body);
  - a ``lax.scan`` runs ``n_microbatches + S - 1`` slots; each slot the stage
    applies its layers to the activation it holds and ``ppermute``s the
    result to the next stage;
  - the first model layer swaps in the next microbatch's embedded input (a
    per-layer flag, so the mechanism is family-agnostic); the CE loss is
    computed at every stage but masked to the last (head redundancy is S-1/S
    of one matmul — measured in EXPERIMENTS.md);
  - slots are remat'd (``jax.checkpoint``), so live memory = in-flight
    activations, matching the planner's Eq. 18 accounting.

Backward is reverse-mode through the scan: ppermute transposes to the
reverse permute, giving a GPipe-schedule backward.  The H-1F1B warm-up-depth
schedule itself is modeled and proven in ``core/`` (pipesim) and drives the
planner's memory bound; XLA's async collective-permute pairs provide the
overlap on hardware.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_loss_fn(spec, mesh, n_microbatches: int, stage_axis: str = "pod"):
    """Build ``loss(staged, shared, consts, batch) -> (loss, metrics)``.

    ``spec`` is a family staging (see ``parallel/staging.py``) providing
    make_io / stage_fn / head_loss / zero_carry.  Only those callables are
    kept, never ``spec`` itself: it holds the unplaced parameter arrays,
    which the caller frees once it has placed them."""
    S = spec.n_stages
    n_mb = n_microbatches
    make_io, stage_fn = spec.make_io, spec.stage_fn
    head_loss, zero_carry = spec.head_loss, spec.zero_carry

    def inner(staged_local, consts_local, shared, io):
        staged1 = jax.tree.map(lambda x: x[0], staged_local)
        consts1 = jax.tree.map(lambda x: x[0], consts_local)
        sidx = jax.lax.axis_index(stage_axis)
        is_last = (sidx == S - 1).astype(jnp.float32)
        carry0 = jax.tree.map(
            lambda x: jax.lax.pcast(x, (stage_axis,), to="varying"),
            zero_carry(io))
        perm = [(i, i + 1) for i in range(S - 1)]

        def step(carry, t):
            io_t = jax.tree.map(lambda a: a[jnp.clip(t, 0, n_mb - 1)], io)
            carry = stage_fn(staged1, consts1, shared, carry, io_t)
            out_idx = jnp.clip(t - (S - 1), 0, n_mb - 1)
            io_out = jax.tree.map(lambda a: a[out_idx], io)
            ce_sum, ntok, aux = head_loss(shared, carry, io_out)
            valid = jnp.asarray(t >= S - 1, jnp.float32) * is_last
            if S > 1:
                carry = jax.tree.map(
                    lambda x: jax.lax.ppermute(x, stage_axis, perm), carry)
            return carry, (ce_sum * valid, ntok * valid, aux * valid)

        from repro.models.common import scan_unroll
        step = jax.checkpoint(step)
        _, (ce, tok, aux) = jax.lax.scan(step, carry0,
                                         jnp.arange(n_mb + S - 1),
                                         unroll=scan_unroll())
        return (jnp.sum(ce)[None], jnp.sum(tok)[None], jnp.sum(aux)[None])

    smapped = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(stage_axis), P(stage_axis), P(), P()),
        out_specs=(P(stage_axis), P(stage_axis), P(stage_axis)),
        axis_names={stage_axis})

    def loss_fn(staged, shared, consts, batch):
        io = make_io(shared, batch, n_mb)
        ce_v, tok_v, aux_v = smapped(staged, consts, shared, io)
        tokens = jnp.maximum(jnp.sum(tok_v), 1.0)
        ce = jnp.sum(ce_v) / tokens
        aux = jnp.sum(aux_v) / n_mb
        return ce + aux, {"loss": ce, "aux_loss": aux, "tokens": tokens}

    return loss_fn
