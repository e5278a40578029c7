"""Optimizers (AdamW, Adafactor-lite) as pure (init, update) pairs with
dtype-configurable state — no external deps.

States inherit the parameter sharding (FSDP'd over ``data``, TP dims over
``model``) so optimizer memory scales with 1/n_devices — the ZeRO-1 trick
the planner's Eq. 18 memory model assumes.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    state_dtype: Any = jnp.float32    # bf16 halves optimizer memory
    master_weights: bool = False      # params bf16 + f32 master in the state
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1


class OptState(NamedTuple):
    step: jnp.ndarray
    mu: Any
    nu: Any
    master: Any = None                # f32 master copy (master_weights mode)


def lr_schedule(cfg: OptimizerConfig, step: jnp.ndarray) -> jnp.ndarray:
    """Linear warmup + cosine decay."""
    warm = jnp.minimum(1.0, (step + 1) / max(cfg.warmup_steps, 1))
    prog = jnp.clip((step - cfg.warmup_steps) /
                    max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    frac = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos
    return cfg.lr * warm * frac


def global_norm(tree) -> jnp.ndarray:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def make_adamw(cfg: OptimizerConfig):
    def init(params):
        zeros = lambda p: jnp.zeros(p.shape, cfg.state_dtype)
        master = (jax.tree.map(lambda p: p.astype(jnp.float32), params)
                  if cfg.master_weights else None)
        return OptState(jnp.zeros((), jnp.int32),
                        jax.tree.map(zeros, params),
                        jax.tree.map(zeros, params), master)

    @jax.named_scope("optimizer")
    def update(grads, state: OptState, params):
        step = state.step + 1
        gn = global_norm(grads)
        scale = jnp.minimum(1.0, cfg.grad_clip / (gn + 1e-9)) \
            if cfg.grad_clip else jnp.float32(1.0)
        lr = lr_schedule(cfg, state.step)
        b1, b2 = cfg.beta1, cfg.beta2
        c1 = 1 - b1 ** step.astype(jnp.float32)
        c2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(g, m, v, p_master):
            g = g.astype(jnp.float32) * scale
            m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g
            v32 = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g)
            mhat = m32 / c1
            vhat = v32 / c2
            delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
            if p_master.ndim >= 2 and cfg.weight_decay:  # none on norms
                delta = delta + cfg.weight_decay * p_master.astype(jnp.float32)
            new_master = p_master.astype(jnp.float32) - lr * delta
            return (new_master, m32.astype(cfg.state_dtype),
                    v32.astype(cfg.state_dtype))

        source = state.master if cfg.master_weights else params
        out = jax.tree.map(upd, grads, state.mu, state.nu, source)
        first = lambda t: t[0]
        is_t = lambda t: isinstance(t, tuple)
        new_master = jax.tree.map(first, out, is_leaf=is_t)
        new_m = jax.tree.map(lambda t: t[1], out, is_leaf=is_t)
        new_v = jax.tree.map(lambda t: t[2], out, is_leaf=is_t)
        if cfg.master_weights:
            new_p = jax.tree.map(lambda mstr, p: mstr.astype(p.dtype),
                                 new_master, params)
            return new_p, OptState(step, new_m, new_v, new_master),                 {"grad_norm": gn, "lr": lr}
        new_p = jax.tree.map(lambda mstr, p: mstr.astype(p.dtype),
                             new_master, params)
        return new_p, OptState(step, new_m, new_v), {"grad_norm": gn, "lr": lr}

    return init, update


def make_optimizer(cfg: OptimizerConfig):
    if cfg.name == "adamw":
        return make_adamw(cfg)
    raise ValueError(f"unknown optimizer {cfg.name!r}")
