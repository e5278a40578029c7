"""Train-step builders for both execution modes.

``make_train_step``          — single-pod: DP(+FSDP) over ``data``, TP over
                               ``model``; grad-accumulated microbatching.
``make_pipeline_train_step`` — multi-pod: the paper's design — pipeline over
                               ``pod`` (slow axis), DP/TP inside each pod.
Both return jit-able pure functions plus the sharding trees the launcher
uses for ``in_shardings`` / dry-run lowering.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import build_model
from repro.models.common import activation_sharding
from repro.obs.metrics import watch_compiles
from repro.parallel import sharding as shd
from repro.parallel.pipeline import pipeline_loss_fn
from repro.parallel.staging import build_staging
from repro.train.optimizer import OptimizerConfig, OptState, make_optimizer


def batch_pspecs(batch_tree, batch_axes=("data",)) -> Any:
    """Tokens/labels (B, T) -> shard batch dim; modality stubs likewise."""
    ax = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    return jax.tree.map(lambda x: P(ax, *([None] * (len(x.shape) - 1))),
                        batch_tree)


# ---------------------------------------------------------------------------
# single-pod
# ---------------------------------------------------------------------------


def make_train_step(cfg: ArchConfig, opt_cfg: OptimizerConfig, *,
                    act_rules: Optional[Dict] = None,
                    param_dtype=jnp.float32,
                    n_microbatches: int = 1,
                    use_pallas: bool = False):
    """Returns (train_step, model, opt_init).

    train_step(params, opt_state, batch) -> (params, opt_state, metrics).
    Microbatching = grad accumulation via lax.scan (keeps activation memory
    at 1/n_mb; the DP gradient reduce happens once, after accumulation)."""
    model = build_model(cfg, param_dtype=param_dtype, use_pallas=use_pallas)
    opt_init, opt_update = make_optimizer(opt_cfg)
    rules = act_rules or shd.train_act_rules()

    def loss_fn(params, batch):
        return model.loss(params, batch)

    def train_step(params, opt_state, batch):
        with activation_sharding(rules):
            if n_microbatches == 1:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)
            else:
                mb_batch = jax.tree.map(
                    lambda x: x.reshape(n_microbatches,
                                        x.shape[0] // n_microbatches,
                                        *x.shape[1:]), batch)

                def acc_fn(carry, mb):
                    g_acc, l_acc = carry
                    (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                        params, mb)
                    g_acc = jax.tree.map(jnp.add, g_acc, g)
                    return (g_acc, l_acc + l), m

                g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                  params)
                from repro.models.common import scan_unroll
                (grads, loss_sum), ms = jax.lax.scan(
                    acc_fn, (g0, jnp.zeros((), jnp.float32)), mb_batch,
                    unroll=scan_unroll())
                grads = jax.tree.map(lambda g: g / n_microbatches, grads)
                loss = loss_sum / n_microbatches
                metrics = jax.tree.map(lambda x: jnp.mean(x), ms)
            params, opt_state, om = opt_update(grads, opt_state, params)
        return params, opt_state, {"total_loss": loss, **metrics, **om}

    return train_step, model, opt_init


def train_shardings(cfg: ArchConfig, mesh, opt_init, model,
                    param_dtype=jnp.float32):
    """(param_shardings, opt_shardings) NamedSharding trees for jit."""
    pspecs = shd.param_pspecs(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    opt_shape = jax.eval_shape(
        opt_init, jax.eval_shape(model.init, jax.random.PRNGKey(0)))

    def opt_spec(path_leaf):
        return None
    # OptState(step, mu, nu): mu/nu mirror params
    opt_shard = type(opt_shape)(
        NamedSharding(mesh, P()),
        jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
        jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs))
    return pshard, opt_shard


# ---------------------------------------------------------------------------
# multi-pod (pipeline over 'pod')
# ---------------------------------------------------------------------------


def make_pipeline_train_step(cfg: ArchConfig, opt_cfg: OptimizerConfig, *,
                             mesh, n_stages: int, n_microbatches: int,
                             act_rules: Optional[Dict] = None,
                             param_dtype=jnp.float32,
                             act_dtype=jnp.bfloat16,
                             params: Optional[Any] = None,
                             abstract: bool = False):
    """Returns (train_step, staging, opt_init, shardings dict).

    ``abstract=True`` builds the staging from ShapeDtypeStructs (dry-run —
    no allocation).  The step does not keep the staging: once its arrays
    are placed, the caller drops it (and ``params``) to free the unplaced
    copies.  JAX's compiles are counted from here on
    (``watch_compiles``): no ``api.fit`` reaches this step yet."""
    watch_compiles()
    model = build_model(cfg, param_dtype=param_dtype)
    if params is None:
        if abstract:
            params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        else:
            params = model.init(jax.random.PRNGKey(0))
    # build_staging accepts ShapeDtypeStructs: restructuring runs under
    # eval_shape (no allocation) and the callables only close over cfg
    staging = build_staging(cfg, n_stages, params, act_dtype=act_dtype)

    opt_init, opt_update = make_optimizer(opt_cfg)
    loss_fn = pipeline_loss_fn(staging, mesh, n_microbatches)
    rules = act_rules or shd.train_act_rules(multi_pod=True)

    def train_step(staged, shared, consts, opt_state, batch):
        with activation_sharding(rules):
            (loss, metrics), grads = jax.value_and_grad(
                lambda st, sh: loss_fn(st, sh, consts, batch),
                argnums=(0, 1), has_aux=True)(staged, shared)
            tree = {"staged": staged, "shared": shared}
            gtree = {"staged": grads[0], "shared": grads[1]}
            new_tree, opt_state, om = opt_update(gtree, opt_state, tree)
        return new_tree["staged"], new_tree["shared"], opt_state, \
            {"total_loss": loss, **metrics, **om}

    shardings = pipeline_shardings(staging, mesh)
    return train_step, staging, opt_init, shardings


def pipeline_shardings(staging, mesh) -> Dict[str, Any]:
    staged_specs = shd.staged_param_pspecs(staging.staged)
    shared_specs = shd.param_pspecs(staging.shared)
    consts_specs = jax.tree.map(
        lambda x: P("pod", *([None] * (len(x.shape) - 1))), staging.consts)
    to_ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t)
    return {
        "staged": to_ns(staged_specs),
        "shared": to_ns(shared_specs),
        "consts": to_ns(consts_specs),
        "staged_specs": staged_specs,
        "shared_specs": shared_specs,
        "consts_specs": consts_specs,
    }


def pipeline_state_shardings(mesh, shardings: Dict[str, Any], staging,
                             opt_struct) -> Tuple[Any, Any, OptState]:
    """Placement of the pipeline step's trainable state, every leaf's spec
    fitted to its shape: (staged, shared, optimizer-state) NamedSharding
    trees.  ``shardings`` is :func:`pipeline_shardings`' dict; ``opt_struct``
    the optimizer state (or its ``eval_shape``) over ``{"staged", "shared"}``.

    A loop that feeds each step's state to the next gives these as the
    step's ``out_shardings`` too: left free, XLA may return the state placed
    otherwise (it moved the norm weights onto ``model`` on a v5e 2x2), and
    the compiled step then refuses it."""
    def fitted(key, tree):
        return shd.fitted_shardings(mesh, shardings[key + "_specs"], tree)

    parts = ("staged", "shared")
    return (fitted("staged", staging.staged), fitted("shared", staging.shared),
            OptState(NamedSharding(mesh, P()),
                     {k: fitted(k, opt_struct.mu[k]) for k in parts},
                     {k: fitted(k, opt_struct.nu[k]) for k in parts}))
