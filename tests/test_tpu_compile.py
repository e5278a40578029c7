"""Compile the main path's kernels and the training step for a described
TPU v5e chip (no chip attached: nothing runs, the TPU compiler only checks
block layouts, VMEM budgets and device memory).

Interpret-mode tests cannot catch a block spec Mosaic refuses; these can.
The topology is described inside a module fixture (never at import), and
the persistent compile cache is off around the compiles, because an entry
written here could not be read back without a chip.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.train.optimizer import OptimizerConfig
from repro.train.step import (
    make_pipeline_train_step, make_train_step, pipeline_state_shardings,
)

V5E_HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("B,T,H,KV,D", [(4, 1024, 32, 32, 80),    # gpt-2b
                                        (2, 1024, 32, 8, 128)])   # GQA
def test_flash_attention_fwd_bwd_compiles(one_chip, B, T, H, KV, D):
    def loss(q, k, v):
        out = ops.flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    q = _struct((B, T, H, D), jnp.bfloat16, one_chip)
    kv = _struct((B, T, KV, D), jnp.bfloat16, one_chip)
    _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)


def test_ssd_intra_compiles_at_mamba2_widths(one_chip):
    Bsz, nc, Q, H, P, N = 1, 4, 256, 80, 64, 128      # mamba2-2.7b
    f32 = jnp.float32
    _compile(lambda *a: ops.ssd_intra(*a, interpret=False),
             _struct((Bsz, nc, Q, H, P), f32, one_chip),
             _struct((Bsz, nc, Q, H), f32, one_chip),
             _struct((Bsz, nc, Q, H), f32, one_chip),
             _struct((Bsz, nc, Q, N), f32, one_chip),
             _struct((Bsz, nc, Q, N), f32, one_chip))


@pytest.mark.parametrize("lead", [(4096,), (4, 1001)])   # incl. odd rows
def test_rmsnorm_compiles(one_chip, lead):
    D = 2560
    _compile(lambda x, w: ops.rmsnorm(x, w, interpret=False),
             _struct((*lead, D), jnp.bfloat16, one_chip),
             _struct((D,), jnp.float32, one_chip))


def test_gpt2b_train_step_with_donated_state_fits_one_chip(one_chip):
    """The step ``api.fit`` runs, at gpt-2b's published widths cut to 4
    layers with batch 8 x 1024: donating params and optimizer state lets the
    outputs alias the inputs, which is what makes it fit in 16 GB."""
    cfg = dataclasses.replace(get_config("gpt-2b"), n_layers=4)
    step, model, opt_init = make_train_step(cfg, OptimizerConfig())
    on_chip = lambda t: jax.tree.map(
        lambda s: _struct(s.shape, s.dtype, one_chip), t)
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    opt_state = on_chip(jax.eval_shape(opt_init, params))
    batch = {k: _struct((8, cfg.max_position), jnp.int32, one_chip)
             for k in ("tokens", "labels")}
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt_state, batch).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert ma.alias_size_in_bytes >= 0.9 * ma.argument_size_in_bytes
    assert total < V5E_HBM_BYTES, ma


def test_gpt2b_pipeline_step_compiles_for_v5e_2x2(topo):
    """The 4-chip pipeline step at gpt-2b widths (4 layers, 2 stages x 2-way
    TP, bf16 activations — XLA's CPU compiler aborts on this backward, the
    TPU's must not), with the state placed back as it came in."""
    mesh = make_mesh((2, 1, 2), ("pod", "data", "model"), devices=topo.devices)
    cfg = dataclasses.replace(get_config("gpt-2b"), n_layers=4)
    step, staging, opt_init, sh = make_pipeline_train_step(
        cfg, OptimizerConfig(), mesh=mesh, n_stages=2, n_microbatches=4,
        abstract=True, act_dtype=jnp.bfloat16)
    opt_struct = jax.eval_shape(
        opt_init, {"staged": staging.staged, "shared": staging.shared})
    staged_sh, shared_sh, opt_sh = pipeline_state_shardings(
        mesh, sh, staging, opt_struct)
    batch = {k: jax.ShapeDtypeStruct((8, cfg.max_position), jnp.int32)
             for k in ("tokens", "labels")}
    with jax.set_mesh(mesh):
        compiled = jax.jit(
            step, in_shardings=(staged_sh, shared_sh, sh["consts"], opt_sh,
                                NamedSharding(mesh, PartitionSpec("data", None))),
            out_shardings=(staged_sh, shared_sh, opt_sh,
                           NamedSharding(mesh, PartitionSpec())),
            donate_argnums=(0, 1, 3)).lower(
                staging.staged, staging.shared, staging.consts, opt_struct,
                batch).compile()
    state_in = compiled.input_shardings[0]
    assert jax.tree.leaves(compiled.output_shardings[:3]) == jax.tree.leaves(
        state_in[:2] + (state_in[3],))
    assert "collective-permute" in compiled.as_text()
    ma = compiled.memory_analysis()
    per_device = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert per_device < V5E_HBM_BYTES, ma
