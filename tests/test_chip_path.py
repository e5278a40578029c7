"""CPU checks of what the chip run relies on: ``api.fit`` donates its
training state, the pipeline step lets its unplaced parameters go, and the
compile cache lands where it is told to."""
import gc
import math
import weakref

import jax
import jax.numpy as jnp
import pytest

from repro import api, compile_cache
from repro.api import facade
from repro.configs import get_config
from repro.data.pipeline import DataConfig, make_batch
from repro.launch.mesh import make_mesh
from repro.train.optimizer import OptimizerConfig
from repro.train.step import make_pipeline_train_step
from repro.train.trainer import TrainerConfig


def test_fit_donates_params_and_opt_state(monkeypatch, tmp_path):
    seen = {}
    real = facade.make_train_step

    def spy(*args, **kwargs):
        step, model, opt_init = real(*args, **kwargs)

        def init(params):
            seen["params"], seen["opt_state"] = params, opt_init(params)
            return seen["opt_state"]
        return step, model, init

    monkeypatch.setattr(facade, "make_train_step", spy)
    cfg = api.HarpConfig(seq_len=16, global_batch=2, trainer=TrainerConfig(
        total_steps=3, ckpt_dir=str(tmp_path), ckpt_every=2, log_every=1))
    res = api.fit(get_config("gpt-2b").reduced(), cfg, start_step=0,
                  log_fn=lambda *_: None)
    # the first step consumed the initial state's buffers ...
    for name in ("params", "opt_state"):
        assert all(x.is_deleted() for x in jax.tree.leaves(seen[name])), name
    # ... and training went on from the new state, checkpoint included
    assert res["final_step"] == 3
    assert [h["step"] for h in res["history"]] == [1, 2, 3]
    assert all(math.isfinite(h["loss"]) for h in res["history"])
    assert not any(x.is_deleted() for x in jax.tree.leaves(res["state"]))
    assert any(tmp_path.iterdir())


def test_pipeline_step_frees_the_unplaced_staging():
    cfg = get_config("gpt-2b").reduced()
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    step, staging, opt_init, sh = make_pipeline_train_step(
        cfg, OptimizerConfig(warmup_steps=1, total_steps=2), mesh=mesh,
        n_stages=1, n_microbatches=2, act_dtype=jnp.float32)
    staged = jax.device_put(staging.staged, sh["staged"])
    shared = jax.device_put(staging.shared, sh["shared"])
    consts = jax.device_put(staging.consts, sh["consts"])
    gone = [weakref.ref(staging)] + [
        weakref.ref(x) for x in jax.tree.leaves(
            (staging.staged, staging.shared, staging.consts))]
    del staging
    gc.collect()
    # the step kept neither the staging nor any of its unplaced arrays ...
    assert [r for r in gone if r() is not None] == []
    # ... and still trains from the placed state
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=4)
    opt_state = opt_init({"staged": staged, "shared": shared})
    with jax.set_mesh(mesh):
        *_, metrics = jax.jit(step)(staged, shared, consts, opt_state,
                                    make_batch(data, 0))
    assert math.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("env,expected", [
    ({"JAX_COMPILATION_CACHE_DIR": "/elsewhere/cache"}, "/elsewhere/cache"),
    ({}, None),                                   # None: the fixed default
    ({"JAX_COMPILATION_CACHE_DIR": ""}, None),
], ids=["env-set", "env-unset", "env-empty"])
def test_compile_cache_dir(env, expected):
    expected = expected or str(compile_cache.DEFAULT_DIR)
    assert compile_cache.compile_cache_dir(env) == expected


def test_compile_cache_default_is_fixed_and_ignored_by_git():
    root = compile_cache.DEFAULT_DIR.parent
    assert compile_cache.DEFAULT_DIR == root / ".jax_cache"
    assert (root / "src" / "repro" / "compile_cache.py").exists()
    ignored = (root / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_enable_compile_cache(monkeypatch, env_dir):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        if env_dir is None:
            assert path == str(compile_cache.DEFAULT_DIR)
            assert jax.config.jax_compilation_cache_dir == path
        else:
            # JAX reads the variable itself; nothing else is configured
            assert path == env_dir
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
