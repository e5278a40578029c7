"""CPU checks of the names the training path gives the profiler: the model's
and the optimizer's scopes in the compiled step programs, the Trainer's
host spans, and the compile counter with its marks."""
import collections
import glob
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.obs.metrics import MetricsRegistry, watch_compiles
from repro.train.trainer import TrainerConfig

SCOPES = ("embed", "attention", "mlp", "head", "optimizer")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# compiles one step program at a tiny width and prints the op_name of every
# instruction; the pipeline step runs on a (pod 2, data 1, model 2) mesh
STEP_OP_NAMES = textwrap.dedent("""
    import re, sys
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, make_batch
    from repro.launch.mesh import make_mesh
    from repro.train.optimizer import OptimizerConfig
    from repro.train.step import make_pipeline_train_step, make_train_step

    cfg = get_config("gpt-2b").reduced()
    opt = OptimizerConfig(warmup_steps=1, total_steps=2)
    batch = make_batch(DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                  global_batch=4), 0)
    if sys.argv[1] == "fit":
        step, model, opt_init = make_train_step(cfg, opt)
        params = model.init(jax.random.PRNGKey(0))
        compiled = jax.jit(step).lower(params, opt_init(params),
                                       batch).compile()
    else:
        mesh = make_mesh((2, 1, 2), ("pod", "data", "model"))
        step, staging, opt_init, sh = make_pipeline_train_step(
            cfg, opt, mesh=mesh, n_stages=2, n_microbatches=2,
            act_dtype=jnp.float32)
        staged = jax.device_put(staging.staged, sh["staged"])
        shared = jax.device_put(staging.shared, sh["shared"])
        consts = jax.device_put(staging.consts, sh["consts"])
        opt_state = opt_init({"staged": staged, "shared": shared})
        with jax.set_mesh(mesh):
            compiled = jax.jit(step).lower(staged, shared, consts, opt_state,
                                           batch).compile()
    print("\\n".join(sorted(set(re.findall(r'op_name="([^"]*)"',
                                           compiled.as_text())))))
""")


@pytest.mark.parametrize("step", ["fit", "pipeline"])
def test_compiled_step_carries_every_scope(step):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", STEP_OP_NAMES, step],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    paths = proc.stdout.splitlines()
    for scope in SCOPES:
        # a name on the path, bare or inside jvp(...) / transpose(...)
        hit = re.compile(rf"(^|[/(]){scope}([/)]|$)")
        assert any(hit.search(p) for p in paths), scope
    # the backward pass and the remat's recompute keep the scope too
    assert any(p.startswith("jit(train_step)/transpose(")
               and "/rematted_computation/attention/" in p for p in paths)


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_fit_under_the_profiler_writes_each_steps_spans(tmp_path):
    def train_step(w, batch):
        return w + jnp.mean(batch["tokens"]), {"loss": w}

    cfg = api.HarpConfig(seq_len=8, global_batch=2, trainer=TrainerConfig(
        total_steps=3, ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2,
        log_every=1))
    with jax.profiler.trace(str(tmp_path / "trace")):
        res = api.fit("gpt-2b", cfg, train_step=jax.jit(train_step),
                      state={"w": np.float32(0)}, start_step=0,
                      log_fn=lambda *_: None)
    assert res["final_step"] == 3
    events = _host_events(tmp_path / "trace")
    count = collections.Counter(n for n, _, _ in events)
    assert count["trainer.step"] == 3
    for part in ("batch", "dispatch", "sync"):
        assert count[f"trainer.{part}"] == 3, part
    assert count["trainer.checkpoint"] == 1
    steps = [(s, e) for n, s, e in events if n == "trainer.step"]
    for name, s, e in events:
        if name in ("trainer.batch", "trainer.dispatch", "trainer.sync"):
            assert any(a <= s and e <= b for a, b in steps), name
    # the step's compile happened inside the first step, and left its mark
    marks = [s for n, s, _ in events if n == "jax.backend_compiles"]
    assert any(steps[0][0] <= s <= steps[0][1] for s in marks)


def _compiles(reg):
    return reg.snapshot()["counters"].get("jax.backend_compiles", 0)


def test_watch_compiles_counts_a_new_program_once():
    reg = MetricsRegistry()
    assert watch_compiles(reg) is reg
    watch_compiles(reg)                 # registered once: no double count
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = np.ones(3, np.float32)
    f(x).block_until_ready()
    assert _compiles(reg) == 1
    f(x).block_until_ready()            # served from the jit's own cache
    assert _compiles(reg) == 1
    hist = reg.snapshot()["histograms"]["jax.backend_compile_s"]
    assert hist["count"] == 1 and hist["sum"] > 0
    assert "jax.cache_loads" not in reg.snapshot()["counters"]


CACHE_LOAD = textwrap.dedent("""
    import json, sys, tempfile
    import jax, jax.numpy as jnp
    from repro.obs.metrics import MetricsRegistry, watch_compiles
    jax.config.update("jax_compilation_cache_dir", tempfile.mkdtemp())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    reg = watch_compiles(MetricsRegistry())
    f = lambda x: jnp.sin(x) * 2
    jax.jit(f)(1.0)
    jax.clear_caches()                  # the next call loads from disk
    jax.jit(f)(1.0)
    print(json.dumps(reg.snapshot()["counters"]))
""")


def test_a_persistent_cache_load_counts_as_a_compile_and_a_load():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    proc = subprocess.run([sys.executable, "-c", CACHE_LOAD], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert json.loads(proc.stdout.splitlines()[-1]) == {
        "jax.backend_compiles": 2, "jax.cache_loads": 1}


SCOPED_CACHE = textwrap.dedent("""
    import importlib.util, json, os, sys, tempfile
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp()
    import jax
    from repro.compile_cache import enable_compile_cache
    from repro.obs.metrics import MetricsRegistry, watch_compiles
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    reg = watch_compiles(MetricsRegistry())
    SRC = '''
    import jax, jax.numpy as jnp
    def f(x, scoped):
        if not scoped:
            return jnp.sin(x) * 2
        with jax.named_scope("attention"):
            return jnp.sin(x) * 2
    '''
    def load(scoped):   # the same source, from a directory of its own
        d = tempfile.mkdtemp()
        with open(f"{d}/m.py", "w") as fh:
            fh.write(SRC)
        spec = importlib.util.spec_from_file_location("m", f"{d}/m.py")
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
        return jax.jit(lambda x: m.f(x, scoped))
    seen = []
    for scoped in (False, True, True):
        jax.clear_caches()
        text = load(scoped).lower(1.0).compile().as_text()
        seen.append([reg.snapshot()["counters"].get("jax.cache_loads", 0),
                     "attention" in text])
    print(json.dumps(seen))
""")


def test_the_cache_key_takes_in_op_names_but_no_source_path():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    proc = subprocess.run([sys.executable, "-c", SCOPED_CACHE], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    # unscoped: compiled; scoped: compiled anew, not loaded with the
    # unscoped names; scoped again from another directory: loaded
    assert json.loads(proc.stdout.splitlines()[-1]) == [
        [0, False], [0, True], [1, True]]
