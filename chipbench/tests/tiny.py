"""Tiny-width cells and planted faults for the CPU tests.

A tiny cell is a cell's own files (configuration, traffic, workload and
limits) with the widths, depth and sequence cut so that XLA's CPU backend
runs it in seconds.  The pipeline cell keeps float32 activations here: XLA's
CPU compiler aborts on its bfloat16 backward.
"""
from __future__ import annotations

import contextlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

from chipbench import run  # noqa: E402

TINY = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=256,
            vocab_size=512, max_position=32)
FAULTS = {"fit": ("frozen", "half_batch", "doubled_update"),
          "pipeline": ("frozen", "half_batch", "no_exchange",
                       "doubled_update")}


# the cells' files, read without BENCHMARK.json, so a cell kept in the
# directory but not listed there is still tested
CELLS = {"gpt2b.fit-b8s1k": ("gpt-2b-L6", "b8s1k", 1),
         "gpt2b-pp.b16mb8s1k": ("gpt-2b-L12-pp2tp2", "b16mb8s1k", 4)}


def tiny_spec(name: str) -> dict:
    config, mix, chips = CELLS[name]
    read = lambda p: json.loads((ROOT / "chipbench" / p).read_text())
    spec = {"name": name, "chips": chips,
            "config": read(f"configs/{config}.json"),
            "traffic": read(f"traffic/{mix}.json"),
            "workload": read(f"workloads/{name}.json"),
            "end_to_end": json.loads(
                (ROOT / "BENCHMARK.json").read_text())["end_to_end"],
            "per_layer": []}
    spec["config"]["arch"].update(TINY)
    spec["config"]["precision"]["activations"] = "float32"
    spec["traffic"]["seq_len"] = TINY["max_position"]
    return spec


def run_tiny(name: str, seed: int = 2 ** 31 + 5, seconds: float = 0.2):
    return run.run_cell(tiny_spec(name), jax.devices(), seed=seed,
                        seconds=seconds, trace_on=False)


def _wrap_fit(step_fn, kind):
    def step(params, opt_state, batch):
        if kind == "half_batch":
            batch = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        new_p, new_o, m = step_fn(params, opt_state, batch)
        if kind == "frozen":
            return params, opt_state, m
        if kind == "doubled_update":
            new_p = dict(new_p, lm_head=2 * new_p["lm_head"] - params["lm_head"])
        return new_p, new_o, m
    return step


def _wrap_pipeline(step_fn, kind):
    def step(staged, shared, consts, opt_state, batch):
        if kind == "half_batch":
            batch = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        new_st, new_sh, new_o, m = step_fn(staged, shared, consts, opt_state,
                                           batch)
        if kind == "frozen":
            return staged, shared, opt_state, m
        if kind == "doubled_update":
            new_sh = dict(new_sh,
                          lm_head=2 * new_sh["lm_head"] - shared["lm_head"])
        return new_st, new_sh, new_o, m
    return step


@contextlib.contextmanager
def planted(kind: str):
    """The program's step, broken underneath the harness."""
    import repro.train.step as step_mod
    saved = (step_mod.make_train_step, step_mod.make_pipeline_train_step,
             jax.lax.ppermute)
    make_fit, make_pipe, _ = saved

    def fit(*a, **k):
        step_fn, *rest = make_fit(*a, **k)
        return (_wrap_fit(step_fn, kind), *rest)

    def pipe(*a, **k):
        step_fn, *rest = make_pipe(*a, **k)
        return (_wrap_pipeline(step_fn, kind), *rest)

    step_mod.make_train_step, step_mod.make_pipeline_train_step = fit, pipe
    if kind == "no_exchange":
        jax.lax.ppermute = lambda x, axis_name, perm: x
    try:
        yield
    finally:
        (step_mod.make_train_step, step_mod.make_pipeline_train_step,
         jax.lax.ppermute) = saved


def main() -> None:
    """Run a tiny cell clean and under each fault; print one JSON line."""
    name = sys.argv[1]
    entry = tiny_spec(name)["workload"]["entry"]
    out = {"clean": run_tiny(name)}
    for kind in FAULTS[entry]:
        with planted(kind):
            out[kind] = run_tiny(name)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
