"""Chip benchmark of the training paths: one cell per run.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration (``chipbench/configs/<config>.json``), traffic
(``chipbench/traffic/<traffic>.json``), entry and limits
(``chipbench/workloads/<name>.json``, ``chipbench/entries/<entry>.py``) and
per-layer metric readers (``chipbench/metrics/<metric>.py``) are files of
their own, found by name.

A run: make the weights on the device from ``--seed``, compile (or load
from the compile cache), and run the first steps, which the comparison
reads; that is set-up.  Then ``--trace 0`` times a window of about
``--seconds`` seconds of steps on the host clock, and ``--trace 1`` traces
a few steps with the profiler and reduces the trace to the per-layer
metrics.  Once the window has closed and the program's state is freed, the
plain reference trains from the same weights on the same batches, and the
gaps between the two decide ``correct``.  The last line of stdout is one
JSON object; the numbers compared, each beside its limit, are the last
lines of stderr and the result's last key.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CHECK_STEPS = 3


def load_cell(root: Path, name: str) -> dict:
    """Everything a cell names, read from its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    read = lambda p: json.loads((root / p).read_text())
    reports = {m["name"] for m in bench["end_to_end"]
               if name in m.get("workloads", [name])}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in reports]
    return {"name": name, "chips": cell["chips"],
            "config": read(conf["file"]),
            "traffic": read(f"chipbench/traffic/{cell['traffic']}.json"),
            "workload": read(f"chipbench/workloads/{name}.json"),
            "end_to_end": [m for m in bench["end_to_end"]
                           if m["name"] in reports],
            "per_layer": per_layer}


def require_chips(n: int) -> list:
    """The TPU devices, or exit: never a CPU number under a device name."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU; JAX found "
                         f"{devices[0].platform}")
    if len(devices) < n:
        raise SystemExit(f"chipbench: the cell needs {n} chips; JAX sees "
                         f"{len(devices)}")
    return devices


def _traced(runner, steps: int):
    """Run ``steps`` under the profiler; the reduced trace and its window."""
    import jax
    from chipbench.lib import trace
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        with jax.profiler.trace(tmp):
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                runner.run(steps)
        tr = trace.load(glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")[0])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    lo, hi = tr.window()
    return tr, lo, hi


def _memory_peak(devices) -> int:
    """The fullest chip's peak: its arrays' peak plus the peak that the TPU
    runtime reserved for the programs' temporaries, which
    ``peak_bytes_in_use`` leaves out."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0)
                     + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def release_programs() -> None:
    """Unload every compiled program, so that the memory the runtime keeps
    reserved for their temporaries is free for the reference."""
    import gc
    import jax
    jax.clear_caches()
    gc.collect()


def run_cell(spec: dict, devices: list, *, seed: int, seconds: float,
             trace_on: bool) -> dict:
    """One run of a cell on ``devices``; returns the result object."""
    import jax
    from chipbench.lib import compare, flops, peaks, trace, traffic

    config, traffic_mix, workload = (spec["config"], spec["traffic"],
                                     spec["workload"])
    ref = importlib.import_module(
        f"chipbench.references.{config['reference']}")
    used = devices[:spec["chips"]]
    entry = importlib.import_module(f"chipbench.entries.{workload['entry']}")
    runner = entry.Entry(config, traffic_mix, used)
    runner.load(seed)
    prog = runner.check_steps(CHECK_STEPS)
    setup_s = time.perf_counter() - T0

    metrics, device = {}, {}
    if trace_on:
        steps = workload["trace_steps"]
        tr, lo, hi = _traced(runner, steps)
        ctx = types.SimpleNamespace(
            trace=tr, lo=lo, hi=hi, chips=len(used),
            tokens=steps * runner.tokens_per_step,
            flops_per_token=flops.train_flops_per_token(
                config["arch"], traffic_mix["seq_len"]),
            peak=peaks.peak(used[0].device_kind))
        for m in spec["per_layer"]:
            reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = {"busy_s": trace.mean_busy_s(tr, lo, hi),
                  "window_s": (hi - lo) / 1e9}
        breakdown = {"device_ops": trace.top_ops(tr, lo, hi),
                     "idle_gaps": trace.idle_gaps(tr, lo, hi)}
    else:
        steps = max(1, round(seconds / prog["step_s"]))
        t0 = time.perf_counter()
        runner.run(steps)
        window_s = time.perf_counter() - t0
        values = {"tokens_per_s": steps * runner.tokens_per_step / window_s,
                  "setup_s": setup_s}
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    memory_peak = _memory_peak(used)
    runner.free()
    del runner
    release_programs()

    batches = [traffic.batch(seed, k, rows=traffic_mix["batch"],
                             seq_len=traffic_mix["seq_len"],
                             vocab=config["arch"]["vocab_size"])
               for k in range(CHECK_STEPS)]
    reference = ref.Reference(config["arch"], config["optimizer"], used,
                              workload["reference_rows_per_block"])
    want = reference.train(seed, batches)
    verdict = compare.judge(compare.gaps(prog, want), workload["limits"])

    result = {"correct": verdict["correct"], "attempted": steps, "failed": 0,
              "metrics": metrics,
              "device": {"platform": used[0].platform,
                         "kind": used[0].device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": memory_peak, **device}}
    if trace_on:
        result["breakdown"] = breakdown
    result["checks"] = verdict["checks"]
    return result


def emit(result: dict) -> None:
    """The compared numbers on stderr, then the result line on stdout."""
    for name, c in result["checks"].items():
        ok = math.isfinite(c["value"]) and c["value"] <= c["limit"]
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr, flush=True)
    for c in result["checks"].values():
        if not math.isfinite(c["value"]):
            c["value"] = None      # JSON has no infinity
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("chipbench: --seed must be non-negative")
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"chipbench: no program under {ROOT / 'src'}")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    spec = load_cell(ROOT, args.workload)
    devices = require_chips(spec["chips"])

    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program of a run, however quick to compile, is served from the
    # cache in the next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    emit(run_cell(spec, devices, seed=args.seed, seconds=args.seconds,
                  trace_on=bool(args.trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
