"""Readings that a cell's limits are set from, on the chip, in one process.

    python3 chipbench/calibrate.py --workload <name> --seeds 1 2 3 ... \
        [--control-seeds 1 2 3] [--plant exchange] [--out FILE]

For each seed: the program's first steps through the cell's own entry
(the timed path at the timed sizes), then the plain reference, and the
gaps between the two: the lower readings.  For each control seed also: the
reference in the precision below the configuration's (the control) and the
reference with half of each batch left out, each against the reference:
upper readings.  ``--plant exchange`` runs the program with the pipeline's
``ppermute`` left out (the exchange between chips), a fault planted in the
program.  One JSON line per reading goes to stdout and, with ``--out``, to
that file.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
# the precision below the one a configuration states for its activations
CONTROL = {"float32": "bf16", "bfloat16": "fp8"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--plant", choices=("exchange",), default=None)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import run
    spec = run.load_cell(ROOT, args.workload)
    devices = run.require_chips(spec["chips"])

    import jax
    from chipbench.lib import compare, traffic
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if args.plant == "exchange":
        jax.lax.ppermute = lambda x, axis_name, perm: x

    config, mix, workload = spec["config"], spec["traffic"], spec["workload"]
    ref = importlib.import_module(
        f"chipbench.references.{config['reference']}")
    used = devices[:spec["chips"]]
    entry = importlib.import_module(f"chipbench.entries.{workload['entry']}")
    runner = entry.Entry(config, mix, used)
    rows = workload["reference_rows_per_block"]
    reference = ref.Reference(config["arch"], config["optimizer"], used, rows)
    control = ref.Reference(config["arch"], config["optimizer"], used, rows,
                            mode=CONTROL[config["precision"]["activations"]])
    out = open(args.out, "a") if args.out else None

    def emit(seed, kind, values, t0):
        line = json.dumps({"workload": args.workload, "seed": seed,
                           "kind": kind, "plant": args.plant,
                           "seconds": time.perf_counter() - t0, **values})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for seed in args.seeds:
        t0 = time.perf_counter()
        runner.load(seed)
        prog = runner.check_steps(run.CHECK_STEPS)
        runner.free()
        run.release_programs()
        batches = [traffic.batch(seed, k, rows=mix["batch"],
                                 seq_len=mix["seq_len"],
                                 vocab=config["arch"]["vocab_size"])
                   for k in range(run.CHECK_STEPS)]
        want = reference.train(seed, batches)
        emit(seed, "program", {**compare.gaps(prog, want),
                               "loss": prog["loss"].tolist(),
                               "ref_loss": want["loss"].tolist(),
                               "step_s": prog["step_s"]}, t0)
        if seed in args.control_seeds:
            t0 = time.perf_counter()
            emit(seed, "control", compare.gaps(
                control.train(seed, batches), want), t0)
            t0 = time.perf_counter()
            emit(seed, "half_batch", compare.gaps(
                reference.train(seed, batches, rows=mix["batch"] // 2),
                want), t0)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
