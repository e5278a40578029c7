"""Observability benchmark -> ``BENCH_obs.json`` at repo root.

Two cases, both gated under ``--fail-on-regression``:

- **trace_export**: lower one compiled plan's simulation through the
  ``repro.obs`` span model and export Chrome-trace JSON, twice.  Gates:

  1. **adapter exactness** — per-stage compute-span duration sums equal
     ``SimResult.stage_compute`` bit for bit and the comm-span sum equals
     ``comm_total`` (the whole point of lowering instead of
     re-simulating);
  2. **byte determinism** — both exports are byte-identical;
  3. **bounded overhead** — lower + export wall stays under an absolute
     budget (tracing must never cost more than the simulation it
     describes is worth).

- **drift_detection**: feed a :class:`repro.obs.DriftLedger` the plan's
  own prediction, then (a) faithful samples and (b) samples with a 20%
  uniform slowdown.  Gates: the clean run is *not* flagged, the slowed
  run *is*, and the slowdown is attributed to every hosting pool.

``--tiny`` shrinks the export round-trip count to CI size.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import tempfile                                               # noqa: E402

from benchmarks.common import emit_csv                        # noqa: E402

from repro import api                                         # noqa: E402
from repro.core.cluster import paper_case_study_cluster       # noqa: E402
from repro.core.pipesim import plain_sum                      # noqa: E402
from repro.core.planner import PlannerConfig                  # noqa: E402
from repro.obs import DriftLedger, trace_from_sim, trace_to_chrome  # noqa: E402

BENCH_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_obs.json")

ARCH = "gpt-2b"
SEQ_LEN = 512
GLOBAL_BATCH = 16
EXPORT_BUDGET_S = 5.0        # absolute wall budget per lower+export round


def _compile():
    cfg = api.HarpConfig(
        seq_len=SEQ_LEN, global_batch=GLOBAL_BATCH,
        planner=PlannerConfig(granularity=16, n_microbatches=16))
    return api.compile(ARCH, paper_case_study_cluster(), cfg)


def trace_export_case(exe, rounds: int) -> Dict:
    res = exe.simulate(priced=False)
    t0 = time.perf_counter()
    paths: List[str] = []
    with tempfile.TemporaryDirectory() as d:
        for k in range(rounds):
            tr = trace_from_sim(res, name=ARCH)
            p = os.path.join(d, f"t{k}.json")
            trace_to_chrome(tr, p)
            paths.append(p)
        blobs = [open(p, "rb").read() for p in paths]
    wall = time.perf_counter() - t0

    tr = trace_from_sim(res, name=ARCH)
    compute = [s for s in tr.spans if s.cat == "compute"]
    exact = all(
        plain_sum(s.dur for s in compute if s.args["stage"] == i) == expected
        for i, expected in enumerate(res.stage_compute))
    comm = plain_sum(s.dur for s in tr.spans
                     if s.cat == "comm" and s.args.get("kind") in ("CF", "CB"))
    return {
        "rounds": rounds,
        "n_spans": len(tr.spans),
        "export_wall_s": round(wall, 4),
        "export_wall_per_round_s": round(wall / rounds, 4),
        "adapter_exact": bool(exact and comm == res.comm_total),
        "export_deterministic": len(set(blobs)) == 1,
        "overhead_bounded": wall / rounds < EXPORT_BUDGET_S,
    }


def drift_detection_case(exe, n_steps: int) -> Dict:
    res = exe.simulate(priced=False)
    predicted = {"makespan_s": res.makespan,
                 "stage_compute_s": list(res.stage_compute)}
    pools = exe._stage_pools()

    def fold(scale: float):
        led = DriftLedger(threshold=0.15, window=8)
        led.register_plan(predicted, stage_pools=pools)
        for step in range(n_steps):
            led.observe_step(step, res.makespan * scale,
                             stage_times=[t * scale
                                          for t in res.stage_compute])
        return led.report()

    clean, slowed = fold(1.0), fold(1.2)
    return {
        "n_steps": n_steps,
        "clean_rel_error": round(clean.rel_error, 6),
        "slowed_rel_error": round(slowed.rel_error, 6),
        "slowed_flagged_pools": slowed.flagged_pools,
        "clean_not_flagged": not clean.flagged,
        "slowdown_flagged": slowed.flagged,
        "pools_attributed":
            slowed.flagged_pools == sorted(set(pools.values())),
    }


def run(tiny: bool = False, label: Optional[str] = None) -> Dict:
    rounds = 3 if tiny else 20
    n_steps = 20 if tiny else 100
    t0 = time.perf_counter()
    exe = _compile()
    cases = {
        "trace_export": trace_export_case(exe, rounds),
        "drift_detection": drift_detection_case(exe, n_steps),
    }
    cases["trace_export"]["bench_seconds"] = round(
        time.perf_counter() - t0, 3)
    return {"label": label or "HEAD",
            "mode": "tiny" if tiny else "full",
            "cases": cases}


def extend_trajectory(entry: Dict, path: str = BENCH_PATH) -> Dict:
    """Append one run to the obs trajectory (creates the file on first
    use)."""
    doc = {"schema": 1,
           "description": "Observability trajectory; one entry per "
                          "benchmarks/obs_bench.py run — see "
                          "docs/observability.md.",
           "runs": []}
    if os.path.exists(path):
        with open(path) as f:
            doc = json.load(f)
    doc["runs"].append(entry)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    return doc


def rows_from_entry(entry: Dict) -> List[Dict]:
    te = entry["cases"]["trace_export"]
    dd = entry["cases"]["drift_detection"]
    return [
        {"label": "trace_export",
         "step_time_s": te["export_wall_per_round_s"],
         "derived": f"spans={te['n_spans']};exact={te['adapter_exact']};"
                    f"deterministic={te['export_deterministic']}"},
        {"label": "drift_detection",
         "step_time_s": 0.0,
         "derived": f"slowed_rel={dd['slowed_rel_error']};"
                    f"flagged={dd['slowdown_flagged']};"
                    f"pools={dd['slowed_flagged_pools']}"},
    ]


def main() -> None:
    """benchmarks/run.py contract: full measurement, CSV on stdout, one
    trajectory entry appended to BENCH_obs.json."""
    entry = run(tiny=False)
    extend_trajectory(entry)
    emit_csv(rows_from_entry(entry))


def cli(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiny", action="store_true",
                    help="CI-sized round count")
    ap.add_argument("--label", default=None,
                    help="trajectory entry label (default HEAD)")
    ap.add_argument("--out", default=BENCH_PATH,
                    help="trajectory JSON path (default repo root)")
    ap.add_argument("--fail-on-regression", action="store_true",
                    help="exit 1 unless adapters are exact, exports are "
                         "byte-deterministic and within budget, and the "
                         "drift ledger flags the injected slowdown (and "
                         "only it)")
    args = ap.parse_args(argv)

    entry = run(tiny=args.tiny, label=args.label)
    extend_trajectory(entry, args.out)
    emit_csv(rows_from_entry(entry))
    print(f"# trajectory entry appended to {os.path.abspath(args.out)}",
          file=sys.stderr)

    bad = [name for name, c in entry["cases"].items()
           if not all(v for k, v in c.items() if isinstance(v, bool))]
    if bad:
        print(f"# obs bench regressed on: {bad}", file=sys.stderr)
        if args.fail_on_regression:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(cli())
