"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per row (scaffold contract).
Strategy planning results are cached under results/bench_cache/ so re-runs
are fast; delete the cache to re-plan."""
import importlib
import sys
import time
import traceback

MODULES = [
    "benchmarks.table1_case_study",    # Table 1 + Fig. 3 case study
    "benchmarks.fig7_end_to_end",      # Fig. 7 end-to-end vs baselines
    "benchmarks.fig8_breakdown",       # Fig. 8 stage breakdown + eta
    "benchmarks.fig9_homo_vs_hetero",  # Fig. 9 / §6.2
    "benchmarks.fig10_bandwidth",      # Fig. 10 bandwidth sensitivity
    "benchmarks.fig11_ablations",      # Fig. 11 granularity + joint opt
    "benchmarks.search_overhead",      # §6.6 planning overhead; appends a
                                       # run to BENCH_search.json (repo root)
    "benchmarks.comm_bench",           # comm subsystem: algorithm selection,
                                       # compression, contention; appends a
                                       # run to BENCH_comm.json (repo root)
    "benchmarks.serve_replay",         # serving: disaggregated vs colocated
                                       # replay on the fig10 fleet; appends a
                                       # run to BENCH_serve.json (repo root)
    "benchmarks.kbench_bench",         # measured-kernel pricing: autotune
                                       # speedups, interpolation error,
                                       # planner integration; appends a run
                                       # to BENCH_kbench.json (repo root)
    "benchmarks.roofline",             # repo-specific: dry-run roofline
]


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = sys.argv[1] if len(sys.argv) > 1 else None
    failures = []
    for name in MODULES:
        if only and only not in name:
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.time()
        try:
            importlib.import_module(name).main()
        except Exception:
            traceback.print_exc()
            failures.append(name)
        print(f"# ({name}: {time.time() - t0:.1f}s)", flush=True)
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
