"""repro.obs: unified tracing / metrics / drift accounting (ISSUE 10).

Covers the exactness contract (adapter span sums reproduce the engines'
own totals bit for bit), Chrome-trace byte determinism + golden schema
pinning, migration timeline lanes with flow arrows, drift-ledger math
(the 20% pool-slowdown acceptance case), the v8 ``obs`` config off-state,
metrics shims over pre-existing counters, replay/run-log round trips, and
the ``repro trace`` CLI.
"""
import dataclasses
import json
import os

import pytest

from repro import api
from repro.core import paper_case_study_cluster
from repro.core.pipesim import ascii_timeline, plain_sum, sim_memo_stats
from repro.core.planner import PlannerConfig
from repro.migrate import (
    diff_layouts, layout_from_strategy, lost_devices, price_migration,
)
from repro.obs import (
    DriftLedger, MetricsRegistry, ObsConfig, iter_kind, read_runlog,
    render_ascii, sync_from_sim_memo, trace_from_decisions,
    trace_from_migration, trace_from_serve, trace_from_sim, trace_to_chrome,
)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "obs_trace_schema.json")


def small_cfg(**kw):
    return api.HarpConfig(
        seq_len=512, global_batch=16,
        planner=PlannerConfig(granularity=16, n_microbatches=16), **kw)


@pytest.fixture(scope="module")
def exe_case():
    """Plain compile on the paper's case-study mixed fleet."""
    return api.compile("gpt-2b", paper_case_study_cluster(), small_cfg())


@pytest.fixture(scope="module")
def chaos_run(tmp_path_factory):
    """One elastic chaos replay with the full obs surface wired: drift
    ledger, JSONL run-log, and a Chrome trace with the decision track."""
    d = tmp_path_factory.mktemp("obs")
    log = d / "run.jsonl"
    trace_path = d / "replay_trace.json"
    cfg = small_cfg(obs=ObsConfig(run_log=str(log)))
    exe = api.compile("gpt-2b", paper_case_study_cluster(), cfg)
    exe.attach_elastic()
    res = exe.replay("chaos", 200, seed=1, trace_out=str(trace_path))
    return exe, res, log, trace_path


# ---------------------------------------------------------------------------
# Adapter exactness (the module's core contract)
# ---------------------------------------------------------------------------


def test_sim_adapter_span_sums_reproduce_engine_totals(exe_case):
    res = exe_case.simulate(priced=False)
    tr = trace_from_sim(res)
    compute = [s for s in tr.spans if s.cat == "compute"]
    for i, expected in enumerate(res.stage_compute):
        got = plain_sum(s.dur for s in compute if s.args["stage"] == i)
        assert got == expected          # exact float equality, not approx
    comm = plain_sum(s.dur for s in tr.spans
                     if s.cat == "comm" and s.args.get("kind") in ("CF", "CB"))
    assert comm == res.comm_total
    assert tr.meta["comm_exposed_s"] == res.comm_exposed
    assert tr.makespan() == res.makespan


def test_render_ascii_matches_legacy_pipesim_timeline(exe_case):
    res = exe_case.simulate(priced=False)
    assert render_ascii(trace_from_sim(res), width=100) == \
        ascii_timeline(res, width=100)


def test_describe_timeline_rides_the_span_model(exe_case):
    out = exe_case.describe(timeline=True)
    assert "stage0|" in out


def test_serve_adapter_pool_busy_rollup():
    events = [(0.0, 0.10, 0, "poolA", "prefill", 256),
              (0.10, 0.05, 0, "poolA", "decode", 4),
              (0.05, 0.02, 1, "poolB", "decode", 2)]
    tr = trace_from_serve(events)
    assert len(tr.spans) == 3
    assert tr.meta["pool_busy_s"] == {
        "poolA/decode": 0.05, "poolA/prefill": 0.10, "poolB/decode": 0.02}


# ---------------------------------------------------------------------------
# Chrome export: byte determinism + golden schema
# ---------------------------------------------------------------------------


def test_chrome_export_is_byte_deterministic(exe_case, tmp_path):
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    trace_to_chrome(exe_case.trace(), str(a))
    trace_to_chrome(exe_case.trace(), str(b))
    assert a.read_bytes() == b.read_bytes()
    doc = json.loads(a.read_text())
    assert doc["otherData"]["schema"] == 1
    assert all(ev["dur"] >= 0 for ev in doc["traceEvents"]
               if ev["ph"] == "X")


def _chrome_shape(doc):
    """Structural digest: per-phase event key sets + top-level layout —
    what a Perfetto-compatible consumer depends on."""
    shapes = {}
    for ev in doc["traceEvents"]:
        shapes.setdefault(ev["ph"], sorted(ev.keys()))
    return {"top": sorted(doc.keys()),
            "otherData": sorted(doc["otherData"].keys()),
            "schema": doc["otherData"]["schema"],
            "event_shapes": {k: shapes[k] for k in sorted(shapes)}}


def _golden_trace(exe):
    """Contended sim trace (has a link-busy counter) plus one synthetic
    flow pair, so every event phase the exporter can emit is pinned."""
    tr = exe.trace(contention=True)
    tr.add_span("x", "rel", "release", "drain", 0.0, 1.0,
                flow_id=0, flow_start=True)
    tr.add_span("x", "mig", "flow", "migration", 1.0, 1.0,
                flow_id=0, flow_end=True)
    return tr


def test_chrome_schema_matches_golden(exe_case):
    with open(GOLDEN) as f:
        golden = json.load(f)
    got = _chrome_shape(_golden_trace(exe_case).to_chrome())
    assert got == golden, (
        "Chrome-trace event schema drifted from tests/golden/"
        "obs_trace_schema.json.  If the change is INTENTIONAL, bump "
        "repro.obs.trace.OBS_TRACE_SCHEMA and regenerate the golden file "
        "(json.dump(_chrome_shape(...), indent=2, sort_keys=True)); "
        "otherwise you broke every saved trace consumers already have.")


def test_executable_trace_writes_valid_chrome_json(exe_case, tmp_path):
    out = tmp_path / "trace.json"
    tr = exe_case.trace(out=str(out))
    doc = json.loads(out.read_text())
    n_x = sum(1 for ev in doc["traceEvents"] if ev["ph"] == "X")
    assert n_x == len(tr.spans)
    # counters land on tid 0, metadata names every pid exactly once
    assert all(ev["tid"] == 0 for ev in doc["traceEvents"]
               if ev["ph"] == "C")
    names = [ev for ev in doc["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "process_name"]
    assert len({ev["pid"] for ev in names}) == len(names)


def test_registry_resolves_trace_adapters(exe_case):
    fn = api.registry.resolve("trace_adapter", "sim")
    tr = fn(exe_case.simulate(priced=False))
    assert tr.spans and tr.makespan() > 0


# ---------------------------------------------------------------------------
# Migration timeline lanes
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def shrink_costs(exe_case):
    """Price the same shrink migration (one meshA100 node leaves) with and
    without the timeline; live flows survive, so the trace has arrows."""
    cl = exe_case.cluster
    sc0 = cl.subclusters[0]
    shrunk = dataclasses.replace(
        cl, subclusters=(dataclasses.replace(sc0, n_nodes=sc0.n_nodes - 1),)
        + cl.subclusters[1:])
    exe2 = api.compile("gpt-2b", shrunk, small_cfg())
    old_lay = layout_from_strategy(exe_case.strategy, cl, exe_case.layers)
    new_lay = layout_from_strategy(exe2.strategy, shrunk, exe_case.layers)
    mplan = diff_layouts(old_lay, new_lay,
                         lost=lost_devices(cl, shrunk))
    kw = dict(old_strategy=exe_case.strategy, old_cluster=cl,
              layers=exe_case.layers)
    with_tl = price_migration(mplan, old_lay, shrunk,
                              collect_timeline=True, **kw)
    without = price_migration(mplan, old_lay, shrunk, **kw)
    return with_tl, without


def test_timeline_collection_never_changes_prices(shrink_costs):
    with_tl, without = shrink_costs
    assert with_tl.serial_s == without.serial_s
    assert with_tl.overlap_extra_s == without.overlap_extra_s
    assert with_tl.drain_s == without.drain_s
    assert without.timeline is None
    assert len(with_tl.timeline["flows"]) == with_tl.n_flows


def test_migration_trace_lanes_and_flow_arrows(shrink_costs):
    with_tl, without = shrink_costs
    tr = trace_from_migration(with_tl)
    tl = with_tl.timeline
    assert len(tr.spans) == len(tl["flows"]) + len(tl["drain"])
    # live flows (a surviving source stage) terminate a flow arrow from
    # that stage's release span
    ends = [s for s in tr.spans if s.flow_end]
    starts = [s for s in tr.spans if s.flow_start]
    assert ends and starts
    assert {s.flow_id for s in ends} <= {s.flow_id for s in starts}
    assert tr.meta["downtime_s"] == with_tl.downtime_s
    with pytest.raises(ValueError, match="collect_timeline"):
        trace_from_migration(without)


# ---------------------------------------------------------------------------
# Drift ledger
# ---------------------------------------------------------------------------


def test_drift_ledger_exact_math_and_window():
    led = DriftLedger(threshold=0.15, window=4)
    led.register_plan({"makespan_s": 1.0, "stage_compute_s": [0.5, 0.25]},
                      stage_pools={0: "A", 1: "B"})
    for step in range(10):                  # window keeps the last 4
        led.observe_step(step, 1.1, stage_times=[0.55, 0.25])
    rep = led.report()
    assert rep.n_samples == 4 and rep.n_observed == 10
    assert rep.rel_error == pytest.approx(0.1)
    assert rep.per_stage[0] == pytest.approx(0.1)
    assert rep.per_stage[1] == 0.0
    assert rep.per_pool == {"A": pytest.approx(0.1), "B": 0.0}
    assert not rep.flagged                  # 10% < 15% threshold
    # a new plan restarts the window: old samples don't indict it
    led.register_plan({"makespan_s": 2.0})
    rep2 = led.report()
    assert rep2.n_samples == 0 and not rep2.flagged
    assert rep2.n_observed == 10


def test_drift_report_flags_injected_pool_slowdown(exe_case):
    """ISSUE 10 acceptance: a 20% slowdown on every stage flags the run
    and attributes it to the hosting pools."""
    res = exe_case.simulate(priced=False)
    led = DriftLedger(threshold=0.15, window=8)
    led.register_plan(
        {"makespan_s": res.makespan,
         "stage_compute_s": list(res.stage_compute)},
        stage_pools=exe_case._stage_pools())
    for step in range(10):
        led.observe_step(step, res.makespan * 1.2,
                         stage_times=[t * 1.2 for t in res.stage_compute])
    rep = led.report()
    assert rep.flagged
    assert rep.rel_error == pytest.approx(0.2)
    assert rep.flagged_pools == sorted(set(exe_case._stage_pools().values()))
    assert "DRIFT" in rep.describe() and "+20.0%" in rep.describe()
    assert json.loads(rep.to_json())["flagged"] is True


def test_drift_report_requires_a_ledger(exe_case):
    with pytest.raises(ValueError, match="obs"):
        exe_case.drift_report()


# ---------------------------------------------------------------------------
# Config plumbing (schema v8)
# ---------------------------------------------------------------------------


def test_obs_config_round_trips_and_off_state_is_null():
    assert api.HarpConfig().to_dict()["obs"] is None
    cfg = small_cfg(obs=ObsConfig(run_log="run.jsonl",
                                  drift_threshold=0.2, drift_window=4))
    back = api.HarpConfig.from_dict(cfg.to_dict())
    assert back.obs == cfg.obs
    assert back.to_json() == cfg.to_json()


def test_pre_v8_config_dict_still_loads():
    d = small_cfg().to_dict()
    d.pop("obs")                            # a v7 artifact has no obs key
    assert api.HarpConfig.from_dict(d).obs is None


# ---------------------------------------------------------------------------
# Metrics registry + shims
# ---------------------------------------------------------------------------


def test_metrics_registry_snapshot_is_deterministic():
    def build():
        r = MetricsRegistry()
        r.inc("req", 2, pool="b")
        r.inc("req", pool="a")
        r.gauge("depth", 3.0)
        r.observe("lat_s", 0.2)
        r.observe("lat_s", 0.4)
        return r.snapshot()
    snap = build()
    assert snap == build()
    assert snap["counters"] == {"req{pool=a}": 1, "req{pool=b}": 2}
    assert snap["histograms"]["lat_s"] == {
        "count": 2, "sum": pytest.approx(0.6), "min": 0.2, "max": 0.4}
    r = MetricsRegistry()
    r.inc("x")
    r.reset()
    assert r.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_sim_memo_shim_mirrors_live_stats(exe_case):
    exe_case.simulate(priced=False)         # ensure the memo has traffic
    reg = sync_from_sim_memo(MetricsRegistry())
    s = sim_memo_stats()
    g = reg.snapshot()["gauges"]
    assert g["sim_memo.hits"] == s.hits
    assert g["sim_memo.misses"] == s.misses


def test_ckpt_saves_count_bytes_on_default_registry(tmp_path):
    from repro.checkpoint import ckpt
    from repro.obs.metrics import DEFAULT_REGISTRY

    def written():
        return DEFAULT_REGISTRY.snapshot()["counters"].get(
            "ckpt.bytes_written", 0)

    before = written()
    path = ckpt.save(str(tmp_path), 1, {"w": [1.0, 2.0, 3.0]})
    assert written() - before == os.path.getsize(path)


# ---------------------------------------------------------------------------
# Replay integration: decision track, metrics roll-up, run-log
# ---------------------------------------------------------------------------


def test_replay_trace_has_every_decision(chaos_run):
    exe, res, _log, trace_path = chaos_run
    assert res.decisions                    # the storm must actually act
    doc = json.loads(trace_path.read_text())
    dec = [ev for ev in doc["traceEvents"]
           if ev["ph"] == "X" and ev.get("cat") == "decision"]
    assert len(dec) == len(res.decisions)
    assert {ev["args"]["step"] for ev in dec} == \
        {d.step for d in res.decisions}


def test_replay_result_carries_metrics_snapshot(chaos_run):
    _exe, res, _log, _trace = chaos_run
    m = res.metrics
    assert m["counters"]["replay.tokens"] == res.tokens_total
    assert m["gauges"]["replay.steps"] == 200
    assert m["gauges"]["replay.wall_s"] == pytest.approx(res.wall_total_s)
    n_dec = sum(v for k, v in m["counters"].items()
                if k.startswith("controller.decisions"))
    assert n_dec == len(res.decisions)


def test_run_log_round_trips_on_the_replay_clock(chaos_run):
    _exe, res, log, _trace = chaos_run
    events = read_runlog(str(log))
    assert all(ev["schema"] == 1 for ev in events)
    steps = list(iter_kind(events, "step"))
    assert len(steps) == 200
    assert [e["step"] for e in steps] == sorted(e["step"] for e in steps)
    assert len(list(iter_kind(events, "decision"))) == len(res.decisions)
    # sim clock only: the log's wall matches the replay's, not time.time()
    assert steps[-1]["t"] == pytest.approx(res.wall_total_s)


def test_run_log_rejects_newer_schema(tmp_path):
    p = tmp_path / "run.jsonl"
    p.write_text('{"schema": 99, "kind": "step", "t": 0.0}\n')
    with pytest.raises(ValueError, match="newer"):
        read_runlog(str(p))


def test_controller_drift_ledger_observes_the_replay(chaos_run):
    exe, _res, _log, _trace = chaos_run
    rep = exe.drift_report()
    assert rep.n_observed > 0
    assert rep.predicted_step_s > 0
    # the decision adapter places spans at replay wall times
    tr = trace_from_decisions(
        exe.controller.decisions,
        wall_times={s.step: s.wall_s for s in _res.samples})
    assert tr.meta["clock"] == "wall"
    assert len(tr.spans) == len(exe.controller.decisions)


# ---------------------------------------------------------------------------
# CLI round trip
# ---------------------------------------------------------------------------


def test_cli_trace_round_trip(tmp_path, capsys):
    from repro.api.cli import main
    plan = tmp_path / "plan.json"
    out = tmp_path / "trace.json"
    rc = main(["plan", "--arch", "gpt-2b", "--cluster", "paper_case_study",
               "--granularity", "16", "--microbatches", "16",
               "--global-batch", "16", "--seq-len", "512", "-o", str(plan)])
    assert rc == 0
    rc = main(["trace", "--plan", str(plan), "-o", str(out), "--timeline"])
    assert rc == 0
    printed = capsys.readouterr().out
    assert "Chrome trace written" in printed and "stage0|" in printed
    doc = json.loads(out.read_text())
    assert doc["otherData"]["schema"] == 1
    assert any(ev["ph"] == "X" for ev in doc["traceEvents"])
