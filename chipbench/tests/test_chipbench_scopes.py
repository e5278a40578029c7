"""The reduction of the program's own names: scopes from hand-written HLO
lines, shares and waits on hand-made events, and a recorded chip trace with
its step program's op-to-scope map."""
import json
import types
from pathlib import Path

import pytest

from chipbench.lib import scopes, trace

D0, D1 = "/device:TPU:0", "/device:TPU:1"
DATA = Path(__file__).parent / "data"


@pytest.mark.parametrize("path,want", [
    ("jit(train_step)/transpose(jvp(attention))/dot_general", "attention"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/cos", "mlp"),
    ("jit(train_step)/jvp(head)/reduce_max", "head"),
    ("jit(train_step)/transpose(jvp(embed))/scatter-add", "embed"),
    ("jit(train_step)/optimizer/sqrt", "optimizer"),
    ("jit(train_step)/head/attention/dot_general", "attention"),  # innermost
    ("jit(train_step)/jvp()/while/body/add", None),
    ("jit(train_step)/jvp(mlp_block)/add", None),               # whole names
    ("", None),
])
def test_scope_is_the_innermost_after_wrappers_come_off(path, want):
    assert scopes.scope_of(path) == want


HLO = """HloModule jit_train_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[]}

%fused_computation.3 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %multiply.4 = f32[8]{0} multiply(f32[8]{0} %param_0.1, f32[8]{0} %param_0.1), metadata={op_name="jit(train_step)/optimizer/mul" source_file="optimizer.py" source_line=80}
}

ENTRY %main.9 (p: f32[8]) -> f32[] {
  %p = f32[8]{0} parameter(0)
  %fusion.3 = f32[8]{0:T(256)} fusion(f32[8]{0} %p), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(train_step)/transpose(jvp(mlp))/mul" source_file="mlp.py" source_line=33}
  %copy.1 = f32[8]{0} copy(f32[8]{0} %fusion.3)
  ROOT %reduce.2 = f32[] reduce(f32[8]{0} %copy.1, f32[] %c), dimensions={0}, to_apply=%add, metadata={op_name="jit(train_step)/while/body/add"}
}
"""


def test_op_scopes_read_each_instructions_metadata():
    got = scopes.op_scopes(HLO)
    assert got == {"multiply.4": "optimizer", "fusion.3": "mlp",
                   "reduce.2": None}          # copy.1 has no metadata
    assert scopes.module_name("jit_train_step(301924848613083223)") \
        == "jit_train_step"


def test_programs_of_one_name_keep_only_the_ops_they_scope_alike():
    other = HLO.replace("transpose(jvp(mlp))", "head").replace(
        "optimizer/mul", "optimizer/add")
    maps = scopes.scope_maps([HLO, other, "not a module"])
    assert maps == {"jit_train_step": {"multiply.4": "optimizer",
                                       "reduce.2": None}}


def _trace(ops, modules, host=()):
    return trace.Trace(ops, modules, [("python", trace.WINDOW, 0, 1000),
                                      *host])


MAPS = {"jit_step": {"fusion.1": "attention", "fusion.2": "mlp",
                     "fusion.3": "optimizer", "copy.4": None}}


def test_shares_count_unscoped_ops_and_loops_as_other_and_sum_to_one():
    ops = {D0: [("while.9", 0, 400), ("fusion.1", 0, 200),
                ("fusion.2", 250, 400),         # 200..250: the loop alone
                ("copy.4", 400, 450),           # no scope
                ("fusion.1", 450, 500),         # a program with no map
                ("fusion.3", 600, 900)],
           D1: [("fusion.1", 100, 300), ("fusion.3", 300, 500)]}
    modules = {D0: [("jit_step(7)", 0, 450), ("jit_norms(8)", 450, 500),
                    ("jit_step(7)", 600, 900)],
               D1: [("jit_step(7)", 100, 500)]}
    tr = _trace(ops, modules)
    got = scopes.shares(tr, 0, 1000, MAPS)
    assert got["attention"] == pytest.approx((0.2 + 0.2) / 2)
    assert got["mlp"] == pytest.approx(0.15 / 2)
    assert got["optimizer"] == pytest.approx((0.3 + 0.2) / 2)
    assert got["other"] == pytest.approx((0.05 + 0.05 + 0.05) / 2)
    assert got["embed"] == got["head"] == 0
    assert got["idle"] == pytest.approx(trace.idle_frac(tr, 0, 1000))
    assert sum(got.values()) == pytest.approx(1.0)


def test_the_window_clips_every_share():
    tr = _trace({D0: [("fusion.1", -100, 100), ("fusion.3", 900, 1200)]},
                {D0: [("jit_step(7)", -100, 1200)]})
    got = scopes.shares(tr, 0, 1000, MAPS)
    assert got["attention"] == pytest.approx(0.1)
    assert got["optimizer"] == pytest.approx(0.1)
    assert got["idle"] == pytest.approx(0.8)


def test_idle_is_put_down_to_the_host_span_it_falls_in():
    # two steps; D0 idles 100..180 (batch 60..150 holds 50 of it, dispatch
    # 150..200 the other 30) and 500..560 (sync 480..520 holds 20, the rest
    # falls outside every span); D1 idles 100..200 and 500..600
    host = [("python", "trainer.step", 50, 520), ("python", "trainer.step",
                                                  520, 990),
            ("python", "trainer.batch", 60, 150),
            ("python", "trainer.dispatch", 150, 200),
            ("python", "trainer.sync", 480, 520),
            ("python", "trainer.batch", 530, 540)]
    ops = {D0: [("fusion.1", 0, 100), ("fusion.2", 180, 500),
                ("fusion.1", 560, 1000)],
           D1: [("fusion.1", 0, 100), ("fusion.2", 200, 500),
                ("fusion.1", 600, 1000)]}
    tr = _trace(ops, {}, host)
    ms = 1e6
    assert scopes.wait_ms(tr, 0, 1000, "trainer.batch") * ms == \
        pytest.approx((50 + 50 + 10 + 10) / 2 / 2)
    assert scopes.wait_ms(tr, 0, 1000, "trainer.dispatch") * ms == \
        pytest.approx((30 + 50) / 2 / 2)
    assert scopes.wait_ms(tr, 0, 1000, "trainer.sync") * ms == \
        pytest.approx((20 + 20) / 2 / 2)
    # a program without step spans
    assert scopes.wait_ms(_trace(ops, {}), 0, 1000, "trainer.sync") is None


def test_compile_marks_are_counted_inside_the_window_only():
    host = [("python", scopes.COMPILES, -5, -5),
            ("python", scopes.COMPILES, 300, 300),
            ("python", "jax.cache_loads", 300, 300)]
    assert scopes.marks(_trace({}, {}, host), 0, 1000, scopes.COMPILES) == 1


def test_scope_frac_is_none_where_no_op_maps_to_a_scope():
    ctx = types.SimpleNamespace(trace=_trace({D0: [("fusion.1", 0, 10)]},
                                             {}), lo=0, hi=1000)
    assert scopes.scope_frac(ctx, "mlp") is None
    assert ctx.scope_shares is None


def test_recorded_chip_trace_maps_busy_time_to_the_scopes():
    """A window of 3 steps of gpt2b.fit-b8s1k (6 layers, batch 8 x 1024) on a
    TPU v5e, recorded with the scopes and spans in place, and the map from
    op name to scope that its step program's compiled text gives."""
    tr = trace.load(DATA / "fit-b8s1k-scoped.xplane.pb.gz")
    maps = json.loads((DATA / "fit-b8s1k-scoped.scopes.json").read_text())
    lo, hi = tr.window()
    got = scopes.shares(tr, lo, hi, maps)
    assert sum(got.values()) == pytest.approx(1.0)
    busy = 1 - got["idle"]
    assert busy == pytest.approx(1 - trace.idle_frac(tr, lo, hi))
    assert sum(got[s] for s in scopes.SCOPES) >= 0.9 * busy
    assert all(got[s] > 0 for s in scopes.SCOPES)
    assert len(scopes.spans(tr, scopes.STEP, lo, hi)) == 3
    waits = [scopes.wait_ms(tr, lo, hi, f"trainer.{n}")
             for n in ("batch", "dispatch", "sync")]
    idle_ms_per_step = got["idle"] * (hi - lo) / 3 / 1e6
    assert all(w is not None for w in waits)
    assert sum(waits) <= idle_ms_per_step
    assert scopes.marks(tr, lo, hi, scopes.COMPILES) == 0
