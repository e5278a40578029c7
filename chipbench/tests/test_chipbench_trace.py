"""The trace reduction on hand-made event lists, and the flops and peaks
tables against hand counts."""
import pytest

from chipbench.lib import flops, peaks, trace

D0, D1 = "/device:TPU:0", "/device:TPU:1"


def _trace(ops, modules=None, host=()):
    host = [("python", trace.WINDOW, 0, 1000), *host]
    return trace.Trace(ops, modules or {}, list(host))


def test_merge_clips_and_joins_overlaps():
    got = trace.merge([(50, 70), (-10, 20), (10, 30), (990, 1200)], 0, 1000)
    assert got == [(0, 30), (50, 70), (990, 1000)]
    assert trace.covered([(0, 10), (5, 15), (20, 25)], 0, 100) == 20


@pytest.mark.parametrize("a,b,want", [
    ([(0, 100)], [], 100),
    ([(0, 100)], [(10, 20), (30, 40)], 80),
    ([(0, 100)], [(-5, 200)], 0),
    ([(0, 10), (20, 30)], [(5, 25)], 10),
    ([(0, 10)], [(10, 20)], 10),
])
def test_subtract(a, b, want):
    assert trace.subtract(a, b) == want


def test_idle_frac_is_one_minus_busy_union_averaged_over_devices():
    ops = {D0: [("fusion.1", 0, 600), ("fusion.2", 500, 800)],   # busy 800
           D1: [("fusion.1", 100, 500)]}                        # busy 400
    tr = _trace(ops)
    lo, hi = tr.window()
    assert trace.mean_busy_s(tr, lo, hi) == pytest.approx(600e-9)
    assert trace.idle_frac(tr, lo, hi) == pytest.approx(0.4)
    assert trace.idle_frac(_trace({}), 0, 1000) is None


def test_step_gaps_count_idle_between_runs_of_the_step_program():
    modules = {D0: [("jit_step", 0, 300), ("jit_norms", 310, 320),
                    ("jit_step", 350, 650), ("jit_step", 700, 1000)]}
    ops = {D0: [("fusion", 0, 300), ("reduce", 310, 320),
                ("fusion", 350, 650), ("fusion", 700, 1000)]}
    tr = _trace(ops, modules)
    assert trace.step_program(tr, 0, 1000) == "jit_step"
    # 300..350 holds 10 ns of another program: 40 idle; 650..700: 50 idle
    assert trace.step_gaps_ns(tr, 0, 1000) == [40, 50]


def test_exposed_collective_is_collective_time_with_no_other_op():
    ops = {D0: [("fusion.3", 0, 400), ("all-reduce.7", 300, 600),
                ("collective-permute-done.1", 650, 700)],
           D1: [("fusion.3", 0, 500), ("all-gather.2", 450, 550),
                ("fusion.4", 500, 1000)]}
    tr = _trace(ops)
    # D0: 400..600 and 650..700 exposed = 250; D1: none
    assert trace.exposed_collective_frac(tr, 0, 1000) == pytest.approx(0.125)
    assert trace.exposed_collective_frac(
        _trace({D0: [("fusion", 0, 10)]}), 0, 1000) is None


def test_breakdown_names_ops_and_gaps():
    ops = {D0: [("convolution.1", 0, 300), ("fusion.2", 300, 400),
                ("convolution.1", 600, 900)]}
    host = [("python", "make_batch", 420, 580), ("python", "run", 0, 1000)]
    tr = _trace(ops, host=host)
    assert trace.top_ops(tr, 0, 1000) == [["convolution.1", 600e-9],
                                          ["fusion.2", 100e-9]]
    # gaps 400..600 (make_batch spans its middle) and 900..1000 ("run")
    assert trace.idle_gaps(tr, 0, 1000) == [["make_batch", 200e-9],
                                            ["run", 100e-9]]


def test_loops_count_as_busy_but_only_their_ops_are_ranked():
    ops = {D0: [("while.9", 0, 500), ("fusion.1", 10, 200),
                ("all-reduce.3", 250, 400), ("fusion.2", 600, 700)]}
    tr = _trace(ops)
    assert [n for n, _, _ in trace.leaves(ops[D0])] == [
        "fusion.1", "all-reduce.3", "fusion.2"]
    assert trace.idle_frac(tr, 0, 1000) == pytest.approx(0.4)
    assert trace.exposed_collective_frac(tr, 0, 1000) == pytest.approx(0.15)
    assert [n for n, _ in trace.top_ops(tr, 0, 1000)] == [
        "fusion.1", "all-reduce.3", "fusion.2"]
    assert trace.op_name("%fusion.2 = f32[8]{0} fusion(%p), kind=kLoop") \
        == "fusion.2"


def test_window_is_the_one_annotated_host_span():
    tr = _trace({}, host=[("python", "other", 5, 6)])
    assert tr.window() == (0, 1000)
    with pytest.raises(ValueError):
        trace.Trace({}, {}, []).window()


GPT_2B = {"d_model": 2560, "n_heads": 32, "n_kv_heads": 32, "d_ff": 10240,
          "vocab_size": 51200, "activation": "gelu"}


@pytest.mark.parametrize("layers,params,gflop", [
    (4, 445_644_800, 2.80),      # 4 x 78.64 M per layer + 131.07 M head
    (12, 1_074_790_400, 6.83),
])
def test_gpt2b_flops_per_token_match_hand_counts(layers, params, gflop):
    arch = dict(GPT_2B, n_layers=layers)
    assert flops.matmul_params(arch) == params
    per_token = flops.train_flops_per_token(arch, 1024)
    assert per_token == 6 * params + 12 * layers * 2560 * 1024
    assert per_token / 1e9 == pytest.approx(gflop, abs=0.005)


def test_peaks_know_v5e_and_refuse_unknown_devices():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu")


def test_recorded_chip_trace_reduces_to_fixed_numbers():
    """A ``--trace 1`` run of gpt2b.fit-b8s1k at 4 layers on a TPU v5e (6
    steps of batch 8 x 1024), recorded once and kept: the reduction of it must not
    drift."""
    import types
    from pathlib import Path

    from chipbench.metrics import (
        device_idle_frac, exposed_collective_frac, mfu, step_gap_ms,
    )

    path = Path(__file__).parent / "data" / "fit-b8s1k.xplane.pb.gz"
    tr = trace.load(path)
    lo, hi = tr.window()
    assert (lo, hi) == (50597253.0, 1988595749.0)
    assert list(tr.ops) == ["/device:TPU:0"]
    assert trace.mean_busy_s(tr, lo, hi) == pytest.approx(1.922164992)
    assert trace.step_program(tr, lo, hi).startswith("jit_train_step(")
    assert len(trace.step_gaps_ns(tr, lo, hi)) == 5
    ctx = types.SimpleNamespace(
        trace=tr, lo=lo, hi=hi, chips=1, tokens=6 * 8192,
        flops_per_token=flops.train_flops_per_token(
            dict(GPT_2B, n_layers=4), 1024),
        peak=peaks.peak("TPU v5 lite"))
    assert mfu.read(ctx) == pytest.approx(36.04397724322508)
    assert device_idle_frac.read(ctx) == pytest.approx(0.00817002904423303)
    assert step_gap_ms.read(ctx) == pytest.approx(2.5838704)
    assert exposed_collective_frac.read(ctx) is None   # one chip
    assert trace.top_ops(tr, lo, hi, k=1) == [["fusion.2",
                                               pytest.approx(0.121849206)]]
    gaps = trace.idle_gaps(tr, lo, hi)
    assert len(gaps) == 10
    assert gaps[0] == ["$api.py:3097 block_until_ready",
                       pytest.approx(0.002782714)]
