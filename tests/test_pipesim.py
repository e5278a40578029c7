"""Pipeline-DAG simulator: ``no_overlap`` mode (HexiScale-like synchronous
sends), the eta load-balance metric's edge cases, and the closed-form fast
path's bit-exact equivalence with the reference graph engine — the surfaces
the elastic replay harness builds on."""
import random

import pytest

from repro.core.h1f1b import (
    classic_1f1b_counts, eager_1f1b_counts, h1f1b_counts,
)
from repro.core.pipesim import (
    clear_sim_memo, eta_load_balance, fast_path_eligible, sim_memo_stats,
    simulate,
)


def test_no_overlap_never_faster():
    t_f, t_b, c = [1.0, 1.2], [2.0, 2.4], [0.5]
    counts = h1f1b_counts([3.0, 3.6], c, 8)
    over = simulate(t_f, t_b, c, 8, counts)
    sync = simulate(t_f, t_b, c, 8, counts, no_overlap=True)
    assert sync.makespan >= over.makespan - 1e-12


def test_no_overlap_equals_overlap_without_comm():
    t_f, t_b = [1.0, 1.0, 1.0], [2.0, 2.0, 2.0]
    c = [0.0, 0.0]
    counts = [3, 2, 1]
    over = simulate(t_f, t_b, c, 6, counts)
    sync = simulate(t_f, t_b, c, 6, counts, no_overlap=True)
    assert sync.makespan == pytest.approx(over.makespan)
    assert sync.comm_total == 0.0


def test_no_overlap_two_stage_one_microbatch_exact():
    # F0(1) -> send(0.5) -> F1(1) -> B1(1) -> send back(0.5) -> B0(1)
    res = simulate([1.0, 1.0], [1.0, 1.0], [0.5], 1, [1, 1], no_overlap=True)
    assert res.makespan == pytest.approx(5.0)


def test_no_overlap_comm_charged_to_stages():
    t_f, t_b, c = [1.0, 1.0], [1.0, 1.0], [0.4]
    B = 4
    sync = simulate(t_f, t_b, c, B, [2, 1], no_overlap=True)
    # every CF is charged to stage 0, every CB to stage 1; full duplex both ways
    assert sync.stage_comm_blocking[0] == pytest.approx(B * 0.4)
    assert sync.stage_comm_blocking[1] == pytest.approx(B * 0.4)
    assert sum(sync.stage_comm_blocking) == pytest.approx(sync.comm_total)
    over = simulate(t_f, t_b, c, B, [2, 1])
    assert over.stage_comm_blocking == [0.0, 0.0]


def test_no_overlap_busy_idle_accounting():
    sync = simulate([1.0, 2.0], [1.0, 2.0], [0.3], 5, [2, 1], no_overlap=True)
    for i in range(2):
        total = (sync.stage_compute[i] + sync.stage_comm_blocking[i]
                 + sync.stage_idle[i])
        assert total == pytest.approx(sync.makespan)


# ---------------------------------------------------------------------------
# Closed-form fast path == graph engine (bit-exact)
# ---------------------------------------------------------------------------


def _assert_same(a, b):
    assert a.makespan == b.makespan          # exact, not approx
    assert a.start == b.start and a.dur == b.dur
    assert a.stage_compute == b.stage_compute
    assert a.stage_idle == b.stage_idle
    assert a.comm_total == b.comm_total
    assert a.comm_exposed == b.comm_exposed
    assert a.stage_intra_comm == b.stage_intra_comm
    assert a.warmup_counts == b.warmup_counts


@pytest.mark.parametrize("sched", ["h1f1b", "h1f1b_banded", "classic",
                                   "eager"])
@pytest.mark.parametrize("seed", range(4))
def test_fast_path_matches_graph_all_schedules(sched, seed):
    rng = random.Random(f"{sched}:{seed}")    # str seeds are not salted
    S = rng.randint(1, 6)
    B = rng.randint(1, 16)
    t_f = [rng.uniform(0.1, 2.0) for _ in range(S)]
    t_b = [rng.uniform(0.1, 3.0) for _ in range(S)]
    c = [rng.choice([0.0, rng.uniform(0.0, 1.5)]) for _ in range(S - 1)]
    if sched == "classic":
        counts = classic_1f1b_counts(S, B)
    elif sched == "eager":
        counts = eager_1f1b_counts(S, B)
    else:
        counts = h1f1b_counts([f + b for f, b in zip(t_f, t_b)], c, B,
                              banded=(sched == "h1f1b_banded"))
    assert fast_path_eligible(counts)
    fast = simulate(t_f, t_b, c, B, counts, fast=True, cache=False)
    graph = simulate(t_f, t_b, c, B, counts, fast=False, cache=False)
    _assert_same(fast, graph)


@pytest.mark.parametrize("seed", range(3))
def test_fast_path_matches_graph_with_intra_and_bwd_links(seed):
    rng = random.Random(seed)
    S, B = rng.randint(2, 5), rng.randint(2, 10)
    t_f = [rng.uniform(0.1, 2.0) for _ in range(S)]
    t_b = [rng.uniform(0.1, 3.0) for _ in range(S)]
    c = [rng.uniform(0.0, 1.0) for _ in range(S - 1)]
    cb = [x * rng.uniform(0.5, 1.5) for x in c]
    intra_f = [rng.uniform(0.0, 0.3) for _ in range(S)]
    intra_b = [rng.uniform(0.0, 0.3) for _ in range(S)]
    counts = h1f1b_counts([f + b for f, b in zip(t_f, t_b)], c, B)
    kw = dict(c_links_bwd=cb, intra_f=intra_f, intra_b=intra_b,
              intra_overlap=rng.uniform(0.0, 1.0), cache=False)
    _assert_same(simulate(t_f, t_b, c, B, counts, fast=True, **kw),
                 simulate(t_f, t_b, c, B, counts, fast=False, **kw))


def test_fast_path_ineligible_schedules():
    # growing warm-up counts downstream break the recurrence's issue order
    assert not fast_path_eligible([1, 2, 3])
    assert not fast_path_eligible([2, 0, 1])
    assert not fast_path_eligible([3, 2, 1], no_overlap=True)
    with pytest.raises(ValueError, match="not closed-form eligible"):
        simulate([1.0, 1.0], [1.0, 1.0], [0.1], 4, [1, 2], fast=True)
    # auto mode falls back to the graph engine, which diagnoses the
    # growing-counts schedule as what it is: a deadlocked pipeline
    with pytest.raises(AssertionError, match="cycle"):
        simulate([1.0, 1.0], [1.0, 1.0], [0.1], 4, [1, 2], cache=False)


def test_no_overlap_uses_graph_engine():
    s0 = sim_memo_stats().graph_path
    simulate([1.0, 1.0], [1.0, 1.0], [0.3], 4, [2, 1], no_overlap=True,
             cache=False)
    assert sim_memo_stats().graph_path == s0 + 1


def test_sim_memo_hits_and_misses():
    clear_sim_memo()
    args = ([1.0, 1.5], [2.0, 2.5], [0.25], 8, [2, 1])
    s0 = sim_memo_stats().snapshot()
    r1 = simulate(*args)
    r2 = simulate(*args)
    live = sim_memo_stats()
    assert live.misses - s0.misses == 1
    assert live.hits - s0.hits == 1
    assert r1 is r2                       # served from cache, same object
    # different signature -> miss
    simulate(*args, no_overlap=True)
    assert sim_memo_stats().misses - s0.misses == 2


def test_eta_zero_compute():
    assert eta_load_balance([0.0, 0.0], [1e12, 1e12]) == 1.0


def test_eta_single_stage():
    assert eta_load_balance([3.0], [5e12]) == pytest.approx(1.0)


def test_eta_perfect_balance():
    assert eta_load_balance([2.0, 2.0], [1e12, 3e12]) == pytest.approx(1.0)


def test_eta_imbalance_weighted_by_peak():
    # idle time on the big sub-cluster hurts more than on the small one
    eta_big_idle = eta_load_balance([1.0, 2.0], [3e12, 1e12])
    eta_small_idle = eta_load_balance([2.0, 1.0], [3e12, 1e12])
    assert eta_big_idle < eta_small_idle < 1.0


def test_eta_one_stage_idle_zero_compute():
    # a stage with zero compute on equal peaks: eta = 1 - 1/2
    assert eta_load_balance([2.0, 0.0], [1e12, 1e12]) == pytest.approx(0.5)
