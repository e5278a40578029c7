"""The ``fit`` entry at a tiny width on the CPU: the path the one-chip cells
time, the comparison with the plain reference, and faults planted under
the harness that the comparison has to catch."""
import numpy as np
import pytest

from chipbench.tests import tiny

CELL = "gpt2b.fit-b8s1k"


def test_fit_entry_trains_and_matches_the_reference():
    res = tiny.run_tiny(CELL)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("kind", tiny.FAULTS["fit"])
def test_fit_faults_are_not_correct(kind):
    with tiny.planted(kind):
        res = tiny.run_tiny(CELL)
    assert not res["correct"], res["checks"]


def test_bf16_control_reads_far_above_the_program():
    """The control, the reference in bf16 in the program's place, reads
    gaps far above the float32 program's own."""
    import jax

    from chipbench.lib import compare, traffic
    from chipbench.references import gpt as ref

    spec = tiny.tiny_spec(CELL)
    arch, opt, mix = (spec["config"]["arch"], spec["config"]["optimizer"],
                      spec["traffic"])
    seed = 12345
    batches = [traffic.batch(seed, k, rows=mix["batch"],
                             seq_len=mix["seq_len"], vocab=arch["vocab_size"])
               for k in range(3)]
    devs = jax.devices()[:1]
    want = ref.Reference(arch, opt, devs, 2).train(seed, batches)
    got = ref.Reference(arch, opt, devs, 2, mode="bf16").train(seed, batches)
    control = compare.gaps(got, want)
    program = tiny.run_tiny(CELL, seed=seed)["checks"]
    assert max(control.values()) > 10 * max(
        c["value"] for c in program.values())
    assert np.isfinite(list(control.values())).all()
