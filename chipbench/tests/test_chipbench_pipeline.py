"""The ``pipeline`` entry at a tiny width on 4 virtual CPU devices, clean
and under each planted fault, in a child process (the device count is set
before JAX starts)."""
import json
import os
import subprocess
import sys

from chipbench.tests import tiny

CELL = "gpt2b-pp.b16mb8s1k"


def test_pipeline_entry_matches_the_reference_and_catches_faults():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.tests.tiny", CELL],
        cwd=tiny.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    clean = out.pop("clean")
    assert clean["correct"], clean["checks"]
    assert clean["device"]["count"] == 4
    assert set(out) == set(tiny.FAULTS["pipeline"])
    for kind, res in out.items():
        assert not res["correct"], (kind, res["checks"])
