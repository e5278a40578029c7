"""The command refuses to run without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

from chipbench.tests import tiny

ARGS = ["--workload", "gpt2b.fit-b8s1k", "--seed", str(2 ** 31 + 9),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_command_refuses_a_host_without_a_tpu():
    proc = _run(tiny.ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr


def test_command_needs_the_program_beside_it(tmp_path):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
