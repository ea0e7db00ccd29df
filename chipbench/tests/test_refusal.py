"""Without a TPU the benchmark does no work, prints no result and exits
non-zero; so it does in a checkout without the program."""
import os
import shutil
import subprocess
import sys

from run import BENCH, ROOT


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script, "--workload", "stablelm-3b.chat",
         "--seed", str(2**31 + 3), "--seconds", "10", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_chip():
    r = _run(ROOT, str(BENCH / "run.py"))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs 1 TPU chip" in r.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, str(tmp_path / "chipbench" / "run.py"))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
