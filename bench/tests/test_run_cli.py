"""The command refuses to run without a chip, and prints no result then."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "clf_icu_pod16",
         "--seed", "2147483653", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_no_tpu_exits_nonzero_without_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = _run(ROOT, env)
    assert out.returncode != 0
    assert _no_result(out.stdout)
    assert "needs a TPU" in out.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    subprocess.run(["cp", "-r", str(ROOT / "bench"), str(tmp_path)],
                   check=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    out = _run(tmp_path, env)
    assert out.returncode != 0
    assert _no_result(out.stdout)
    assert "No module named 'repro'" in out.stderr
