"""The command refuses to measure without a TPU, and without the
program: it exits non-zero and prints no result line."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

from perfbench import spec


def _run(cwd: Path, env_extra: dict) -> subprocess.CompletedProcess:
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "pong.analysis",
         "--seed", str(2 ** 31 + 12345), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


def test_no_tpu_no_result():
    r = _run(spec.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert _no_result(r.stdout)
    assert "needs a TPU" in r.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("_out", "__pycache__"))
    r = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert _no_result(r.stdout)
