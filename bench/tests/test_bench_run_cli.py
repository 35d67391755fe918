"""``bench/run.py`` refuses to run without a TPU, and in a directory that
holds only the benchmark's own files; it prints no result either way."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cfd_karman_snap", "--seed", str(2**33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240,
    )


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            continue
    return True


def test_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert _no_result(p.stdout)


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
