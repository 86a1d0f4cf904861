"""``bench/run.py`` measures only on a TPU: elsewhere it prints no result."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

from bench.lib import harness

ARGS = ["--workload", "graph500-s19.bfs", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run(root: Path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def _no_result(p) -> bool:
    return not any(line.startswith("{") for line in p.stdout.splitlines())


def test_refuses_to_run_without_a_tpu():
    p = _run(harness.ROOT)
    assert p.returncode == 3, p.stderr[-2000:]
    assert _no_result(p)
    assert "no TPU" in p.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p)
