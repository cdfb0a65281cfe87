"""The command refuses anything but a TPU, and a checkout without the
program: it exits non-zero and prints no result line."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "ids-v1.mixed-small", "--seed", "0", "--seconds",
        "10", "--trace", "0"]


def _run(cmd, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def _no_result(p):
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


@pytest.mark.parametrize("entry", [["bench/run.py"], ["-m", "bench.run"]])
def test_refuses_the_cpu(entry):
    _no_result(_run([sys.executable, *entry, *ARGS], ROOT))


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run([sys.executable, "bench/run.py", *ARGS], tmp_path))


def test_unknown_workload_is_refused():
    p = _run([sys.executable, "bench/run.py", "--workload", "nope.none",
              "--seed", "1", "--seconds", "1"], ROOT)
    _no_result(p)
    assert "unknown workload" in p.stderr
