"""Off a TPU the benchmark prints no result: it exits 2 at once, and a
rehearsal at a tiny size runs the whole cell and exits 1."""

import json
import os
import subprocess
import sys

import pytest

from bench import harness

RUN = os.path.join(harness.HERE, "run.py")
CELLS = [w["name"] for w in json.load(
    open(os.path.join(harness.ROOT, "BENCHMARK.json")))["workloads"]]


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, RUN, *args], env=env,
                          capture_output=True, text=True, timeout=600)


def test_no_tpu_no_result():
    p = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1")
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("name", CELLS)
def test_rehearsal_exits_nonzero_without_result(name):
    p = _run("--workload", name, "--seed", str(2**33 + 7), "--seconds",
             "0.5", "--trace", "1", "--rehearse")
    assert p.returncode == 1, p.stderr[-2000:]
    assert '"correct": true' not in p.stdout
    assert p.stdout.strip() == ""
    assert "rehearsal, not a result" in p.stderr
    assert "check " in p.stderr


def test_benchmark_alone_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files
    holds no program: the run exits non-zero and prints no result."""
    import shutil

    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
