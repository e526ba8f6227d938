"""A whole benchmark run on the CPU at a tiny size: the harness's look for
a chip is skipped, the rest of the run is the chip run's. Tiny cells are
added as data files in a copy of ``bench/`` (``benchtiny``)."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

import benchtiny
from bench import run as R


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mamba2-780m"])
def test_tiny_cell_runs_correct(tmp_path, monkeypatch, arch):
    benchtiny.steer(monkeypatch)
    cell, _ = benchtiny.make_bench(tmp_path, arch)
    line = R.execute(cell, 2 ** 31 + 99, 2.0, False, jax.devices(),
                     t_start=time.perf_counter())
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == {"stream_mismatch", "budget_errors",
                                   "widest_gap"}
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"output_tok_s", "tpot_p95_ms",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["count"] == 1
    json.dumps(line)


def _cmd(cwd, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-decode-c16",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def test_no_tpu_exits_nonzero_without_a_result():
    p = _cmd(benchtiny.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_bare_checkout_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(benchtiny.ROOT / "BENCHMARK.json", tmp_path)
    for d in json.loads((benchtiny.ROOT / "BENCHMARK.json").read_text())[
            "paths"]:
        shutil.copytree(benchtiny.ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PYTHONPATH": ""}
    p = _cmd(tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
