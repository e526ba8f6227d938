"""Tiny cells for driving the benchmark on the CPU in tests: a copy of
``bench/`` in a temporary directory with reduced configurations, mixes
and checks added as data files, and the program steered to its reduced
configuration. Nothing here is read by the benchmark itself."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
if str(ROOT) not in sys.path:          # the benchmark imports as ``bench``
    sys.path.insert(0, str(ROOT))

TINY_MIX = {
    "kind": "serve", "loop": "closed", "clients": 2, "cycle": 2,
    "prompt_len": [8, 16], "prompt_sizes": 3, "output_len": [4, 8],
    "ramp_s": 0.2, "trace_offset_s": 0.1, "trace_s": 0.5,
    "engine": {"slots": 2, "max_len": 32, "page_size": 8,
               "drafter_mode": "none"},
}


def tiny_config(arch: str) -> dict:
    """The configuration file of ``arch`` at the program's reduced sizes:
    its family's keys (``program_pairs``) with the reduced values."""
    from repro.configs import get_config

    from bench import harness as H
    full = H.load_json(BENCH / "configs" / f"{arch}.json")
    t = get_config(arch).reduced()
    return {"name": f"tiny-{arch}", "source": "reduced", "program_arch": arch,
            "family": full["family"], "reference": full["reference"],
            "reduced": [], "dtype": t.dtype,
            **H.load_family(full).program_pairs(full, t)}


def make_bench(tmp: Path, arch: str, limits=None, mix=None):
    """(Cell, spec) of a tiny serving cell of ``arch`` in a copy of bench/
    under ``tmp``, added as data files alone."""
    from bench import harness as H
    bench = tmp / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    name = f"tiny-{arch}"
    (bench / "configs" / f"{name}.json").write_text(
        json.dumps(tiny_config(arch)))
    (bench / "traffic" / "tiny-closed.json").write_text(
        json.dumps(mix or TINY_MIX))
    (bench / "cells" / f"{name}.cell.json").write_text(json.dumps({
        "limits": limits or {"widest_gap": 1e-3, "stream_mismatch": 0,
                             "budget_errors": 0},
        "sample_tokens": 16}))
    spec = H.load_json(ROOT / "BENCHMARK.json")
    spec["workloads"] = [{"name": f"{name}.cell", "config": name,
                          "traffic": "tiny-closed", "chips": 1,
                          "why": "tiny CPU cell"}]
    retarget(spec, "qwen2-decode-c16", f"{name}.cell")
    return H.Cell(spec, f"{name}.cell", bench_dir=bench), spec


def retarget(spec: dict, like: str, cell: str) -> None:
    """Give ``cell`` the metrics that the listed cell ``like`` reports."""
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if "workloads" in m:
                m["workloads"] = [cell] if like in m["workloads"] else []


def steer(monkeypatch) -> None:
    """Point the program at its reduced configurations and give the CPU a
    peak, so a run can go through on the CPU."""
    import repro.configs
    from bench import peaks
    real = repro.configs.get_config
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda arch: real(arch).reduced())
    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(peaks.PEAKS, kind,
                        peaks.Peak(1e12, 1e11, "test"))


TINY_TRAIN = {
    "kind": "train", "reference": "drafter", "seq": 16, "batch": 2,
    "k_train": 3, "cod_rate": 0.8, "corpus_seqs": 64, "check_steps": 3,
    "trace_offset_s": 0.1, "trace_s": 0.3,
    "drafter": {"layers": 1, "rope_theta": 10000.0, "rms_norm_eps": 1e-06},
    "optimizer": {"lr": 1e-3, "total_steps": 100, "warmup_ratio": 0.0025,
                  "weight_decay": 0.01, "max_grad_norm": 1.0,
                  "b1": 0.9, "b2": 0.95, "eps": 1e-08},
}


def make_train_bench(tmp: Path, limits=None):
    """(Cell, spec) of a tiny training cell of qwen2-1.5b's reduced form."""
    from bench import harness as H
    cell, spec = make_bench(tmp, "qwen2-1.5b")
    bench = tmp / "bench"
    (bench / "traffic" / "tiny-train.json").write_text(json.dumps(TINY_TRAIN))
    (bench / "cells" / "tiny-train.json").write_text(json.dumps({
        "limits": limits or {"loss_gap": 1e-4, "change_gap": 1e-3}}))
    spec = H.load_json(ROOT / "BENCHMARK.json")
    spec["workloads"] = [{"name": "tiny-train", "config": "tiny-qwen2-1.5b",
                          "traffic": "tiny-train", "chips": 1,
                          "why": "tiny CPU training cell"}]
    retarget(spec, "qwen2-train-s256", "tiny-train")
    return H.Cell(spec, "tiny-train", bench_dir=bench), spec
