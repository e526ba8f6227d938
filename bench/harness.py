"""Finds a cell's pieces by name and assembles its result line.

Everything that belongs to one configuration, one traffic mix, one cell or
one metric is a file of its own, found by the name ``BENCHMARK.json``
gives it:

  bench/configs/<config>.json   the configuration's sizes as run, with its
                                source, its cuts, its family's and its
                                reference's names
  bench/families/<family>.py    what the harness knows of one model family:
                                ``program_pairs(cfg, tcfg)`` (file key ->
                                the program's value, checked before any
                                device work), ``flops(c, past, new,
                                head_rows)`` (one sequence's forward over
                                ``new`` positions after ``past`` cached
                                ones) and ``decode_bytes(c, live,
                                weight_bytes, cache_itemsize)`` (one
                                decode iteration);
                                optionally ``weight(name, key, shape,
                                dtype)`` for leaves of its own layers
  bench/refs/<reference>.py     the plain reference of that family
  bench/traffic/<traffic>.json  the traffic mix: parameters of the one
                                generator (bench/traffic.py) and the kind
                                of run (``kind``: a runner bench/<kind>.py)
  bench/cells/<workload>.json   what the cell compares and each limit
  bench/metrics/<metric>.py     one metric's reader: ``read(run)`` returns
                                a number, or None where the run has nothing
                                for it to read

A cell, configuration, family, mix or metric is added by adding such
files and entries, without editing any file that is there. Family and
reference modules, like metric readers, are loaded from the cell's own
``bench`` directory; a missing one fails before any device work.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Any, Dict, List

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, spec: dict, workload: str, bench_dir: Path = BENCH_DIR):
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
        self.spec = spec
        self.bench_dir = bench_dir
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.config = load_json(bench_dir / "configs"
                                / f"{self.entry['config']}.json")
        self.mix = load_json(bench_dir / "traffic"
                             / f"{self.entry['traffic']}.json")
        self.check = load_json(bench_dir / "cells" / f"{workload}.json")
        self.family = load_family(self.config, bench_dir)
        _need(bench_dir / "refs" / f"{self.config['reference']}.py",
              "reference", f"configuration {self.entry['config']!r}")
        if "reference" in self.mix:
            _need(bench_dir / "refs" / f"{self.mix['reference']}.py",
                  "reference", f"traffic mix {self.entry['traffic']!r}")

    def reference(self, name: str):
        """The plain reference module ``<bench>/refs/<name>.py``."""
        return load_module(self.bench_dir / "refs" / f"{name}.py",
                           f"bench_ref_{name}")

    def metrics(self, traced: bool) -> List[dict]:
        """The cell's end-to-end metrics (untraced) or per-layer metrics
        (traced): those whose ``workloads`` list names it, or that have no
        such list."""
        group = self.spec["per_layer" if traced else "end_to_end"]
        return [m for m in group
                if "workloads" not in m or self.name in m["workloads"]]

    def reader(self, metric: str):
        return load_module(self.bench_dir / "metrics" / f"{metric}.py",
                           f"bench_metric_{metric.replace('.', '_')}")

    def runner(self):
        """The runner module of the mix's ``kind`` (``bench/<kind>.py``)."""
        import importlib
        return importlib.import_module(f"bench.{self.mix['kind']}")


def _need(path: Path, what: str, owner: str) -> Path:
    if not path.is_file():
        raise FileNotFoundError(f"{owner} names a {what} with no module: "
                                f"{path} is missing")
    return path


def load_family(config: dict, bench_dir: Path = BENCH_DIR):
    """The family module ``<bench>/families/<family>.py`` of a
    configuration file."""
    name = config["family"]
    path = _need(bench_dir / "families" / f"{name}.py", "family",
                 f"configuration {config['name']!r}")
    return load_module(path, f"bench_family_{name}")


def check_config(family, cfg: dict, tcfg) -> None:
    """The program's configuration must be the file's, size for size: the
    family's keys and the served dtype."""
    pairs = dict(family.program_pairs(cfg, tcfg), dtype=tcfg.dtype)
    bad = {k: (cfg[k], v) for k, v in pairs.items() if cfg[k] != v}
    if bad:
        raise ValueError(f"the program's {cfg['program_arch']} differs from "
                         f"the configuration file: {bad}")


def seed32(seed: int, salt: int = 0) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` from any whole ``seed``."""
    ss = np.random.SeedSequence([int(seed) & (2 ** 64 - 1), salt])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


class CompileCounter:
    """Counts the programs this process lowers (each one a jit cache miss:
    compiled, or read from the persistent cache) through JAX's monitoring
    events."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self._mark = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1

    def mark(self) -> None:
        self._mark = self.count

    def since_mark(self) -> int:
        return self.count - self._mark


class Check:
    """One number compared with its limit; passes when value <= limit."""

    def __init__(self, name: str, value: float, limit: float):
        self.name, self.value, self.limit = name, float(value), float(limit)

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(devices, chips: int) -> Dict[str, Any]:
    used = devices[:chips]
    peaks = []
    for d in used:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": max(peaks)}


def result(run, cell: Cell, traced: bool) -> Dict[str, Any]:
    """The result line of a finished run: the cell's metrics read by their
    readers (a reader that finds nothing is left out), the checks last."""
    metrics = {}
    for m in cell.metrics(traced):
        value = cell.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    checks = run.checks
    line: Dict[str, Any] = {
        "correct": bool(checks) and all(c.ok for c in checks),
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": metrics,
        "device": dict(run.device),
    }
    if traced and run.trace is not None:
        line["device"]["busy_s"] = run.trace.busy_s
        line["device"]["window_s"] = run.trace.window_s
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.trace.device_ops],
            "idle_gaps": [[n, s] for n, s in run.trace.idle_gaps]}
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    for c in checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAILED'}")
    return line


def control_result(run, cell: Cell) -> Dict[str, Any]:
    """The result line the run would print with its control in the
    program's place: the same checks and limits, the control's readings
    (``run.control_checks``), decided by ``result``."""
    import dataclasses
    return result(dataclasses.replace(run, checks=run.control_checks), cell,
                  False)


def now() -> float:
    return time.perf_counter()
