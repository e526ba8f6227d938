"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, checks and metric readers are
found by name from ``BENCHMARK.json`` (see ``bench/harness.py``). The run
needs a TPU: where JAX's first device is not one, or there are fewer chips
than the cell asks for, it exits non-zero and prints no result. JAX's
persistent compilation cache is kept at one fixed path inside the checkout
(``launch/build.py::use_compile_cache``), so only a cell's first run there
compiles.

With ``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of part of the window is taken and the line
carries the per-layer metrics, the device's busy and window seconds and
the ``breakdown``. The numbers compared to decide ``correct`` are printed
beside their limits as the last lines of standard error and under
``checks``, the last key of the line, which is the last line of standard
output.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness as H
    cell = H.Cell(H.load_json(ROOT / "BENCHMARK.json"), args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        H.log(f"bench: the first JAX device is {devices[0].platform!r}, not "
              "a TPU; nothing was run")
        return 2
    if len(devices) < cell.chips:
        H.log(f"bench: {args.workload} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}")
        return 2
    from repro.launch.build import use_compile_cache
    H.log(f"device {devices[0].device_kind} x{len(devices)}; compile cache "
          f"{use_compile_cache()}")
    line = execute(cell, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(line), flush=True)
    return 0


def execute(cell, seed: int, seconds: float, traced: bool, devices,
            t_start: float = T_START) -> dict:
    """Run ``cell`` on ``devices`` (no device check: the caller's) and
    return its result line."""
    from bench import harness as H
    counter = H.CompileCounter()
    run = cell.runner().run(cell, seed, seconds, traced, t_start, counter,
                            devices)
    return H.result(run, cell, traced)


if __name__ == "__main__":
    sys.exit(main())
