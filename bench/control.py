"""Readings that a cell's limits are set from.

    python3 bench/control.py --workload <name> --seeds 1-12 --seconds 30

In one process, for each seed: one run of the cell as the benchmark makes
it (its own traffic or batch, its sizes, a window of ``--seconds``), with
the program's numbers compared, and the control's on the same inputs. A
serving cell's control is the reference with its weights rounded to
float8 e4m3 (``bench/correct.py``), the next precision below the target's
bfloat16; a training cell's is the reference computed in bfloat16, the
next below the drafter's float32 (``bench/train.py``). Both are judged by
the code that decides ``correct`` (``harness.result``), with the cell's
limits. One JSON line per seed, then for each number the lower reading
(the largest the program gives) and the upper one (the smallest the
control gives). Exits non-zero where a sound run comes out not correct or
a control comes out correct. Needs a TPU, like ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def seed_list(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1-12 or 3,5,8")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness as H
    cell = H.Cell(H.load_json(ROOT / "BENCHMARK.json"), args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        H.log("bench/control.py needs the cell's TPU chips")
        return 2
    from repro.launch.build import use_compile_cache
    use_compile_cache()
    runner = cell.runner()
    counter = H.CompileCounter()
    prog, ctrl, wrong = [], [], 0
    for seed in seed_list(args.seeds):
        run = runner.run(cell, seed, args.seconds, False, time.perf_counter(),
                         counter, devices, control=True)
        line = H.result(run, cell, False)
        cline = H.control_result(run, cell)
        checks = {k: v["value"] for k, v in line["checks"].items()}
        control = {k: v["value"] for k, v in cline["checks"].items()}
        wrong += (not line["correct"]) + cline["correct"]
        prog.append(checks)
        ctrl.append(control)
        print(json.dumps({"seed": seed, "program": checks,
                          "program_correct": line["correct"],
                          "control": control,
                          "control_correct": cline["correct"],
                          "setup_s": run.setup_s}), flush=True)
        del run
        gc.collect()
    for name in ctrl[0]:
        lower = max(p[name] for p in prog)
        upper = min(c[name] for c in ctrl)
        print(json.dumps({"workload": args.workload, "number": name,
                          "lower_reading": lower, "upper_reading": upper,
                          "ratio": upper / lower if lower else None}),
              flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
