"""Beyond-paper Table 14 — model-sharded serving of the scheduler loop:
OTPS and per-step dispatch overhead at serving-mesh sizes 1/2/4/8.

The model-sharded engine (``EngineConfig(shard_model=True)``, see
docs/sharding.md) storage-shards weights and the paged KV pools over a 1-D
``("model",)`` mesh and gathers them at an explicit replication boundary
inside each jitted step — token-for-token lossless by construction (the
tier-1 parametrized tests pin it; this table re-asserts it per row).

On this CPU container every "device" is a forced host-platform device
carved from the same CPU, so there is no memory-capacity or FLOP win to
measure — what the table isolates is the *cost* side of the design: the
per-step dispatch + gather/scatter overhead the replication boundary adds
as the mesh grows, over an identical async workload. Reported per mesh
size: OTPS (wall), virtual-time makespan, mean per-step wall time, and the
per-step overhead vs the unsharded engine. Rows persist to
``results/table14_sharded.csv``.

Needs >= 8 jax devices. On a CPU host, start the process with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the same
forced-host-device setup as CI's tier1-multidevice lane); the flag only
takes effect before JAX first initialises, so the table raises instead of
starting a child once JAX is up.
"""
import numpy as np

MESH_SIZES = (1, 2, 4, 8)
PAGE = 8
MAX_LEN = 128


def _serve_workload(eng, prompts, budgets, arrivals):
    from repro.serving import Request, Scheduler
    sched = Scheduler(eng)
    rep = None
    for _ in range(2):                 # second run = warm, compile excluded
        rep = sched.serve([Request(p, max_new_tokens=b, arrival_time=a)
                           for p, b, a in zip(prompts, budgets, arrivals)])
    return rep


def run(epochs=15, n_requests=16, max_new=20, mean_gap=0.5):
    import jax
    if jax.device_count() < max(MESH_SIZES):
        raise RuntimeError(
            f"table14 needs {max(MESH_SIZES)} devices but jax sees "
            f"{jax.device_count()}; on a CPU host rerun with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={max(MESH_SIZES)}")

    from benchmarks.common import (get_corpus, longtail_budgets, get_target,
                                   row, train_drafter, write_results_csv)
    from repro.serving import Engine, EngineConfig
    from repro.sharding.utils import serving_mesh

    arch = "qwen2-1.5b"
    tcfg, m, tparams = get_target(arch)
    dcfg, dp, _ = train_drafter("table9_peagle_" + arch, arch=arch,
                                epochs=epochs, n_layers=4, k_train=8)

    corpus = get_corpus(arch)
    rng = np.random.default_rng(29)
    rows_ = rng.choice(len(corpus), size=n_requests, replace=False)
    prompts = [np.asarray(corpus[i, :6]) for i in rows_]
    budgets = longtail_budgets(n_requests, max_new, rng)
    arrivals = np.cumsum(rng.exponential(mean_gap, size=n_requests)).tolist()

    def make(n_shard):
        return Engine(tcfg, dcfg, tparams, dp,
                      EngineConfig(K=5, max_new_tokens=max_new,
                                   drafter_mode="parallel", max_len=MAX_LEN,
                                   kv_layout="paged", page_size=PAGE,
                                   shard_model=n_shard > 0,
                                   mesh=(serving_mesh(n_shard)
                                         if n_shard else None)),
                      batch=4)

    ref = _serve_workload(make(0), prompts, budgets, arrivals)
    ref_step_us = ref["wall_s"] / max(ref["iterations"], 1) * 1e6
    ref_tokens = [r["tokens"] for r in ref["results"]]
    out = [{"mesh": 0, "otps": round(ref["otps"], 1),
            "makespan_vt": round(ref["makespan_vt"], 1),
            "step_us": round(ref_step_us, 1), "overhead_us": 0.0,
            "lossless": True}]
    row("table14/unsharded", ref_step_us, f"otps={ref['otps']:.1f}")

    for n in MESH_SIZES:
        rep = _serve_workload(make(n), prompts, budgets, arrivals)
        step_us = rep["wall_s"] / max(rep["iterations"], 1) * 1e6
        lossless = all(np.array_equal(a, b["tokens"])
                       for a, b in zip(ref_tokens, rep["results"]))
        out.append({"mesh": n, "otps": round(rep["otps"], 1),
                    "makespan_vt": round(rep["makespan_vt"], 1),
                    "step_us": round(step_us, 1),
                    "overhead_us": round(step_us - ref_step_us, 1),
                    "lossless": lossless})
        row(f"table14/mesh{n}", step_us,
            f"otps={rep['otps']:.1f} overhead_us="
            f"{step_us - ref_step_us:.0f} lossless={lossless}")
        if not lossless:
            raise AssertionError(
                f"mesh={n} diverged from the single-device stream — the "
                "sharded engine must be token-for-token lossless")

    path = write_results_csv("table14_sharded.csv", out)
    print(f"# wrote {path}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=15)
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=20)
    ap.add_argument("--mean-gap", type=float, default=0.5)
    args = ap.parse_args()
    run(epochs=args.epochs, n_requests=args.n_requests,
        max_new=args.max_new, mean_gap=args.mean_gap)
