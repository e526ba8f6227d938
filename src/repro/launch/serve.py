"""Serving launcher: load a trained drafter checkpoint and serve a stream of
requests through the event-driven continuous-batching scheduler, printing
per-request and aggregate OTPS / acceptance / latency stats.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
        --ckpt results/ckpt --mode parallel --k 5 --requests 12

``--temperature/--top-p/--top-k/--seed`` set the per-request decoding
policy (serving/sampling.SamplingParams): temperature 0 (default) is greedy
verification; temperature > 0 runs seeded lossless rejection sampling
against the warped target distribution, each request on its own
deterministic PRNG stream (``seed + i``, bitwise reproducible across runs
and slot placements). ``--mixed-sampling`` alternates greedy and sampled
requests through ONE batch — the mixed-policy step the redesign enables.

``--mean-gap G`` spaces request arrivals by Exp(G) gaps on the scheduler's
deterministic virtual clock (0 = everything arrives at t=0); async runs
report virtual-time p50/p99 latency and queue wait plus preemption counts.
``--kv-growth upfront`` restores PR-2's static admission sizing,
``--no-preempt`` disables eviction (slots stall on pool exhaustion instead).
``--swap host`` turns preemption into swap-to-host: the victim's pages move
to a byte-budgeted host pool (``--host-pool-bytes``) and resume is a device
scatter instead of a recompute-prefill — same token streams, no prefill
FLOPs re-paid.
``--round-based`` serves the same queue with the pre-scheduler baseline
(batch refilled only between full generation rounds) for comparison.
vlm/encdec targets serve through the scheduler like everything else —
per-request frontend extras (vision/encoder embeds) are synthesized as
deterministic stubs at admission.

``--shard-model N`` serves model-sharded: weights and full-length KV (page
pools included) are storage-sharded over a 1-D ``("model",)`` mesh of N
devices, token-for-token identical to the single-device engine (see
docs/sharding.md). On a CPU host, force host devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.serve --reduced \
        --shard-model 8 ...
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.launch.build import build_engine, init_target, use_compile_cache
from repro.serving import (Engine, EngineConfig, Request, SamplingParams,
                           Scheduler, serve_round_based)
from repro.sharding.utils import serving_mesh


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="2-layer CPU-scale config (default: published "
                         "widths)")
    ap.add_argument("--ckpt", default="results/ckpt")
    ap.add_argument("--mode", default="parallel",
                    choices=["parallel", "ar", "none"])
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy "
                         "verification, the lossless-vs-AR default)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 disables)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k filter (0 disables)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed; request i uses seed + i "
                         "(deterministic per-request PRNG streams)")
    ap.add_argument("--mixed-sampling", action="store_true",
                    help="alternate greedy and sampled requests in one "
                         "batch (even i greedy, odd i at --temperature)")
    ap.add_argument("--sync-every", type=int, default=1,
                    help="speculative iterations between scheduler host syncs")
    ap.add_argument("--round-based", action="store_true",
                    help="also run the round-based baseline on the same queue")
    ap.add_argument("--kv-layout", default="contiguous",
                    choices=["contiguous", "paged"],
                    help="paged = block-table KV pool; admission claims "
                         "ceil(need/page) pages instead of a max_len row")
    ap.add_argument("--page-size", type=int, default=16,
                    help="positions per KV page (paged layout)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="page-pool size; 0 = batch * max_len/page_size")
    ap.add_argument("--no-bucket", action="store_true",
                    help="disable power-of-two bucketing of admission "
                         "prefills (retraces per distinct prompt length)")
    ap.add_argument("--mean-gap", type=float, default=0.0,
                    help="mean exponential inter-arrival gap in virtual "
                         "steps (Poisson arrivals); 0 = all requests at t=0")
    ap.add_argument("--kv-growth", default="incremental",
                    choices=["incremental", "upfront"],
                    help="paged admission sizing: grow pages as slots "
                         "lengthen (incremental) or reserve prompt+budget "
                         "up front (PR-2 baseline)")
    ap.add_argument("--no-preempt", action="store_true",
                    help="never evict a running slot on pool exhaustion; "
                         "slots stall until pages free up")
    ap.add_argument("--swap", default="none", choices=["none", "host"],
                    help="preemption flavor: host = copy the victim's pages "
                         "(KV + stream state + sampling rows) to a host "
                         "pool and resume by device scatter instead of "
                         "recompute-prefill (paged layout only; lossless "
                         "either way)")
    ap.add_argument("--host-pool-bytes", type=int, default=0,
                    help="host swap-pool byte budget (0 = unbounded); when "
                         "full, preemption falls back to recompute-prefill")
    ap.add_argument("--adaptive-k", action="store_true",
                    help="per-request dynamic draft length: an acceptance "
                         "EMA per request sets k_row <= K via the jitted "
                         "step's max-K mask (serving/speculation.py); easy "
                         "rows speculate deep, hard rows stop burning "
                         "verify FLOPs and page headroom")
    ap.add_argument("--draft-sampling", action="store_true",
                    help="sample drafts from the row-warped drafter "
                         "distribution for temperature > 0 requests (the "
                         "rejection proposal q becomes that distribution "
                         "instead of the argmax one-hot); greedy requests "
                         "are unchanged")
    ap.add_argument("--shard-model", type=int, default=0, metavar="N",
                    help="storage-shard weights + full-length KV over a 1-D "
                         "(model,) mesh of N devices (0 = single-device); "
                         "lossless — output is token-for-token identical")
    args = ap.parse_args()
    if args.mixed_sampling and args.temperature <= 0:
        raise SystemExit(
            "--mixed-sampling alternates greedy and sampled requests, but "
            "--temperature is 0 (greedy) so every request would be greedy; "
            "pass --temperature > 0, e.g. --temperature 0.8")
    if args.shard_model > jax.device_count():
        raise SystemExit(
            f"--shard-model {args.shard_model} needs {args.shard_model} "
            f"devices but jax sees {jax.device_count()}; on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N first")

    tcfg, _, tparams = init_target(args.arch, reduced=args.reduced)
    mesh = serving_mesh(args.shard_model) if args.shard_model else None
    eng = build_engine(
        tcfg, tparams,
        EngineConfig(K=args.k, max_new_tokens=args.max_new,
                     drafter_mode=args.mode, max_len=256,
                     kv_layout=args.kv_layout, page_size=args.page_size,
                     pool_pages=args.pool_pages,
                     bucket_prefill=not args.no_bucket,
                     kv_growth=args.kv_growth,
                     shard_model=args.shard_model > 0, mesh=mesh,
                     draft_sampling=args.draft_sampling, swap=args.swap,
                     host_pool_bytes=args.host_pool_bytes),
        args.batch, layers=args.layers, ckpt=args.ckpt)
    if mesh is not None:
        print(f"model-sharded over {mesh.shape['model']} devices "
              f"(mesh axes {mesh.axis_names}); storage-sharded weights + "
              "KV pools, replicated compute — lossless")
    rng = np.random.default_rng(3)
    # varied prompt lengths exercise bucketed admission; the round-based
    # baseline prefills whole batches, so give it equal lengths to compare
    # the two disciplines on an identical workload
    plen = (lambda: 8) if args.round_based else (
        lambda: int(rng.integers(4, 13)))
    prompts = [rng.integers(0, tcfg.vocab_size - 2,
                            size=plen()).astype(np.int32)
               for _ in range(args.requests)]
    budgets = rng.integers(max(args.max_new // 2, 1), args.max_new + 1,
                           size=args.requests).tolist()
    arrivals = (np.cumsum(rng.exponential(args.mean_gap,
                                          size=args.requests)).tolist()
                if args.mean_gap > 0 else [0.0] * args.requests)
    if args.round_based and tcfg.family in ("vlm", "encdec"):
        raise SystemExit(
            "--round-based is a whole-batch loop without per-request "
            "extras; serve vlm/encdec through the scheduler (default)")

    def params_for(i: int):
        if args.temperature <= 0 or (args.mixed_sampling and i % 2 == 0):
            return SamplingParams.greedy(seed=args.seed + i)
        return SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.seed + i)
    sps = [params_for(i) for i in range(args.requests)]
    n_sampled = sum(not sp.is_greedy for sp in sps)
    if n_sampled:
        print(f"sampling: {n_sampled}/{args.requests} requests at "
              f"T={args.temperature} top_k={args.top_k} top_p={args.top_p} "
              f"(seeds {args.seed}..{args.seed + args.requests - 1}; "
              "deterministic per-request streams)")

    # vlm/encdec requests need no explicit extras here: admission
    # synthesizes deterministic per-prompt stub frontend inputs (real
    # deployments attach actual vision/audio features via Request.extras)
    sched = Scheduler(eng, eos_id=args.eos_id, sync_every=args.sync_every,
                      preempt=False if args.no_preempt else None,
                      adaptive_k=args.adaptive_k)
    rep = None
    for _ in range(2):      # second run = warm, compile excluded
        rep = sched.serve([Request(p, max_new_tokens=b, arrival_time=a,
                                   sampling=sp)
                           for p, b, a, sp in zip(prompts, budgets, arrivals,
                                                  sps)])
    print(f"mode={args.mode} K={args.k} batch={args.batch} "
          f"requests={rep['n_requests']}: OTPS={rep['otps']:.1f} "
          f"AL={rep['weighted_acceptance_length']:.2f} "
          f"({rep['total_new_tokens']} tokens, {rep['iterations']} iterations,"
          f" mean latency {rep['mean_latency_s'] * 1e3:.0f} ms)")
    if args.adaptive_k:
        spec = rep["speculation"]
        print(f"adaptive-K: mean_k={spec['mean_k']:.2f} "
              f"(min {spec['min_k']} / max {spec['max_k']} of K={args.k})")
    if args.mean_gap > 0 or rep["preemptions"]:
        print(f"async: makespan={rep['makespan_vt']:.1f} vt  "
              f"latency p50/p99={rep['p50_latency_vt']:.1f}/"
              f"{rep['p99_latency_vt']:.1f} vt  "
              f"wait p50/p99={rep['p50_wait_vt']:.1f}/"
              f"{rep['p99_wait_vt']:.1f} vt  "
              f"preemptions={rep['preemptions']}")
    if args.swap == "host":
        hp = rep["host_pool"]
        print(f"swap-to-host: {rep['preempt_swap']} swapped / "
              f"{rep['preempt_recompute']} recomputed / "
              f"{rep['swap_drops']} dropped  "
              f"recomputed_prefill_tokens={rep['recomputed_prefill_tokens']}"
              f"  host pool peak {hp['peak_bytes']} B"
              + (f" of {hp['capacity_bytes']}" if hp["capacity_bytes"]
                 else " (unbounded)"))
    for r in rep["results"]:
        pre = f"  preempt={r['n_preempt']}" if r["n_preempt"] else ""
        print(f"  req {r['rid']:3d}: {r['n_new']:3d} tok in {r['iters']:3d} "
              f"iters  AL={r['acceptance_length']:.2f}  "
              f"latency={r['latency_s'] * 1e3:6.1f} ms{pre}")
    if eng.paged:
        print(f"paged KV: {eng.pool_pages} pages x {args.page_size} "
              f"positions shared by {args.batch} slots, {args.kv_growth} "
              f"growth (peak {eng.allocator.peak_used} pages, "
              f"{eng.allocator.n_free} free after drain)")

    if args.round_based:
        rb_eng = eng
        if eng.paged:
            # the round-based baseline is a whole-batch loop (one contiguous
            # state per round) — paged states are scheduler-only
            rb_eng = Engine(tcfg, eng.dcfg, tparams, eng.dparams,
                            EngineConfig(K=args.k,
                                         max_new_tokens=args.max_new,
                                         drafter_mode=args.mode, max_len=256),
                            args.batch)
        rb = None
        for _ in range(2):      # same per-request budgets as the scheduler
            rb = serve_round_based(rb_eng, prompts, budgets)
        print(f"round-based baseline: OTPS={rb['otps']:.1f} "
              f"({rb['rounds']} rounds) → continuous is "
              f"{rep['otps'] / max(rb['otps'], 1e-9):.2f}x")


if __name__ == "__main__":
    main()
