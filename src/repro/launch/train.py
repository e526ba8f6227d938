"""Production training launcher.

Trains a P-EAGLE drafter on a frozen target at the published widths, or,
with ``--reduced``, on the 2-layer CPU-scale variant, so the whole pipeline
(data → COD → segments → step → checkpoint) runs anywhere.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
        --reduced --epochs 10 --segments 2 --ckpt results/ckpt
"""
from __future__ import annotations

import argparse

import jax

from repro.checkpoint import save_pytree
from repro.configs import DrafterConfig
from repro.data import MTPPipeline, markov_corpus, self_generated_corpus
from repro.launch.build import init_target, use_compile_cache
from repro.models import make_extras
from repro.training import Trainer, TrainConfig


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="2-layer CPU-scale config (default: published "
                         "widths)")
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=48)
    ap.add_argument("--n-seqs", type=int, default=64)
    ap.add_argument("--k-train", type=int, default=8)
    ap.add_argument("--cod-rate", type=float, default=0.8)
    ap.add_argument("--segments", type=int, default=1)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--variant", default="shared")
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--ar-baseline", action="store_true")
    ap.add_argument("--data", default="self",
                    choices=["self", "markov"])
    ap.add_argument("--ckpt", default="results/ckpt")
    args = ap.parse_args()

    print(f"init target {args.arch} (reduced={args.reduced}) ...")
    tcfg, model, tparams = init_target(args.arch, reduced=args.reduced)
    key = jax.random.PRNGKey(0)

    if args.data == "self":
        extras_fn = ((lambda b: make_extras(tcfg, b, "prefill", key))
                     if tcfg.family in ("vlm", "encdec") else None)
        corpus = self_generated_corpus(
            model, tparams, seed=1, n_seqs=args.n_seqs,
            seq_len=args.seq_len, batch=min(16, args.n_seqs),
            extras_fn=extras_fn)
    else:
        corpus = markov_corpus(0, args.n_seqs, args.seq_len,
                               tcfg.vocab_size)

    dcfg = DrafterConfig(
        n_layers=args.layers, k_train=args.k_train, cod_rate=args.cod_rate,
        hidden_state_variant=args.variant,
        parallel=not args.ar_baseline).resolve(tcfg)
    pipe = MTPPipeline(corpus, k_train=dcfg.k_train,
                       cod_rate=dcfg.cod_rate, batch=args.batch, seed=0,
                       segments=args.segments)
    extras = (make_extras(tcfg, args.batch, "train", key)
              if tcfg.family in ("vlm", "encdec") else {})
    steps = args.epochs * max(len(corpus) // args.batch, 1)
    tr = Trainer(tcfg, dcfg, tparams, TrainConfig(lr=args.lr,
                                                  total_steps=steps),
                 extras=extras)
    tr.train(pipe, epochs=args.epochs, log_every=5)
    fn = save_pytree(tr.dparams, args.ckpt,
                     f"drafter_{args.arch}", step=steps)
    print(f"saved {fn}")


if __name__ == "__main__":
    main()
