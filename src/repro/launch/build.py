"""Construction shared by the launchers and ``chip_smoke.py``: the
persistent compile cache, config → target params, and drafter → engine.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

from repro.checkpoint import load_pytree
from repro.configs import DrafterConfig, get_config
from repro.core import drafter as D
from repro.models import get_model
from repro.serving import Engine, EngineConfig

REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed directory and
    return it: ``$JAX_COMPILATION_CACHE_DIR`` when set (the directory JAX
    itself reads from that variable), else ``<repo root>/.jax_cache``. The
    path is part of each entry's key, so it never varies between runs."""
    path = os.environ.get(CACHE_ENV) or str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def init_target(arch: str, *, reduced: bool = False, seed: int = 0):
    """(config, model, params) of target ``arch``; ``reduced`` is the
    2-layer CPU-scale variant, else the published widths."""
    tcfg = get_config(arch)
    if reduced:
        tcfg = tcfg.reduced()
    model = get_model(tcfg)
    return tcfg, model, model.init(jax.random.PRNGKey(seed))


def build_engine(tcfg, tparams, ecfg: EngineConfig, batch: int, *,
                 layers: int = 4, dparams: Optional[dict] = None,
                 ckpt: Optional[str] = None, seed: int = 0) -> Engine:
    """Serving engine over ``tcfg``. Unless ``ecfg.drafter_mode`` is
    "none", the drafter is ``dparams`` when given, else the checkpoint
    ``drafter_<arch>`` under ``ckpt`` (see :func:`load_drafter`)."""
    dcfg = None
    if ecfg.drafter_mode == "none":
        dparams = None
    else:
        dcfg = DrafterConfig(n_layers=layers, k_infer=ecfg.K).resolve(tcfg)
        if dparams is None:
            dparams = load_drafter(dcfg, tcfg, ckpt, seed)
    return Engine(tcfg, dcfg, tparams, dparams, ecfg, batch)


def load_drafter(dcfg, tcfg, ckpt: Optional[str], seed: int) -> dict:
    """Drafter params from checkpoint ``drafter_<arch>`` under ``ckpt``. A
    checkpoint that does not exist gives a drafter initialised from
    ``seed``, and says so; any other load error raises."""
    fresh = D.init_params(dcfg, tcfg, jax.random.PRNGKey(seed))
    name = f"drafter_{tcfg.arch_id}"
    if ckpt is not None:
        try:
            dparams = load_pytree(fresh, ckpt, name)
        except FileNotFoundError:
            pass
        else:
            print(f"loaded drafter checkpoint {name} from {ckpt}")
            return dparams
    where = f" under {ckpt}" if ckpt is not None else ""
    print(f"no drafter checkpoint {name}{where}; drafter initialised from "
          f"seed {seed}")
    return fresh
