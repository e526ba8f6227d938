"""Distribution-layer tests.

Multi-device lowering runs in a SUBPROCESS (jax locks the device count on
first init, and the rest of the suite needs the real single CPU device).
The subprocess uses reduced configs + scaled-down shapes on a (2,2,2) debug
mesh — structurally the same code path as the 512-chip production dry-run.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.launch import roofline as RL

ROOT = os.path.join(os.path.dirname(__file__), "..")

_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax
from repro.configs import get_config
from repro.configs.base import InputShape
import repro.launch.steps as S
from repro.launch.mesh import make_debug_mesh
from repro.launch.steps import (build_prefill_step, build_serve_step,
                                build_train_step, resolve_drafter)
from repro.sharding.utils import mesh_scope

S.INPUT_SHAPES = dict(S.INPUT_SHAPES)
S.INPUT_SHAPES["train_4k"] = InputShape("train_4k", 64, 8, "train")
S.INPUT_SHAPES["decode_32k"] = InputShape("decode_32k", 128, 8, "decode")

arch, kind = sys.argv[1], sys.argv[2]
tcfg = get_config(arch).reduced()
dcfg = resolve_drafter(tcfg, n_layers=2, remat=True)
mesh = make_debug_mesh(2, 2, multi_pod=True)
if kind == "train":
    fn, mi = build_train_step(tcfg, dcfg, "train_4k", n_micro=2)
    order = ["tparams", "dparams", "opt_state", "tokens", "pos", "depth",
             "labels", "rng"]
elif kind == "decode":
    fn, mi = build_serve_step(tcfg, dcfg, "decode_32k", K=3)
    order = ["tparams", "dparams", "state"]
args, extras, sh, exsh = mi(mesh)
av = [args[k] for k in order]
sv = [sh[k] for k in order]
if kind == "train":
    av.append(extras); sv.append(exsh)
with mesh_scope(mesh):
    comp = jax.jit(fn, in_shardings=tuple(sv)).lower(*av).compile()
cost = comp.cost_analysis()
if isinstance(cost, list):        # jax 0.4.x: one dict per device
    cost = cost[0] if cost else {}
txt = comp.as_text()
n_coll = sum(txt.count(k) for k in
             ("all-reduce", "all-gather", "reduce-scatter", "all-to-all"))
print(json.dumps({"flops": float(cost.get("flops", 0)),
                  "collectives": n_coll}))
"""


def _run(arch, kind):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", _SUBPROC, arch, kind],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow          # multi-device subprocess compile, ~5-15 s each
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "dbrx-132b", "mamba2-780m"])
def test_multipod_train_lowers(arch):
    r = _run(arch, "train")
    assert r["flops"] > 0
    assert r["collectives"] > 0    # model-sharded training must communicate


@pytest.mark.slow          # multi-device subprocess compile, ~5-15 s each
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "recurrentgemma-2b"])
def test_multipod_decode_lowers(arch):
    r = _run(arch, "decode")
    assert r["flops"] > 0


# ---------------------------------------------------------------------------
# roofline unit tests (pure parsing, no devices)
# ---------------------------------------------------------------------------

def test_collective_parser():
    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(%x), dims={0}
  %ar.1 = f32[16,16]{1,0} all-reduce(%y), to_apply=%add
  %rs = f32[4]{0} reduce-scatter(%z), dimensions={0}
  %cp = u32[2]{0} collective-permute(%w)
  %a2a = bf16[8,8]{1,0} all-to-all(%v), dimensions={1}
  %ars = f32[16,16]{1,0} all-reduce-start(%y2), to_apply=%add
"""
    st = RL.collective_stats(hlo)
    assert st["all-gather"]["count"] == 1
    assert st["all-gather"]["bytes"] == 8 * 128 * 2
    assert st["all-reduce"]["count"] == 2          # sync + async start
    assert st["reduce-scatter"]["bytes"] == 16
    assert st["all-to-all"]["count"] == 1
    assert st["collective-permute"]["bytes"] == 8


def test_roofline_terms_bottleneck():
    cost = {"flops": 197e12, "bytes accessed": 819e9 * 2}
    coll = {"all-gather": {"count": 1, "bytes": 50e9}}
    t = RL.roofline_terms(cost, coll, 256)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(2.0)
    assert t["collective_s"] == pytest.approx(1.0)
    assert t["bottleneck"] == "memory_s"


def test_param_count_sane():
    from repro.configs import get_config
    n = RL.param_count(get_config("qwen2-1.5b"))
    assert 1.2e9 < n < 2.2e9
    n_moe_total = RL.param_count(get_config("dbrx-132b"))
    n_moe_active = RL.param_count(get_config("dbrx-132b"), active_only=True)
    assert 1.1e11 < n_moe_total < 1.6e11
    assert n_moe_active < n_moe_total / 2.5
