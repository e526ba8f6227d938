"""A model family is added by files alone (``bench/families/<family>.py``
and ``bench/refs/<reference>.py`` beside the configuration, cell and mix),
and a configuration that names a family or reference with no module fails
before any device work, naming the missing file."""
import json
import os
import shutil
import subprocess
import sys
import time
from types import SimpleNamespace

import jax
import pytest

import benchtiny
from bench import harness as H
from bench import serve

# A family the harness has never seen: a dense transformer whose
# configuration file names its widths as GPT-2's config.json does. It
# builds on the dense module; the harness learns of it from this file only.
NAMES = {"n_embd": "hidden_size", "n_layer": "num_hidden_layers",
         "n_head": "num_attention_heads", "n_kv_head": "num_key_value_heads",
         "n_head_dim": "head_dim", "n_inner": "intermediate_size",
         "rope_base": "rope_theta", "layer_norm_epsilon": "rms_norm_eps",
         "qkv_bias": "attention_bias"}

# Loads a module beside the file it is in, so the family and reference
# build on the copy's own dense module and transformer reference.
SIBLING = '''
import importlib.util
from pathlib import Path


def _sibling(name):
    path = Path(__file__).with_name(f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"gpt_names_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
'''

FAMILY = f'''
"""A dense transformer with GPT-2's names for its widths."""
{SIBLING}

dense = _sibling("dense")
NAMES = {NAMES!r}


def as_dense(c):
    return dict(c, **{{d: c[k] for k, d in NAMES.items()}})


def program_pairs(cfg, tcfg):
    pairs = dense.program_pairs(as_dense(cfg), tcfg)
    return {{k: pairs[d] for k, d in NAMES.items()}}


def flops(c, past, new, head_rows):
    return dense.flops(as_dense(c), past, new, head_rows)


def decode_bytes(c, live, weight_bytes, cache_itemsize):
    return dense.decode_bytes(as_dense(c), live, weight_bytes,
                              cache_itemsize)
'''

REFERENCE = f'''
"""The plain transformer reference over GPT-2's names."""
{SIBLING}

transformer = _sibling("transformer")
NAMES = {NAMES!r}


def _c(c):
    return dict(c, **{{d: c[k] for k, d in NAMES.items()}})


def hidden(c, params, tokens, w):
    return transformer.hidden(_c(c), params, tokens, w)


def head(c, params, h, w):
    return transformer.head(_c(c), params, h, w)
'''


def gpt_names_bench(tmp_path):
    """(Cell, spec) of a tiny serving cell of a new family, ``gpt_names``,
    added as files to a copy of bench/."""
    _, spec = benchtiny.make_bench(tmp_path, "qwen2-1.5b")
    bench = tmp_path / "bench"
    (bench / "families" / "gpt_names.py").write_text(FAMILY)
    (bench / "refs" / "gpt_names_ref.py").write_text(REFERENCE)
    dense_cfg = benchtiny.tiny_config("qwen2-1.5b")
    cfg = {k: v for k, v in dense_cfg.items() if k not in NAMES.values()}
    cfg.update({k: dense_cfg[d] for k, d in NAMES.items()},
               name="tiny-gpt-names", family="gpt_names",
               reference="gpt_names_ref")
    (bench / "configs" / "tiny-gpt-names.json").write_text(json.dumps(cfg))
    (bench / "cells" / "tiny-gpt-names.cell.json").write_text(
        (bench / "cells" / "tiny-qwen2-1.5b.cell.json").read_text())
    spec["workloads"] = [{"name": "tiny-gpt-names.cell",
                          "config": "tiny-gpt-names",
                          "traffic": "tiny-closed", "chips": 1,
                          "why": "tiny CPU cell of a new family"}]
    benchtiny.retarget(spec, "tiny-qwen2-1.5b.cell", "tiny-gpt-names.cell")
    return H.Cell(spec, "tiny-gpt-names.cell", bench_dir=bench), spec, \
        dense_cfg


def test_a_new_family_runs_by_files_alone(tmp_path, monkeypatch):
    benchtiny.steer(monkeypatch)
    cell, _, dense_cfg = gpt_names_bench(tmp_path)
    assert "hidden_size" not in cell.config
    ref = cell.reference("gpt_names_ref")
    for mod in (cell.family, cell.family.dense, ref, ref.transformer):
        assert mod.__file__.startswith(str(tmp_path)), mod
    run = serve.run(cell, 2 ** 31 + 15, 2.0, False, time.perf_counter(),
                    H.CompileCounter(), jax.devices())
    line = H.result(run, cell, False)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    # the new family's counts are the dense ones of the same sizes
    w = run.window
    got = run.useful_target_flops(*w)
    run.cell = SimpleNamespace(family=H.load_family(dense_cfg))
    run.config = dense_cfg
    assert got > 0 and got == run.useful_target_flops(*w)


def test_a_new_family_checks_its_own_keys(tmp_path):
    from repro.configs import get_config
    cell, _, _ = gpt_names_bench(tmp_path)
    tcfg = get_config("qwen2-1.5b").reduced()
    H.check_config(cell.family, cell.config, tcfg)
    wrong = dict(cell.config, n_inner=cell.config["n_inner"] + 1)
    with pytest.raises(ValueError, match="n_inner"):
        H.check_config(cell.family, wrong, tcfg)


@pytest.mark.parametrize("key,name,missing", [
    ("family", "moe", "families/moe.py"),
    ("reference", "moe_ref", "refs/moe_ref.py")])
def test_a_missing_module_fails_before_any_device_work(tmp_path, key, name,
                                                       missing):
    shutil.copy(benchtiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(benchtiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = tmp_path / "bench" / "configs" / "qwen2-1.5b.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                    **{key: name})))
    spec = H.load_json(tmp_path / "BENCHMARK.json")
    with pytest.raises(FileNotFoundError, match=missing):
        H.Cell(spec, "qwen2-decode-c16", bench_dir=tmp_path / "bench")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-decode-c16",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert missing in p.stderr
    assert "not a TPU" not in p.stderr        # the device was never asked
