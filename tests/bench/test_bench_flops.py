"""FLOP and byte counts against hand arithmetic at the published shapes,
and the peak table."""
import json
from pathlib import Path

import pytest

import benchtiny  # noqa: F401  (puts the benchmark on the path)

from bench import flops as F
from bench.peaks import peak_for

CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_qwen2_forward_by_hand():
    c = config("qwen2-1.5b")
    # per layer: q 1536x1536, k and v 1536x256 each, o 1536x1536,
    # gate/up/down 3 x 1536x8960
    per_layer = 1536 * 1536 * 2 + 1536 * 256 * 2 + 3 * 1536 * 8960
    assert per_layer == 46_792_704
    assert F.transformer_layer_matmul_params(c) == per_layer
    # 10 tokens, 55 attended pairs, one LM-head row
    want = (2 * 28 * per_layer * 10 + 4 * 28 * 12 * 128 * 55
            + 2 * 1536 * 151936 * 1)
    assert F.target_flops(c, 10, 55, 1) == want
    assert F.prefill_attended(10) == 55
    # float32 KV: 28 layers x (K and V) x 2 heads x 128 x 4 bytes = 56 KiB
    assert F.transformer_kv_bytes_per_position(c, 4) == 57_344


def test_mamba2_forward_by_hand():
    c = config("mamba2-780m")
    d_inner, H, P, N = F.mamba2_dims(c)
    assert (d_inner, H, P, N) == (3072, 48, 64, 128)
    in_proj = 2 * 1536 * (2 * 3072 + 2 * 128 + 48)
    conv = 2 * 4 * (3072 + 256)
    ssm = 2 * 48 * 64 * 128 * 3
    out = 2 * 3072 * 1536
    per_token = 48 * (in_proj + conv + ssm + out)
    assert F.target_flops(c, 3, 0, 2) == per_token * 3 + 2 * 1536 * 50280 * 2
    # per slot: 48 layers x (48x64x128 float32 state + 3 x 3328 conv window)
    assert F.mamba2_state_bytes(c, 4) == 48 * (48 * 64 * 128 * 4
                                               + 3 * 3328 * 4)
    assert F.mamba2_state_bytes(c, 4) == 77_414_400


def test_drafter_of_qwen2_by_hand():
    c = config("qwen2-1.5b")
    dc = F.drafter_shape(c, {"layers": 4})
    assert (dc["num_attention_heads"], dc["head_dim"],
            dc["intermediate_size"]) == (12, 128, 5376)
    per_layer = 4 * 1536 * 1536 + 3 * 1536 * 5376
    fuse = 2 * (3 * 1536 * 1536 + 2 * 1536 * 1536)
    assert F.drafter_flops(dc, 1, 0, 0) == fuse + 2 * 4 * per_layer


def test_decode_iteration_counts_each_part_once():
    c = config("qwen2-1.5b")
    live = [100, 300]
    fl, nb = F.decode_iteration(c, live, weight_bytes=10 ** 9,
                                cache_itemsize=4, conv_itemsize=4)
    # one new position per slot: its key joins the 100 / 300 it attends
    assert fl == F.target_flops(c, 2, 101 + 301, 2)
    assert nb == 10 ** 9 + 57_344 * (400 + 2)
    m = config("mamba2-780m")
    fl, nb = F.decode_iteration(m, live, 10 ** 9, 4, 4)
    assert fl == F.target_flops(m, 2, 0, 2)
    assert nb == 10 ** 9 + 2 * 2 * F.mamba2_state_bytes(m, 4)


def test_peaks_are_keyed_by_device_kind():
    p = peak_for("TPU v5 lite")
    assert (p.flops, p.hbm_bytes_s) == (197e12, 819e9)
    with pytest.raises(KeyError):
        peak_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peak_for("cpu")
