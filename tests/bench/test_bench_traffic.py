"""The traffic generator is a function of the seed, every seed gets the
same sizes, and a cell added as data files alone is found by name."""
import json

import numpy as np
import pytest

import benchtiny
from bench import harness as H
from bench import traffic as T

MIX = json.loads((benchtiny.BENCH / "traffic" / "decode-c16.json").read_text())


def sizes(reqs):
    return (sorted(r.prompt.size for r in reqs),
            sorted(r.max_new for r in reqs))


@pytest.mark.parametrize("seed", [2 ** 31 + 12345, 7])   # past 32 bits too
def test_plan_is_a_function_of_the_seed(seed):
    mix = MIX
    a = T.plan(mix, seed, 151936, 64)
    b = T.plan(mix, seed, 151936, 64)
    c = T.plan(mix, seed + 1, 151936, 64)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               and x.client == y.client for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    assert sizes(a) == sizes(c)             # same work, another order
    assert all(0 <= r.prompt.min() and r.prompt.max() < 151936 - 2 for r in a)


def test_sizes_cover_the_mix_log_uniformly():
    reqs = T.plan(MIX, 7, 1000, 64)
    lens = [r.prompt.size for r in reqs]
    outs = [r.max_new for r in reqs]
    assert min(lens) >= 64 and max(lens) <= 512
    assert min(outs) >= 128 and max(outs) <= 1024
    assert len(set(lens)) == MIX["prompt_sizes"]
    assert set(lens) == set(T.prompt_lengths(MIX))
    # log-uniform: the geometric midpoint splits the sizes in half
    assert sum(o < (128 * 1024) ** 0.5 for o in outs) == 32
    assert [r.client for r in reqs[:17]] == list(range(16)) + [0]


def test_only_a_closed_loop_is_planned():
    with pytest.raises(ValueError):
        T.plan(dict(MIX, loop="open"), 3, 1000, 16)


def test_stagger_puts_clients_out_of_phase():
    firsts = [T.stagger(MIX, i, 1000) for i in range(16)]
    assert firsts == sorted(firsts) and len(set(firsts)) == 16


def test_a_cell_added_as_data_files_is_found_by_name(tmp_path):
    cell, spec = benchtiny.make_bench(tmp_path, "qwen2-1.5b")
    found = H.Cell(spec, cell.name, bench_dir=tmp_path / "bench")
    assert found.config["program_arch"] == "qwen2-1.5b"
    assert found.mix["engine"]["slots"] == 2
    assert found.check["limits"]["widest_gap"] > 0
    assert {m["name"] for m in found.metrics(False)} == {
        "output_tok_s", "tpot_p95_ms", "setup_s"}
    for m in found.metrics(True) + found.metrics(False):
        assert callable(found.reader(m["name"]).read)
    with pytest.raises(KeyError):
        H.Cell(spec, "no-such-cell", bench_dir=tmp_path / "bench")


def test_every_listed_cell_has_its_files():
    spec = H.load_json(benchtiny.ROOT / "BENCHMARK.json")
    for w in spec["workloads"]:
        cell = H.Cell(spec, w["name"])
        assert cell.runner().run
        for m in cell.metrics(True) + cell.metrics(False):
            assert callable(cell.reader(m["name"]).read)
