"""The training cell on the CPU at a tiny size: the reference repeats the
trainer's first steps, a step that returns its state unchanged comes out
not correct, and so does the bfloat16 control put in the program's
place."""
import time

import jax
import numpy as np
import pytest

import benchtiny
from bench import harness as H
from bench import train


def _run(tmp_path, control=False):
    cell, _ = benchtiny.make_train_bench(tmp_path)
    return cell, train.run(cell, 2 ** 31 + 7, 1.5, False, time.perf_counter(),
                           H.CompileCounter(), jax.devices(), control=control)


def test_tiny_training_matches_its_reference(tmp_path, monkeypatch):
    benchtiny.steer(monkeypatch)
    cell, r = _run(tmp_path)
    assert all(c.ok for c in r.checks), [(c.name, c.value) for c in r.checks]
    assert r.steps > 0 and r.failed == 0
    line = H.result(r, cell, False)
    assert line["correct"] is True
    assert set(line["checks"]) == {"loss_gap", "change_gap"}
    assert line["metrics"]["train_tok_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged"])
def test_broken_step_is_not_correct(tmp_path, monkeypatch, fault):
    import repro.training.trainer as TT
    benchtiny.steer(monkeypatch)
    monkeypatch.setattr(TT, "apply_updates", lambda params, updates: params)
    cell, r = _run(tmp_path)
    checks = {c.name: c for c in r.checks}
    assert not checks["change_gap"].ok
    assert checks["change_gap"].value == pytest.approx(1.0, abs=1e-3)
    assert H.result(r, cell, False)["correct"] is False


def test_bfloat16_control_is_not_correct(tmp_path, monkeypatch):
    benchtiny.steer(monkeypatch)
    cell, r = _run(tmp_path, control=True)
    assert H.result(r, cell, False)["correct"] is True
    line = H.control_result(r, cell)
    assert line["correct"] is False
    assert set(line["checks"]) == {"loss_gap", "change_gap"}


def test_mask_pairs_by_hand():
    # depth-0 chain of 3 is causal (6 pairs); (g=1, p=2) anchors at 1: sees
    # (0, 0), (0, 1) and itself; padding sees nothing
    pos = np.array([0, 1, 2, 2, -1])
    depth = np.array([0, 0, 0, 1, -1])
    assert train.mask_pairs(pos, depth) == 6 + 3
