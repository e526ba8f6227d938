"""The serving check fails what it must: a run whose timed path alters the
tokens it produces comes out not correct, and the float8 control, put
through the code that decides ``correct``, comes out not correct where
sound runs meet the limit. A mix that names a drafter is refused."""
import time

import jax
import jax.numpy as jnp
import pytest

import benchtiny
from bench import harness as H
from bench import run as R
from bench import serve


def altered_step(real):
    """speculative_step with every token it commits moved to the next id."""
    def step(model, tcfg, dcfg, ecfg, tparams, dparams, state, **kw):
        out = real(model, tcfg, dcfg, ecfg, tparams, dparams, state, **kw)
        idx = jnp.arange(out["tokens"].shape[1])[None]
        new = (idx > state["last"][:, None]) & (idx <= out["last"][:, None])
        out["tokens"] = jnp.where(new, (out["tokens"] + 1)
                                  % (tcfg.vocab_size - 2), out["tokens"])
        return out
    return step


@pytest.mark.parametrize("arch", ["qwen2-1.5b"])
def test_token_altered_where_produced_is_not_correct(tmp_path, monkeypatch,
                                                     arch):
    import repro.serving.engine as E
    benchtiny.steer(monkeypatch)
    monkeypatch.setattr(E, "speculative_step",
                        altered_step(E.speculative_step))
    cell, _ = benchtiny.make_bench(tmp_path, arch)
    line = R.execute(cell, 5, 2.0, False, jax.devices(),
                     t_start=time.perf_counter())
    assert line["correct"] is False
    gap = line["checks"]["widest_gap"]
    assert gap["value"] > gap["limit"]
    # the front end streamed what the engine committed, at full budget:
    # only the comparison with the reference sees the fault
    assert line["checks"]["stream_mismatch"]["value"] == 0
    assert line["checks"]["budget_errors"]["value"] == 0


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mamba2-780m"])
def test_float8_control_fails_the_limit(tmp_path, monkeypatch, arch):
    benchtiny.steer(monkeypatch)
    cell, _ = benchtiny.make_bench(tmp_path, arch)
    run = serve.run(cell, 11, 2.0, False, time.perf_counter(),
                    H.CompileCounter(), jax.devices(), control=True)
    assert H.result(run, cell, False)["correct"] is True
    line = H.control_result(run, cell)
    assert line["correct"] is False
    gap = line["checks"]["widest_gap"]
    assert gap["value"] > gap["limit"] == cell.check["limits"]["widest_gap"]


@pytest.mark.parametrize("mode", ["parallel", "ar"])
def test_a_mix_with_a_drafter_is_refused(tmp_path, monkeypatch, mode):
    benchtiny.steer(monkeypatch)
    mix = dict(benchtiny.TINY_MIX, engine=dict(
        benchtiny.TINY_MIX["engine"], drafter_mode=mode))
    cell, _ = benchtiny.make_bench(tmp_path, "qwen2-1.5b", mix=mix)
    with pytest.raises(ValueError, match="drafts"):
        serve.build(cell, 1)
