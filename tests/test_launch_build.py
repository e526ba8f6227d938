"""Construction shared by the launchers and chip_smoke.py
(repro/launch/build.py): the compile-cache directory and drafter loading."""
import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.checkpoint import save_pytree
from repro.configs import DrafterConfig
from repro.core import drafter as D
from repro.launch import build


@pytest.fixture
def keep_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_dir_is_fixed(monkeypatch, tmp_path, keep_cache_dir,
                                    env_set):
    """$JAX_COMPILATION_CACHE_DIR when set, else <repo root>/.jax_cache —
    never a per-run path."""
    if env_set:
        monkeypatch.setenv(build.CACHE_ENV, str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv(build.CACHE_ENV, raising=False)
        want = str(build.REPO_ROOT / ".jax_cache")
        assert (build.REPO_ROOT / "pyproject.toml").is_file()
    assert build.use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert build.use_compile_cache() == want          # stable across calls


def _drafter(n_layers):
    tcfg, _, _ = build.init_target("qwen2-1.5b", reduced=True)
    return tcfg, DrafterConfig(n_layers=n_layers).resolve(tcfg)


def test_missing_drafter_checkpoint_gives_seeded_drafter(tmp_path, capsys):
    tcfg, dcfg = _drafter(1)
    got = build.load_drafter(dcfg, tcfg, str(tmp_path / "absent"), seed=3)
    want = D.init_params(dcfg, tcfg, jax.random.PRNGKey(3))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert "no drafter checkpoint" in capsys.readouterr().out


def test_mismatched_drafter_checkpoint_raises(tmp_path):
    """A checkpoint that exists but does not fit the drafter is an error,
    not a silent fall back to random weights."""
    tcfg, dcfg2 = _drafter(2)
    save_pytree(D.init_params(dcfg2, tcfg, jax.random.PRNGKey(0)),
                str(tmp_path), f"drafter_{tcfg.arch_id}", step=1)
    _, dcfg1 = _drafter(1)
    with pytest.raises((KeyError, ValueError)):
        build.load_drafter(dcfg1, tcfg, str(tmp_path), seed=0)
