"""repro-lint self-tests: every rule family catches a purpose-built bad
fixture and passes its good twin; suppression comments and the baseline
add/expire semantics behave; the state-surgery checker fails when a real
surgery surface loses a leaf handler; and the live tree is clean modulo
the checked-in baseline.

Pure stdlib (ast + the linter itself) — no jax imports, so this file is
cheap enough to run in tier-1 even though CI also runs the linter
directly in its ``lint`` job.
"""
import os
import shutil
import sys
import textwrap

import pytest

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from tools.lint import surgery  # noqa: E402
from tools.lint.core import (RefusedPath, collect_files, lint_file,  # noqa: E402
                             lint_source, load_baseline, match_baseline,
                             write_baseline)

SERVING = "src/repro/serving/fixture.py"


def rules_of(findings):
    return [f.rule for f in findings]


def lint(src, relpath="src/repro/fixture.py", rules=None):
    return lint_source(textwrap.dedent(src), relpath, rules)


# ---------------------------------------------------------------------------
# PRNG
# ---------------------------------------------------------------------------

def test_prng01_flags_split_and_carry():
    out = lint("""
        import jax

        def draw(key):
            key, sub = jax.random.split(key)
            return sub
    """)
    assert rules_of(out) == ["PRNG01"]


def test_prng01_flags_attribute_carry_and_aliased_import():
    out = lint("""
        from jax import random as jr

        class T:
            def advance(self):
                self.rng, sub = jr.split(self.rng)
                return sub
    """)
    assert rules_of(out) == ["PRNG01"]


def test_prng01_good_fold_in_counter_stream():
    out = lint("""
        import jax

        def draw(base, i):
            sub = jax.random.split(jax.random.fold_in(base, i), 2)
            return sub
    """)
    assert "PRNG01" not in rules_of(out)


def test_prng02_flags_key_passed_to_two_draws():
    out = lint("""
        import jax

        def draw(key, logits):
            a = jax.random.categorical(key, logits)
            b = jax.random.uniform(key, (4,))
            return a, b
    """)
    assert rules_of(out) == ["PRNG02"]


def test_prng02_good_distinct_fold_ins():
    out = lint("""
        import jax

        def draw(key, logits):
            a = jax.random.categorical(jax.random.fold_in(key, 0), logits)
            b = jax.random.uniform(jax.random.fold_in(key, 1), (4,))
            return a, b
    """)
    assert "PRNG02" not in rules_of(out)


def test_prng03_flags_unsalted_serving_stream():
    out = lint("""
        import jax

        def proposals(samp, pos):
            base = step_keys(samp, pos)
            ks = jax.random.split(base, 4)
            return ks
    """, relpath=SERVING)
    assert rules_of(out) == ["PRNG03"]


def test_prng03_good_salted_stream_and_vmap_idiom():
    # both forms of the sampling.py draft_keys idiom must pass: direct
    # fold_in, and fold_in inside a vmapped lambda over the base stream
    out = lint("""
        import jax

        DRAFT_SALT = 0x5EED

        def draft_keys(samp, pos, k):
            base = jax.random.fold_in(step_keys(samp, pos), DRAFT_SALT)
            direct = jax.random.split(base, k)
            mapped = jax.vmap(
                lambda b: jax.random.split(
                    jax.random.fold_in(b, DRAFT_SALT), k)
            )(step_keys(samp, pos))
            return direct, mapped
    """, relpath=SERVING)
    assert "PRNG03" not in rules_of(out)


def test_prng03_scoped_to_serving():
    out = lint("""
        import jax

        def proposals(samp, pos):
            return jax.random.split(step_keys(samp, pos), 4)
    """, relpath="src/repro/training/fixture.py")
    assert "PRNG03" not in rules_of(out)


# ---------------------------------------------------------------------------
# TRACE
# ---------------------------------------------------------------------------

def test_trace01_flags_unmarked_bool_arg():
    out = lint("""
        import jax

        @jax.jit
        def step(state, greedy=False):
            return state
    """)
    assert rules_of(out) == ["TRACE01"]


def test_trace01_good_static_argnames_and_partial_binding():
    out = lint("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("greedy",))
        def step(state, greedy=False):
            return state

        def bound_impl(state, greedy=False):
            return state

        twins = {g: jax.jit(functools.partial(bound_impl, greedy=g))
                 for g in (False, True)}
    """)
    assert "TRACE01" not in rules_of(out)


def test_trace01_sees_through_jit_wrapper_helpers():
    # _greedy_twins binds greedy_only via partial INSIDE the helper; the
    # module-wide partial-bound name set must exempt the impl's parameter
    out = lint("""
        import functools
        import jax

        def _greedy_twins(fn, **kw):
            return {g: jax.jit(functools.partial(fn, greedy_only=g), **kw)
                    for g in (False, True)}

        def _step_impl(state, greedy_only=False):
            return state

        step = _greedy_twins(_step_impl)
    """)
    assert "TRACE01" not in rules_of(out)


def test_trace01_sees_through_update_wrapper():
    # naming the partial after the wrapped impl (so the compiled module
    # carries its name) keeps the partial's binding visible
    out = lint("""
        import functools
        import jax

        def _greedy_twins(fn, **kw):
            return {g: jax.jit(functools.update_wrapper(
                        functools.partial(fn, greedy_only=g), fn), **kw)
                    for g in (False, True)}

        def _step_impl(state, greedy_only=False):
            return state

        step = _greedy_twins(_step_impl)
    """)
    assert "TRACE01" not in rules_of(out)
    unbound = lint("""
        import functools
        import jax

        def _step_impl(state, greedy_only=False):
            return state

        step = jax.jit(functools.update_wrapper(
            functools.partial(_step_impl), _step_impl))
    """)
    assert "TRACE01" in rules_of(unbound)


def test_trace02_flags_host_materialization_in_jitted_body():
    out = lint("""
        import jax
        import numpy as np

        @jax.jit
        def step(state, x):
            n = int(x)
            v = x.item()
            arr = np.asarray(state)
            msg = f"value={x}"
            return n, v, arr, msg
    """)
    assert rules_of(out) == ["TRACE02"] * 4


def test_trace02_good_shape_arithmetic_and_unjitted_host_code():
    out = lint("""
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            n = int(x.shape[0] * x.ndim)
            m = f"batch={x.shape[0]}"
            return n, m

        def host_harness(x):
            return int(x), np.asarray(x)
    """)
    assert "TRACE02" not in rules_of(out)


def test_trace02_covers_impl_naming_convention():
    out = lint("""
        def _step_impl(state, x):
            return x.item()
    """)
    assert rules_of(out) == ["TRACE02"]


# ---------------------------------------------------------------------------
# SYNC
# ---------------------------------------------------------------------------

def test_sync01_flags_state_readback_outside_harvest():
    out = lint("""
        import numpy as np

        def poll(state):
            return np.asarray(state["new_count"])
    """, relpath=SERVING)
    assert rules_of(out) == ["SYNC01"]


def test_sync01_ignores_non_state_and_non_serving():
    clean = lint("""
        import numpy as np

        def encode(prompts):
            return np.asarray(prompts)
    """, relpath=SERVING)
    assert "SYNC01" not in rules_of(clean)
    elsewhere = lint("""
        import numpy as np

        def poll(state):
            return np.asarray(state["new_count"])
    """, relpath="src/repro/training/fixture.py")
    assert "SYNC01" not in rules_of(elsewhere)


# ---------------------------------------------------------------------------
# SHARD
# ---------------------------------------------------------------------------

def test_shard01_flags_bare_jit_in_mesh_module():
    out = lint("""
        import jax

        def build(self, fn, mesh):
            return jax.jit(fn)
    """, relpath=SERVING)
    assert rules_of(out) == ["SHARD01"]


def test_shard01_good_shardings_kwargs_forward_and_mesh_none_branch():
    out = lint("""
        import jax

        def build(self, fn, shd, jit_kwargs):
            if self.mesh is None:
                return jax.jit(fn)
            a = jax.jit(fn, in_shardings=shd)
            b = jax.jit(fn, **jit_kwargs)
            return a, b
    """, relpath=SERVING)
    assert "SHARD01" not in rules_of(out)


def test_shard01_silent_in_meshless_module():
    out = lint("""
        import jax

        def build(fn):
            return jax.jit(fn)
    """, relpath=SERVING)
    assert "SHARD01" not in rules_of(out)


# ---------------------------------------------------------------------------
# ALLOC
# ---------------------------------------------------------------------------

def test_alloc01_flags_allocator_internals_outside_class():
    out = lint("""
        def steal(alloc):
            page = alloc._free.pop()
            alloc._ref[page] = 1
            return page
    """)
    assert rules_of(out) == ["ALLOC01", "ALLOC01"]


def test_alloc01_good_inside_owner_and_unrelated_attrs():
    out = lint("""
        class BlockAllocator:
            def alloc(self):
                return self._free.pop()

        class Engine:
            def __init__(self):
                self._free = None    # jitted free fn, not the allocator
    """)
    assert "ALLOC01" not in rules_of(out)


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

def test_suppression_same_line_and_previous_line():
    src = """
        import jax

        def draw(key, other):
            key, a = jax.random.split(key)  # repro-lint: disable=PRNG01
            # repro-lint: disable=PRNG01
            other, b = jax.random.split(other)
            return a, b
    """
    assert lint(src) == []


def test_suppression_is_rule_specific():
    out = lint("""
        import jax

        def draw(key):
            key, a = jax.random.split(key)  # repro-lint: disable=PRNG02
            return a
    """)
    assert rules_of(out) == ["PRNG01"]


def test_file_level_suppression():
    out = lint("""
        # repro-lint: disable-file=PRNG01
        import jax

        def draw(key, other):
            key, a = jax.random.split(key)
            other, b = jax.random.split(other)
            return a, b
    """)
    assert "PRNG01" not in rules_of(out)


# ---------------------------------------------------------------------------
# baseline semantics
# ---------------------------------------------------------------------------

def test_baseline_absorbs_then_expires(tmp_path):
    findings = lint("""
        import jax

        def draw(key):
            key, a = jax.random.split(key)
            return a
    """)
    assert rules_of(findings) == ["PRNG01"]
    bl = tmp_path / "baseline.txt"
    write_baseline(str(bl), findings)
    entries = load_baseline(str(bl))
    assert len(entries) == 1

    new, stale = match_baseline(findings, entries)
    assert new == [] and stale == []
    # fixing the finding makes the entry STALE — the run must not pass
    new, stale = match_baseline([], entries)
    assert new == [] and stale == entries
    # an unrelated new finding is NEW even with a populated baseline
    other = lint("""
        import jax

        def other(k):
            k, b = jax.random.split(k)
            return b
    """)
    new, stale = match_baseline(other, entries)
    assert rules_of(new) == ["PRNG01"] and stale == entries


def test_baseline_rejects_malformed_lines(tmp_path):
    bl = tmp_path / "baseline.txt"
    bl.write_text("# comment ok\nPRNG01\tonly-two-fields\n")
    with pytest.raises(ValueError):
        load_baseline(str(bl))


# ---------------------------------------------------------------------------
# file collection hygiene
# ---------------------------------------------------------------------------

def test_collect_files_refuses_compiled_artifacts(tmp_path):
    pyc_dir = tmp_path / "pkg" / "__pycache__"
    pyc_dir.mkdir(parents=True)
    (pyc_dir / "mod.cpython-311.pyc").write_bytes(b"\x00")
    (tmp_path / "pkg" / "mod.py").write_text("x = 1\n")
    with pytest.raises(RefusedPath):
        collect_files([str(pyc_dir)], str(tmp_path))
    with pytest.raises(RefusedPath):
        collect_files([str(pyc_dir / "mod.cpython-311.pyc")], str(tmp_path))
    # walking the parent silently SKIPS the cache dir instead
    files = collect_files(["pkg"], str(tmp_path))
    assert [os.path.basename(f) for f in files] == ["mod.py"]


# ---------------------------------------------------------------------------
# SURG01: state-surgery completeness against the real tree
# ---------------------------------------------------------------------------

SURGERY_FILES = [surgery.ENGINE, surgery.SCHEDULER, surgery.CACHE_OPS,
                 surgery.RULES, surgery.STEPS]


def _copy_tree(tmp_path):
    for rel in SURGERY_FILES:
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(os.path.join(REPO_ROOT, rel), dst)
    return str(tmp_path)


def _mutate(root, rel, old, new):
    full = os.path.join(root, rel)
    with open(full, "r", encoding="utf-8") as f:
        src = f.read()
    assert old in src, f"mutation anchor not found in {rel}: {old!r}"
    with open(full, "w", encoding="utf-8") as f:
        f.write(src.replace(old, new))


def test_surg01_clean_on_real_tree(tmp_path):
    assert surgery.check_repo(_copy_tree(tmp_path)) == []


def test_surg01_detects_dropped_swap_reset(tmp_path):
    root = _copy_tree(tmp_path)
    _mutate(root, surgery.ENGINE,
            'snap["slot_iters"] = np.zeros_like(snap["slot_iters"])', "pass")
    out = surgery.check_repo(root)
    assert any(f.rule == "SURG01" and f.qualname == "swap_out_slot"
               and "slot_iters" in f.message for f in out)


def test_surg01_detects_dropped_kv_sharding_handler(tmp_path):
    root = _copy_tree(tmp_path)
    # deleting the k/v handler from _serve_state_leaf must fail the check
    _mutate(root, surgery.RULES,
            'if name in ("k", "v") and leaf.ndim >= 4:',
            'if name in ("positions",) and leaf.ndim >= 4:')
    out = surgery.check_repo(root)
    assert any(f.rule == "SURG01" and f.path == surgery.RULES for f in out)


def test_surg01_detects_leaf_dropped_from_step_rebuild(tmp_path):
    root = _copy_tree(tmp_path)
    _mutate(root, surgery.ENGINE,
            "slot_iters=state[\"slot_iters\"] + active.astype(jnp.int32),",
            "")
    out = surgery.check_repo(root)
    assert any(f.qualname == "speculative_step"
               and "slot_iters" in f.message for f in out)


def test_surg01_detects_leaf_missing_from_launch_template(tmp_path):
    root = _copy_tree(tmp_path)
    _mutate(root, surgery.STEPS, '"new_count": spec_for((GB,), bsp[0]),', "")
    out = surgery.check_repo(root)
    assert any(f.path == surgery.STEPS and "new_count" in f.message
               for f in out)


def test_surg01_detects_harvest_dropping_a_leaf(tmp_path):
    root = _copy_tree(tmp_path)
    _mutate(root, surgery.SCHEDULER,
            'logprobs = np.asarray(state["logprobs"])', "logprobs = None")
    out = surgery.check_repo(root)
    assert any(f.qualname == "Scheduler._harvest"
               and "logprobs" in f.message for f in out)


def test_surg01_new_state_leaf_flags_stale_surfaces(tmp_path):
    # the forward direction: ADD a leaf to make_decode_state and every
    # surface that wasn't updated must light up
    root = _copy_tree(tmp_path)
    _mutate(root, surgery.ENGINE,
            '"slot_iters": jnp.zeros((batch,), jnp.int32),',
            '"slot_iters": jnp.zeros((batch,), jnp.int32),\n'
            '        "new_leaf": jnp.zeros((batch,), jnp.int32),')
    out = surgery.check_repo(root)
    stale_surfaces = {f.path for f in out if "new_leaf" in f.message}
    assert surgery.ENGINE in stale_surfaces   # speculative_step rebuild
    assert surgery.STEPS in stale_surfaces    # launch state_specs template


# ---------------------------------------------------------------------------
# live-tree self-check: the committed tree is clean modulo the baseline
# ---------------------------------------------------------------------------

def test_live_tree_clean_modulo_baseline():
    files = collect_files(["src", "tools"], REPO_ROOT)
    findings = []
    for path in files:
        findings.extend(lint_file(path, REPO_ROOT))
    findings.extend(surgery.check_repo(REPO_ROOT))
    entries = load_baseline(
        os.path.join(REPO_ROOT, "tools", "lint", "baseline.txt"))
    new, stale = match_baseline(findings, entries)
    assert new == [], "new lint findings:\n" + "\n".join(
        f.render() for f in new)
    assert stale == [], "stale baseline entries:\n" + "\n".join(
        "\t".join(e) for e in stale)
