"""Retrace-hazard checkers.

The engine's perf contract is ONE jit trace per entry point per layout
(pinned dynamically by the retrace-bound tests); the two mechanical ways to
break it are Python values in traced signatures and host materialization
inside traced bodies.

TRACE01  a jit-compiled function has a Python ``bool``/``str`` default
         parameter that is not marked static (``static_argnames`` /
         ``static_argnums``) nor bound by a ``functools.partial`` wrapper
         inside the ``jax.jit(...)`` call. Passing a fresh Python value
         per call retraces; unhashable values fail outright.
TRACE02  inside a jitted body: ``.item()``, ``int()``/``float()``/
         ``bool()`` of a (potentially traced) value, f-strings formatting
         non-static values, ``np.asarray``/``np.array``, ``jax.device_get``
         or ``jax.block_until_ready`` — each either forces a blocking
         host sync per trace or raises a TracerConversionError at the
         worst time. Shape arithmetic (``x.shape[0]``, ``.ndim``,
         ``len(...)``) is static and exempt.

A "jitted body" is a def decorated with ``jax.jit`` (bare or via
``functools.partial``), a def passed directly to a ``jax.jit(...)`` call
(through aliases like ``jj = jax.jit`` and the engine's ``_greedy_twins``
helper), a def whose name ends in ``_impl`` (the engine's jit-entry-point
naming convention), or ``speculative_step`` (traced from every step impl).
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from tools.lint.core import Finding, ParsedModule, dotted_name

JIT = "jax.jit"
PARTIAL = "functools.partial"
# functools.update_wrapper(partial(...), fn) names a partial after fn (JAX
# then names the compiled module after it); the partial inside still binds
UPDATE_WRAPPER = "functools.update_wrapper"
# helpers that jit their first argument (possibly wrapping it in a partial)
JIT_WRAPPERS = {"_greedy_twins"}
# module-level functions that are traced from inside jitted bodies even
# though no jit call references them directly
ALWAYS_TRACED = {"speculative_step"}

SYNC_CALLS = {"numpy.asarray", "numpy.array", "jax.device_get",
              "jax.block_until_ready"}
SAFE_ATTRS = {"ndim", "size", "shape", "dtype", "itemsize", "nbytes"}


def _jit_decorated(fn, mod: ParsedModule) -> Optional[ast.Call]:
    """The decorator expression when ``fn`` is jit-decorated; a bare
    ``@jax.jit`` returns a synthetic empty Call for uniform handling."""
    for dec in fn.decorator_list:
        if mod.resolve(dec) == JIT:
            return ast.Call(func=dec, args=[], keywords=[])
        if isinstance(dec, ast.Call):
            target = mod.resolve(dec.func)
            if target == JIT:
                return dec
            if target == PARTIAL and dec.args \
                    and mod.resolve(dec.args[0]) == JIT:
                return dec
    return None


def _static_names(call: ast.Call, fn) -> Set[str]:
    """Parameter names the jit call marks static."""
    out: Set[str] = set()
    params = [a.arg for a in fn.args.args]
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and isinstance(c.value, str):
                    out.add(c.value)
        elif kw.arg == "static_argnums":
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and isinstance(c.value, int):
                    if 0 <= c.value < len(params):
                        out.add(params[c.value])
    return out


def _jit_target(arg: ast.AST, mod: ParsedModule) -> ast.AST:
    """The callable a jit call compiles, seen through an
    ``update_wrapper`` naming wrapper (its first argument)."""
    if isinstance(arg, ast.Call) and arg.args \
            and mod.resolve(arg.func) == UPDATE_WRAPPER:
        return arg.args[0]
    return arg


def _local_defs(mod: ParsedModule) -> Dict[str, ast.FunctionDef]:
    return {n.name: n for n in ast.walk(mod.tree)
            if isinstance(n, ast.FunctionDef)}


def _jitted_defs(mod: ParsedModule) -> Dict[str, ast.Call]:
    """name -> the jit/partial call that compiles it (or a synthetic empty
    call when only the convention says it's traced)."""
    empty = ast.Call(func=ast.Name(id="jit"), args=[], keywords=[])
    defs = _local_defs(mod)
    out: Dict[str, ast.Call] = {}
    for name, fn in defs.items():
        dec = _jit_decorated(fn, mod)
        if dec is not None:
            out[name] = dec
        elif name.endswith("_impl") or name in ALWAYS_TRACED:
            out[name] = empty
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call) and node.args):
            continue
        target = mod.resolve(node.func)
        fname = dotted_name(node.func) or ""
        is_jit = target == JIT
        is_wrapper = fname.split(".")[-1] in JIT_WRAPPERS
        if not (is_jit or is_wrapper):
            continue
        arg = _jit_target(node.args[0], mod)
        # unwrap functools.partial(fn, bound=...) around the jitted def
        if isinstance(arg, ast.Call) and mod.resolve(arg.func) == PARTIAL \
                and arg.args:
            arg = arg.args[0]
        name = (dotted_name(arg) or "").split(".")[-1]
        if name in defs:
            out[name] = node if is_jit else empty
    return out


def _partial_bound_names(mod: ParsedModule) -> Set[str]:
    """Kwarg names bound by any ``jax.jit(functools.partial(fn, kw=...))``
    in the module. Treated as static for every jitted def here: the
    engine's ``_greedy_twins`` binds ``greedy_only`` via partial inside
    the helper, so the binding isn't visible at the ``_greedy_twins(
    self._step_impl)`` call sites — a module-wide name set is the
    conservative way to honor it without interprocedural analysis."""
    bound: Set[str] = set()
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call) and node.args
                and mod.resolve(node.func) == JIT):
            continue
        arg = _jit_target(node.args[0], mod)
        if isinstance(arg, ast.Call) and mod.resolve(arg.func) == PARTIAL:
            bound.update(kw.arg for kw in arg.keywords if kw.arg)
    return bound


def _check_static_args(mod: ParsedModule) -> List[Finding]:
    out: List[Finding] = []
    defs = _local_defs(mod)
    jitted = _jitted_defs(mod)
    module_bound = _partial_bound_names(mod)
    for name, fn in defs.items():
        call = jitted.get(name)
        if call is None:
            continue
        statics = _static_names(call, fn) | module_bound
        args = fn.args
        defaults = args.defaults
        params = args.args[len(args.args) - len(defaults):]
        for p, d in zip(params, defaults):
            if not (isinstance(d, ast.Constant)
                    and isinstance(d.value, (bool, str))):
                continue
            if p.arg in statics or p.arg == "self":
                continue
            out.append(mod.finding(
                "TRACE01", p,
                f"jitted function {name!r} takes Python "
                f"{type(d.value).__name__} parameter {p.arg!r} without "
                "marking it static — every distinct value retraces "
                "(add static_argnames or bind it with functools.partial)"))
        for p, d in zip(args.kwonlyargs, args.kw_defaults):
            if d is None or not (isinstance(d, ast.Constant)
                                 and isinstance(d.value, (bool, str))):
                continue
            if p.arg in statics:
                continue
            out.append(mod.finding(
                "TRACE01", p,
                f"jitted function {name!r} takes Python "
                f"{type(d.value).__name__} parameter {p.arg!r} without "
                "marking it static — every distinct value retraces "
                "(add static_argnames or bind it with functools.partial)"))
    return out


# ---------------------------------------------------------------------------
# TRACE02 — host materialization inside jitted bodies
# ---------------------------------------------------------------------------

def _is_safe(node: ast.AST, depth: int = 0) -> bool:
    """Statically-known-at-trace-time expressions: constants, shape/ndim
    arithmetic, len(). Conservative — anything else is assumed traced."""
    if depth > 8:
        return False
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Attribute) and node.attr in SAFE_ATTRS:
        return True
    if isinstance(node, ast.Subscript):
        return _is_safe(node.value, depth + 1)
    if isinstance(node, ast.Call):
        fname = dotted_name(node.func) or ""
        if fname == "len":
            return True
        if fname.split(".")[-1] in ("prod", "ceil", "floor", "log2",
                                    "max", "min"):
            return all(_is_safe(a, depth + 1) for a in node.args)
        return False
    if isinstance(node, ast.BinOp):
        return _is_safe(node.left, depth + 1) and _is_safe(node.right,
                                                           depth + 1)
    if isinstance(node, ast.UnaryOp):
        return _is_safe(node.operand, depth + 1)
    if isinstance(node, (ast.Tuple, ast.List)):
        return all(_is_safe(e, depth + 1) for e in node.elts)
    return False


def _check_jitted_bodies(mod: ParsedModule) -> List[Finding]:
    out: List[Finding] = []
    defs = _local_defs(mod)
    jitted = _jitted_defs(mod)
    for name, fn in defs.items():
        if name not in jitted:
            continue
        for node in ast.walk(fn):
            # nested defs inside a jitted body are traced too — keep them
            if isinstance(node, ast.Call):
                target = mod.resolve(node.func)
                if target in SYNC_CALLS:
                    out.append(mod.finding(
                        "TRACE02", node,
                        f"{(dotted_name(node.func) or target)} inside "
                        f"jitted body {name!r}: forces a host sync or "
                        "TracerConversionError at trace time"))
                    continue
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "item":
                    out.append(mod.finding(
                        "TRACE02", node,
                        f".item() inside jitted body {name!r}: "
                        "concretizes a traced value"))
                    continue
                fname = dotted_name(node.func) or ""
                if fname in ("int", "float", "bool") and node.args \
                        and not _is_safe(node.args[0]):
                    out.append(mod.finding(
                        "TRACE02", node,
                        f"{fname}() of a traced value inside jitted body "
                        f"{name!r}: concretizes at trace time — use "
                        "jnp casts/asarray, or hoist to the host caller"))
            elif isinstance(node, ast.JoinedStr):
                dynamic = [v for v in node.values
                           if isinstance(v, ast.FormattedValue)
                           and not _is_safe(v.value)]
                if dynamic:
                    out.append(mod.finding(
                        "TRACE02", node,
                        f"f-string formats a traced value inside jitted "
                        f"body {name!r}: formatting concretizes — build "
                        "messages from static shapes only"))
    return out


def check(mod: ParsedModule) -> List[Finding]:
    return _check_static_args(mod) + _check_jitted_bodies(mod)
