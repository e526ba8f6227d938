"""Mesh-aware sharding helpers.

Models sprinkle ``shard_hint(x, "data", None, "model")`` constraints; on a
single-device CPU run (tests, benchmarks) there is no mesh and the hint is a
no-op, while under ``mesh_scope`` (``jax.set_mesh``) in the
dry-run, launchers, and the model-sharded serving engine it becomes
``with_sharding_constraint``. Axes that do not exist in the mesh or do not
divide the corresponding dimension are dropped from the spec rather than
erroring, which lets one model definition serve every (arch × mesh).
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

AxisEntry = Union[None, str, Sequence[str]]


def mesh_scope(mesh):
    """Enter ``mesh`` (``jax.set_mesh``) so ``shard_hint`` / ``spec_for`` /
    the rules in sharding/rules.py see it during tracing or eager spec
    resolution."""
    return jax.set_mesh(mesh)


def serving_mesh(n_devices: Optional[int] = None):
    """1-D ``("model",)`` mesh over the first ``n_devices`` local devices
    (all of them when None) — the serving engine's tensor-sharding mesh.

    Serving shards *storage* over a single model axis (weights and KV page
    pools; see docs/sharding.md): there is no data axis because the
    scheduler's continuous batch is one replica — request rows are slots of
    one decode state, not a data-parallel shard."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if n > len(devs):
        raise ValueError(f"serving_mesh({n}): only {len(devs)} devices")
    return jax.sharding.Mesh(np.asarray(devs[:n]), ("model",))


def replicate_tree(tree, mesh):
    """Constrain every leaf of ``tree`` to be fully replicated over ``mesh``
    (inside jit: an all-gather at this point for sharded-at-rest leaves).

    This is the serving engine's exactness boundary: storage-sharded
    weights/pools are gathered here and every op downstream computes with
    the exact tensor shapes of a single-device run, so results are
    bit-identical to the unsharded engine (reduction order and backend
    matmul tiling are shape-dependent — sharded *compute* is not lossless;
    sharded *storage* with gather-on-use is)."""
    repl = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(x, repl), tree)


def _current_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not mesh.axis_names:
        return None
    return mesh


def axis_size(name: str, default: int = 1) -> int:
    mesh = _current_mesh()
    if mesh is None or name not in mesh.axis_names:
        return default
    return mesh.shape[name]


def batch_axes() -> AxisEntry:
    """Axes the global batch shards over: ("pod","data") when both exist."""
    mesh = _current_mesh()
    if mesh is None:
        return None
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    return tuple(axes) if axes else None


def _filter_spec(shape, spec_entries, mesh) -> Optional[P]:
    out = []
    for dim, entry in zip(shape, spec_entries):
        if entry is None:
            out.append(None)
            continue
        names = (entry,) if isinstance(entry, str) else tuple(entry)
        names = [n for n in names if n in mesh.axis_names]
        total = 1
        for n in names:
            total *= mesh.shape[n]
        if not names or total == 0 or dim % total != 0:
            out.append(None)
        else:
            out.append(names[0] if len(names) == 1 else tuple(names))
    return P(*out)


def shard_hint(x: jax.Array, *spec_entries: AxisEntry) -> jax.Array:
    """Best-effort with_sharding_constraint; no-op without a mesh context."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    entries = list(spec_entries) + [None] * (x.ndim - len(spec_entries))
    spec = _filter_spec(x.shape, entries[: x.ndim], mesh)
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


def spec_for(shape, *spec_entries: AxisEntry) -> P:
    """Resolve a divisibility-filtered PartitionSpec for a concrete shape."""
    mesh = _current_mesh()
    if mesh is None:
        return P()
    entries = list(spec_entries) + [None] * (len(shape) - len(spec_entries))
    return _filter_spec(shape, entries[: len(shape)], mesh)
