"""Seeded random weights, made on the device in one jitted call, in the
dtype each leaf is served in.

The benchmark makes the weights, not the program, so the plain reference
(``bench/refs``) can take the same arrays without taking anything the
program made. The tree's structure and dtypes follow the program's own
parameter layout (``jax.eval_shape`` of its initialiser); the values come
from rules by leaf name. The family module (``bench/families``) is asked
first, for the leaves of its own layers (``weight(name, key, shape,
dtype)``, None where it has no rule); then the common rules:

  norm scales (``ln*``, ``*norm*``)        1
  embeddings (``embed``)                   N(0, 0.02^2)
  biases (``b*``), ``h_shared``            N(0, 0.02^2)
  every other matrix (..., fan_in, fan_out)  N(0, 1/fan_in)

These are the scales of the usual initialisers, so activations keep unit
size through every layer and logits stay finite at any depth.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _value(name: str, key, shape, dtype, family=None):
    rule = getattr(family, "weight", None)
    if rule is not None:
        out = rule(name, key, shape, dtype)
        if out is not None:
            return out
    if name.startswith("ln") or "norm" in name:
        return jnp.ones(shape, dtype)
    if name in ("embed", "h_shared") or name.startswith("b"):
        std = 0.02
    elif len(shape) >= 2:
        std = shape[-2] ** -0.5
    else:
        raise ValueError(f"no weight rule for leaf {name!r} of shape {shape}")
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make(abstract_tree, key, family=None):
    """A tree shaped like ``abstract_tree`` (ShapeDtypeStructs), filled on
    the default device in one jitted call from ``key``; ``family`` is the
    family module whose rules come first (None: the common rules alone,
    as for the drafter)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)

    @jax.jit
    def build(key):
        return [_value(_leaf_name(path), jax.random.fold_in(key, i),
                       leaf.shape, leaf.dtype, family)
                for i, (path, leaf) in enumerate(flat)]

    return jax.tree_util.tree_unflatten(treedef, build(key))
