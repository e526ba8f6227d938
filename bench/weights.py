"""Seeded random weights, made on the device in one jitted call, in the
dtype each leaf is served in.

The benchmark makes the weights, not the program, so the plain reference
(``bench/refs``) can take the same arrays without taking anything the
program made. The tree's structure and dtypes follow the program's own
parameter layout (``jax.eval_shape`` of its initialiser); the values come
from the rules below, by leaf name:

  norm scales (``ln*``, ``*norm*``)        1
  Mamba-2 ``A_log``                        log U(1, 16)
  Mamba-2 ``dt_bias``                      softplus^-1 of log-uniform dt in
                                           [1e-3, 1e-1]
  Mamba-2 ``D``                            1
  embeddings (``embed``)                   N(0, 0.02^2)
  biases (``b*``, ``conv_b``), ``h_shared``  N(0, 0.02^2)
  Mamba-2 ``conv_w``                       N(0, 0.5^2)
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


def _value(name: str, key, shape, dtype):
    f32 = jnp.float32
    if name.startswith("ln") or "norm" in name or name == "D":
        return jnp.ones(shape, dtype)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0)).astype(dtype)
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, jnp.log(1e-3),
                                        jnp.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name in ("embed", "h_shared", "conv_b") or name.startswith("b"):
        std = 0.02
    elif name == "conv_w":
        std = 0.5
    elif len(shape) >= 2:
        std = shape[-2] ** -0.5
    else:
        raise ValueError(f"no weight rule for leaf {name!r} of shape {shape}")
    return (std * jax.random.normal(key, shape, f32)).astype(dtype)


def make(abstract_tree, key):
    """A tree shaped like ``abstract_tree`` (ShapeDtypeStructs), filled on
    the default device in one jitted call from ``key``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)

    @jax.jit
    def build(key):
        return [_value(_leaf_name(path), jax.random.fold_in(key, i),
                       leaf.shape, leaf.dtype)
                for i, (path, leaf) in enumerate(flat)]

    return jax.tree_util.tree_unflatten(treedef, build(key))
