"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program's own
``Model.init`` builds them eagerly, layer by layer, and the reference must
take nothing the program has made.  The tree's structure, shapes and dtypes
come from ``jax.eval_shape`` of the program's initializer (nothing runs);
every leaf is then drawn here from the seed, in the dtype it is served in:

* ``scale`` (norm gains): 1 + 0.1 N(0, 1); ``bias`` (norm shifts): 0.1 N;
* ``embed`` and ``lm_head``: 0.02 N;
* projections: N / sqrt(fan_in), fan_in read from the shape: ``wq``,
  ``wk``, ``wv`` are [.., D, N, H] (fan_in D); ``wo`` is [.., N, H, D]
  (fan_in N * H); every other matrix is [.., in, out].

An unknown leaf name is an error, so a new parameter cannot be drawn with
a guessed scale.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _std(name: str, shape) -> float:
    if name in ("embed", "lm_head"):
        return 0.02
    if name in ("wq", "wk", "wv"):
        return 1.0 / math.sqrt(shape[-3])
    if name == "wo":
        return 1.0 / math.sqrt(shape[-3] * shape[-2])
    if name in ("w_gate", "w_up", "w_in", "w_down", "w"):
        return 1.0 / math.sqrt(shape[-2])
    raise ValueError(f"no rule to draw parameter {name!r} {tuple(shape)}")


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits (PRNGKey alone keeps only
    the low 32)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make(shapes, seed: int):
    """Parameters shaped like ``shapes`` (a pytree of ShapeDtypeStruct),
    drawn from ``seed`` on the default device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def draw(key):
        out = []
        for i, (path, s) in enumerate(flat):
            name = getattr(path[-1], "key", None)
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, s.shape, jnp.float32)
            if name == "scale":
                a = 1.0 + 0.1 * z
            elif name == "bias":
                a = 0.1 * z
            else:
                a = _std(name, s.shape) * z
            out.append(a.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw)(jax.random.fold_in(seed_key(seed), 1))
