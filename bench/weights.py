"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program's own
``Model.init`` builds them eagerly, layer by layer, and the reference must
take nothing the program has made.  The tree's structure, shapes and dtypes
come from ``jax.eval_shape`` of the program's initializer (nothing runs);
every leaf is then drawn here from the seed, in the dtype it is served in,
by a rule.  The built-in rules (``BUILTIN``) cover the dense decoders:

* ``scale`` (norm gains): 1 + 0.1 N(0, 1); ``bias`` (norm shifts): 0.1 N;
* ``embed`` and ``lm_head``: 0.02 N;
* projections: N / sqrt(fan_in), fan_in read from the shape: ``wq``,
  ``wk``, ``wv`` are [.., D, N, H] (fan_in D); ``wo`` is [.., N, H, D]
  (fan_in N * H); every other matrix is [.., in, out].

A configuration file adds rules for its own leaves under
``"weight_rules"``, keyed by the leaf's name or by a suffix of its path
(``"mtp/kind_is_moe"``).  A rule is one of

* ``{"normal": "fan_in", "axes": [-2]}``: N / sqrt(product of those dims);
* ``{"normal": <std>}``, with an optional ``"mean"``: mean + std N;
* ``{"uniform": [lo, hi]}``: uniform in [lo, hi]; with ``"log": true`` the
  leaf holds the natural log of that draw (a state-space ``a_log`` =
  log A, A in [1, 16]), so it stays in [log lo, log hi];
* ``{"const": <value>}``: a structural flag, not a weight.

A leaf with no rule, or with two rules that differ (a built-in and the
file's, or two of the file's), is an error, so a new parameter cannot be
drawn with a guessed scale.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

BUILTIN = {
    "scale": {"normal": 0.1, "mean": 1.0},
    "bias": {"normal": 0.1},
    "embed": {"normal": 0.02},
    "lm_head": {"normal": 0.02},
    **dict.fromkeys(("wq", "wk", "wv"), {"normal": "fan_in", "axes": [-3]}),
    "wo": {"normal": "fan_in", "axes": [-3, -2]},
    **dict.fromkeys(("w_gate", "w_up", "w_in", "w_down", "w"),
                    {"normal": "fan_in", "axes": [-2]}),
}


def path_name(path) -> str:
    """A tree path as ``blocks/0/attn/wq``."""
    return jax.tree_util.keystr(path, simple=True, separator="/")


def rule_for(where: str, shape, rules) -> dict:
    """The one rule that draws the leaf at path ``where``: the built-in
    rule of its name and every file rule whose key is its name or a suffix
    of its path must agree."""
    name = where.rsplit("/", 1)[-1]
    found = {f"built-in {name!r}": BUILTIN[name]} if name in BUILTIN else {}
    found.update({f"{k!r}": r for k, r in (rules or {}).items()
                  if where == k or where.endswith("/" + k)})
    if not found:
        raise ValueError(f"no rule to draw parameter {where!r} "
                         f"{tuple(shape)}")
    rule = next(iter(found.values()))
    if any(r != rule for r in found.values()):
        raise ValueError(f"conflicting rules for parameter {where!r}: "
                         f"{found}")
    return rule


def _draw(rule: dict, key, shape, where: str):
    """A float32 draw of one leaf by its rule."""
    if "normal" in rule:
        std = rule["normal"]
        if std == "fan_in":
            std = 1.0 / math.sqrt(math.prod(shape[a] for a in rule["axes"]))
        a = std * jax.random.normal(key, shape, jnp.float32)
        return rule["mean"] + a if "mean" in rule else a
    if "uniform" in rule:
        lo, hi = rule["uniform"]
        u = jax.random.uniform(key, shape, jnp.float32, lo, hi)
        return jnp.log(u) if rule.get("log") else u
    if "const" in rule:
        return jnp.full(shape, rule["const"], jnp.float32)
    raise ValueError(f"rule {rule} for parameter {where!r} is none of "
                     "normal, uniform, const")


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits (PRNGKey alone keeps only
    the low 32)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make(shapes, seed: int, rules=None):
    """Parameters shaped like ``shapes`` (a pytree of ShapeDtypeStruct),
    drawn from ``seed`` on the default device; ``rules`` are the
    configuration file's ``weight_rules``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = [(path_name(p), s) for p, s in flat]
    drawn_by = [rule_for(w, s.shape, rules) for w, s in leaves]

    def draw(key):
        out = [_draw(r, jax.random.fold_in(key, i), s.shape, w)
               .astype(s.dtype)
               for i, ((w, s), r) in enumerate(zip(leaves, drawn_by))]
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw)(jax.random.fold_in(seed_key(seed), 1))
