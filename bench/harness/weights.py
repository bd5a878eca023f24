"""Weights made by the benchmark from the seed, so that the program and
the plain reference start from the same point without the reference
taking anything the program made.

The parameter tree's layout comes from the program (its shapes, by
``jax.eval_shape``); each leaf is filled by the rule of the configuration
file's ``init`` table whose key is the longest suffix of the leaf's path
(``gen/blocks/mixer/conv/b`` matches ``conv/b`` before ``b``).  A leaf no
rule matches is an error.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def _rule(name: str, rules: dict):
    hits = [k for k in rules if name == k or name.endswith("/" + k)]
    if not hits:
        raise KeyError(f"no init rule for parameter {name!r}; "
                       f"rules: {sorted(rules)}")
    return rules[max(hits, key=len)]


def _fill(rule, key, shape, dtype):
    kind, *args = rule
    if kind == "normal":
        return args[0] * jax.random.normal(key, shape, dtype)
    if kind == "glorot":
        limit = math.sqrt(6.0 / (shape[-2] + shape[-1]))
        return jax.random.uniform(key, shape, dtype, -limit, limit)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "log_linspace":
        row = jnp.log(jnp.linspace(args[0], args[1], shape[-1]))
        return jnp.broadcast_to(row.astype(dtype), shape)
    raise ValueError(f"unknown init rule {rule!r}")


def maker(shapes, rules: dict):
    """A jitted ``key -> params`` for the layout ``shapes`` (a pytree of
    ShapeDtypeStructs): one call on the device makes every leaf."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    picked = [(_rule(leaf_name(p), rules), s.shape, s.dtype)
              for p, s in flat]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(picked))
        return jax.tree_util.tree_unflatten(
            treedef, [_fill(r, k, shape, dtype)
                      for (r, shape, dtype), k in zip(picked, keys)])

    return make
