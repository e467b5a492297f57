"""Seeded weights, made on the device in one jitted call.

The program only tells the shapes (``jax.eval_shape`` of its ``init``,
which computes nothing); the values are the benchmark's own, so the plain
reference takes no weight the program made. Biases and norm scales are
random too, so a reference or a program that drops one is seen.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _name(path) -> str:
    k = path[-1]
    return str(getattr(k, "key", getattr(k, "name", k)))


def _fan_in(name: str, shape) -> int:
    if name in ("wq", "wk", "wv"):          # (d, heads, head_dim)
        return shape[-3]
    if name == "wo":                        # (heads, head_dim, d)
        return shape[-3] * shape[-2]
    return shape[-2]                        # (in, out)


def _leaf(key, name: str, s):
    shape, dt = s.shape, s.dtype
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "scale":
        v = 1.0 + 0.1 * z
    elif len(shape) == 0 or name.startswith("b"):
        v = 0.02 * z
    elif name == "embed":
        v = 0.02 * z
    else:
        v = z * _fan_in(name, shape) ** -0.5
    return v.astype(dt)


def make(model, seed: int):
    """The parameter pytree of ``model`` filled from ``seed``."""
    key = jax.random.PRNGKey(seed)
    shapes = jax.eval_shape(model.init, key)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def init(key):
        return treedef.unflatten([
            _leaf(jax.random.fold_in(key, i), _name(path), s)
            for i, (path, s) in enumerate(flat)])

    return init(key)
