"""Seeded weights, made on the device in one jitted call.

The program only tells the shapes (``jax.eval_shape`` of its ``init``,
which computes nothing); the values are the benchmark's own, so the plain
reference takes no weight the program made. Each leaf is drawn by its kind
(``leaves.py``): a matrix at std (fan-in)^-1/2 over its input axes, a norm
scale at 1 + 0.1 z, a bias or an embedding at 0.02 z. Biases and norm
scales are random too, so a reference or a program that drops one is
seen.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import leaves


def _leaf(key, path, s):
    leaf = leaves.kind(path)
    z = jax.random.normal(key, s.shape, jnp.float32)
    if leaf.kind == "norm":
        v = 1.0 + 0.1 * z
    elif leaf.kind in ("bias", "embed"):
        v = 0.02 * z
    else:
        v = z * leaves.fan_in(leaf, s.shape) ** -0.5
    return v.astype(s.dtype)


def shapes_of(model):
    """The parameter shapes ``model.init`` declares, computing nothing."""
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def make(model, seed: int, shapes=None):
    """The parameter pytree of ``model`` filled from ``seed``; ``shapes``,
    where given, is ``shapes_of(model)`` already taken."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes_of(model) if shapes is None else shapes)

    @jax.jit
    def init(key):
        return treedef.unflatten([
            _leaf(jax.random.fold_in(key, i), path, s)
            for i, (path, s) in enumerate(flat)])

    return init(jax.random.PRNGKey(seed))
