"""What each parameter of the program is, by the name its init gives it.

Every leaf is one of four kinds: a matrix, whose input (contracted) axes
are named counted from the end, so that a leading layer-stacking or expert
axis is never part of its fan-in; a norm's scale; a bias; or an
embedding. ``weights.py`` draws each kind by its own rule and
``work/model_step.py`` counts the matrices' operations.

The table covers grouped-query attention, latent attention (MLA), dense
and gated MLPs, routed experts, norms and heads. Two layouts need no entry
of their own: an MLA query projection with no low-rank query (no
``q_lora``) is ``wq`` of shape (d, heads, nope + rope), as in GQA; and a
shared expert is a ``w_gate``/``w_up``/``w_down`` sub-dict of a layer's
``chan``, counted in full, where the routed experts beside ``w_router``
count at their routed share.

A name missing from the table is an error that names the leaf's path:
a parameter the benchmark does not know is never drawn by a default.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Tuple


class Leaf(NamedTuple):
    kind: str                       # matrix | norm | bias | embed
    inputs: Tuple[int, ...] = ()    # a matrix's input axes, from the end


NORM, BIAS, EMBED = Leaf("norm"), Leaf("bias"), Leaf("embed")


def _matrix(*axes: int) -> Leaf:
    return Leaf("matrix", axes)


TABLE = {
    # attention: wq/wk/wv (d, heads, head), wo (heads, head, d)
    "wq": _matrix(-3), "wk": _matrix(-3), "wv": _matrix(-3),
    "wo": _matrix(-3, -2),
    "bq": BIAS, "bk": BIAS, "bv": BIAS,
    "q_norm": NORM, "k_norm": NORM, "kv_norm": NORM,
    # latent attention: down-projections (d, rank) and (d, rope), the
    # up-projections (rank, heads, head)
    "w_dq": _matrix(-2), "w_uq": _matrix(-3),
    "w_dkv": _matrix(-2), "w_kr": _matrix(-2),
    "w_uk": _matrix(-3), "w_uv": _matrix(-3),
    # MLP and experts: ([experts,] in, out); the router (d, experts)
    "w_gate": _matrix(-2), "w_up": _matrix(-2), "w_down": _matrix(-2),
    "w_router": _matrix(-2),
    "b_up": BIAS, "b_down": BIAS,
    # norms, embedding, heads
    "scale": NORM, "bias": BIAS, "embed": EMBED,
    "lm_head": _matrix(-2), "cls": _matrix(-2),
}


def name(key) -> str:
    """One entry of a JAX key path as a string."""
    return str(getattr(key, "key", getattr(key, "name", key)))


def kind(path) -> Leaf:
    """The kind of the leaf at ``path`` (a JAX key path or names)."""
    names = [name(k) for k in path]
    try:
        return TABLE[names[-1]]
    except KeyError:
        raise KeyError(f"parameter {'/'.join(names)}: no kind for "
                       f"{names[-1]!r} in bench/leaves.py") from None


def fan_in(leaf: Leaf, shape) -> int:
    """The product of a matrix's input axes."""
    return math.prod(shape[a] for a in leaf.inputs)
