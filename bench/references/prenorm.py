"""Plain reference of the pre-norm rotary encoder that the
``bert_base_rope*`` configurations run, in straightforward ``jax.numpy``.

It imports nothing of the program. It reads the weights the benchmark made
(``weights.py``) by their names in the program's parameter layout: layers
stacked along a leading axis under ``params["layers"]["seg0"]["l0"]``.

Each layer is ``h += attn(norm1(h)); h += mlp(norm2(h))`` with layer
norms (eps 1e-6), rotary positions on queries and keys (rotate-half form,
theta ``rope_theta``), softmax attention over all keys, and a two-matrix
MLP with biases and the tanh form of GELU. The classifier mean-pools the
final-normed hidden states.

``quant="int8"`` is the control: every linear layer computes with
symmetric int8 weights (one scale per output column) and int8 inputs (one
scale per tensor), as a plain W8A8 model would; attention products stay
float32.
Called under ``jax.default_matmul_precision("highest")`` it is the float32
reference.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EPS = 1e-6


def _q8(x, axis):
    """Symmetric int8 rounding with one scale per slice along ``axis``
    (``None``: one scale for the whole tensor)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


def linear(x, w, quant):
    """x (..., n) @ w (n, m)."""
    if quant == "int8":
        x, w = _q8(x, None), _q8(w, 0)
    return x @ w


def layer_norm(x, p):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def rope(x, theta):
    """x (B, S, H, dh): rotate the two halves of each head by angle
    position / theta^(2i/dh)."""
    S, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, dh/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, p, cfg, quant):
    B, S, D = x.shape
    H, Hkv = cfg["n_heads"], cfg["n_kv_heads"]
    dh = cfg.get("d_head") or D // H

    def proj(w, heads):
        return linear(x, w.reshape(D, heads * dh), quant).reshape(
            B, S, heads, dh)

    q = rope(proj(p["wq"], H), cfg["rope_theta"])
    k = rope(proj(p["wk"], Hkv), cfg["rope_theta"])
    v = proj(p["wv"], Hkv)
    rep = H // Hkv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    apm = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", apm, v).reshape(B, S, H * dh)
    return linear(out, p["wo"].reshape(H * dh, D), quant)


def mlp(x, p, quant):
    h = gelu(linear(x, p["w_up"], quant) + p["b_up"])
    return linear(h, p["w_down"], quant) + p["b_down"]


def hidden(params, tokens, cfg, quant=None):
    """Final-normed hidden states (B, S, D) of token ids (B, S)."""
    if cfg["norm"] != "layernorm" or cfg["glu"] or cfg["causal"] \
            or cfg.get("qkv_bias") or cfg.get("qk_norm") \
            or cfg.get("mixer", "gqa") != "gqa":
        raise ValueError("this reference covers the bidirectional "
                         "layer-norm, plain-MLP GQA encoder only")
    stack = params["layers"]["seg0"]["l0"]
    h = params["embed"][tokens].astype(jnp.float32)
    for i in range(cfg["n_layers"]):
        lp = jax.tree.map(lambda a: a[i].astype(jnp.float32), stack)
        h = h + attention(layer_norm(h, lp["norm1"]), lp["mix"], cfg, quant)
        h = h + mlp(layer_norm(h, lp["norm2"]), lp["chan"], quant)
    return layer_norm(h, params["final_norm"])


def classify(params, tokens, cfg, quant=None):
    """Class logits (B, n_classes) of full-length prompts."""
    pooled = jnp.mean(hidden(params, tokens, cfg, quant), axis=1)
    return linear(pooled, params["cls"], quant)

