"""RWKV-6 "Finch" mixer — attention-free, data-dependent decay.

[arXiv:2404.05892]. Per head (dim N): state S ∈ R^{N×N},
    o_t = (S_t + diag(u)·k_tᵀv_t)ᵀ r_t,    S_{t+1} = diag(w_t)·S_t + k_tᵀ v_t
with per-channel decay w_t = exp(-exp(w0 + lora_w(x̃_t))) ∈ (0,1) and
ddlerp token-shift mixing (low-rank data-dependent interpolation with the
previous token). Output gating g and per-head GroupNorm as in the paper.

AttMemo is inapplicable here (no attention-probability matrix); see
DESIGN.md §Arch-applicability.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init

_LORA = 64          # ddlerp / decay low-rank dim
_MIX_NAMES = ("r", "k", "v", "w", "g")


def rwkv_time_init(key, cfg, dtype=jnp.float32):
    d = cfg.d_model
    nh = d // cfg.rwkv_head_dim
    ks = jax.random.split(key, 16)
    p = {
        "mu_x": jnp.full((d,), 0.5, dtype),
        # one fused ddlerp lora: d -> 5*_LORA -> 5*d
        "ddlerp_a": dense_init(ks[0], (d, 5 * _LORA), dtype=dtype),
        "ddlerp_b": dense_init(ks[1], (5, _LORA, d), scale=_LORA ** -0.5,
                               dtype=dtype),
        "mu": jnp.full((5, d), 0.5, dtype),            # per-proj base mix
        "w0": jnp.full((d,), -6.0, dtype),              # decay bias (slow)
        "decay_a": dense_init(ks[2], (d, _LORA), dtype=dtype),
        "decay_b": dense_init(ks[3], (_LORA, d), scale=_LORA ** -0.5,
                              dtype=dtype),
        "u": jnp.zeros((d,), dtype),                    # bonus
        "wr": dense_init(ks[4], (d, d), dtype=dtype),
        "wk": dense_init(ks[5], (d, d), dtype=dtype),
        "wv": dense_init(ks[6], (d, d), dtype=dtype),
        "wg": dense_init(ks[7], (d, d), dtype=dtype),
        "wo": dense_init(ks[8], (d, d), dtype=dtype),
        "ln_scale": jnp.ones((nh, cfg.rwkv_head_dim), dtype),
    }
    return p


def rwkv_time_specs(cfg):
    return {"mu_x": ("embed",), "ddlerp_a": ("embed", "lora"),
            "ddlerp_b": ("proj5", "lora", "embed"), "mu": ("proj5", "embed"),
            "w0": ("embed",), "decay_a": ("embed", "lora"),
            "decay_b": ("lora", "embed"), "u": ("embed",),
            "wr": ("embed", "heads_embed"), "wk": ("embed", "heads_embed"),
            "wv": ("embed", "heads_embed"), "wg": ("embed", "heads_embed"),
            "wo": ("heads_embed", "embed"),
            "ln_scale": ("heads", "head_dim")}


def _ddlerp(params, x, x_prev):
    """Returns the 5 mixed inputs (r,k,v,w,g): each (B,S,D)."""
    xx = x_prev - x
    xxx = x + xx * params["mu_x"]
    a = jnp.tanh(xxx @ params["ddlerp_a"])               # (B,S,5*LORA)
    B, S, _ = a.shape
    a = a.reshape(B, S, 5, _LORA)
    lora = jnp.einsum("bspl,pld->bspd", a, params["ddlerp_b"])
    mix = params["mu"][None, None] + lora                # (B,S,5,D)
    return x[:, :, None] + xx[:, :, None] * mix          # (B,S,5,D)


def _wkv_scan(r, k, v, w, u, s0):
    """r,k,v,w: (B,S,nh,N); u: (nh,N); s0: (B,nh,N,N) → o (B,S,nh,N), sT."""
    def step(s, inp):
        r_t, k_t, v_t, w_t = inp                          # (B,nh,N)
        kv = jnp.einsum("bhi,bhj->bhij", k_t, v_t)        # outer product
        o_t = jnp.einsum("bhi,bhij->bhj", r_t, s + u[None, :, :, None] * kv)
        s = w_t[..., None] * s + kv
        return s, o_t
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (r, k, v, w))
    sT, o = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 1), sT


def _groupnorm(x, scale, eps=1e-5):
    """x: (B,S,nh,N) — normalize per head."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def rwkv_time_apply(params, x, cfg, state=None):
    """Full-sequence time-mix. x: (B,S,D). state: {'s','x_prev'} or None.
    Returns (y, new_state)."""
    B, S, d = x.shape
    nh, N = d // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    x_prev = (jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1] if state is None
              else jnp.concatenate([state["x_prev"][:, None], x[:, :-1]], 1))
    mixed = _ddlerp(params, x, x_prev)                    # (B,S,5,D)
    xr, xk, xv, xw, xg = (mixed[:, :, i] for i in range(5))
    r = (xr @ params["wr"]).reshape(B, S, nh, N)
    k = (xk @ params["wk"]).reshape(B, S, nh, N)
    v = (xv @ params["wv"]).reshape(B, S, nh, N)
    g = jax.nn.silu(xg @ params["wg"])
    dec = params["w0"] + jnp.tanh(xw @ params["decay_a"]) @ params["decay_b"]
    w = jnp.exp(-jnp.exp(dec.astype(jnp.float32))).astype(x.dtype)
    w = w.reshape(B, S, nh, N)
    u = params["u"].reshape(nh, N)
    s0 = (jnp.zeros((B, nh, N, N), x.dtype) if state is None else state["s"])
    if cfg.act_shard_batch:
        # pin the scan operands/state to batch-sharding over both mesh
        # axes: heads (40) don't divide model=16, the batch does, and a
        # batch-sharded state keeps the whole recurrence collective-free
        from jax.sharding import PartitionSpec as P
        spec4 = P(cfg.act_shard_batch, None, None, None)
        r, k, v, w = (jax.lax.with_sharding_constraint(t, spec4)
                      for t in (r, k, v, w))
        s0 = jax.lax.with_sharding_constraint(s0, spec4)
    o, sT = _wkv_scan(r, k, v, w, u, s0)
    o = _groupnorm(o, params["ln_scale"]).reshape(B, S, d) * g
    y = o @ params["wo"]
    return y, {"s": sT, "x_prev": x[:, -1]}


def rwkv_time_decode(params, x, cfg, state):
    """One-token step; x: (B,1,D)."""
    return rwkv_time_apply(params, x, cfg, state)


def rwkv_time_init_state(cfg, batch, dtype=jnp.float32):
    d = cfg.d_model
    nh, N = d // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    return {"s": jnp.zeros((batch, nh, N, N), dtype),
            "x_prev": jnp.zeros((batch, d), dtype)}


# ---------------------------------------------------------------------------
# channel mix
# ---------------------------------------------------------------------------

def rwkv_channel_init(key, cfg, dtype=jnp.float32):
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    return {"mu_k": jnp.full((d,), 0.5, dtype),
            "mu_r": jnp.full((d,), 0.5, dtype),
            "wk": dense_init(ks[0], (d, ff), dtype=dtype),
            "wv": dense_init(ks[1], (ff, d), dtype=dtype),
            "wr": dense_init(ks[2], (d, d), dtype=dtype)}


def rwkv_channel_specs(cfg):
    return {"mu_k": ("embed",), "mu_r": ("embed",), "wk": ("embed", "ff"),
            "wv": ("ff", "embed"), "wr": ("embed", "heads_embed")}


def rwkv_channel_apply(params, x, cfg, state=None):
    x_prev = (jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1] if state is None
              else jnp.concatenate([state["x_prev"][:, None], x[:, :-1]], 1))
    xx = x_prev - x
    xk = x + xx * params["mu_k"]
    xr = x + xx * params["mu_r"]
    k = jnp.square(jax.nn.relu(xk @ params["wk"]))
    y = jax.nn.sigmoid(xr @ params["wr"]) * (k @ params["wv"])
    return y, {"x_prev": x[:, -1]}


def rwkv_channel_init_state(cfg, batch, dtype=jnp.float32):
    return {"x_prev": jnp.zeros((batch, cfg.d_model), dtype)}
