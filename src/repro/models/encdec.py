"""Whisper-style encoder-decoder backbone (conv/mel frontend stubbed).

The encoder consumes precomputed frame embeddings (B, n_frames, d_enc) —
the assignment's one allowed stub. Decoder: causal self-attention +
cross-attention + MLP, pre-LayerNorm, learned absolute positions (no RoPE),
as in Whisper. Encoder self-attention APMs are the AttMemo target.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.layers import (
    dense_init, embed_init, mlp_apply, mlp_init, mlp_specs, norm_apply,
    norm_init, norm_specs,
)


# ---------------------------------------------------------------------------
# cross attention
# ---------------------------------------------------------------------------

def cross_init(key, d, d_kv, n_heads, dh, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    return {"wq": dense_init(ks[0], (d, n_heads, dh), scale=d ** -0.5,
                             dtype=dtype),
            "wk": dense_init(ks[1], (d_kv, n_heads, dh), scale=d_kv ** -0.5,
                             dtype=dtype),
            "wv": dense_init(ks[2], (d_kv, n_heads, dh), scale=d_kv ** -0.5,
                             dtype=dtype),
            "wo": dense_init(ks[3], (n_heads, dh, d),
                             scale=(n_heads * dh) ** -0.5, dtype=dtype)}


def cross_specs():
    return {"wq": ("embed", "heads", "head_dim"),
            "wk": ("embed", "heads", "head_dim"),
            "wv": ("embed", "heads", "head_dim"),
            "wo": ("heads", "head_dim", "embed")}


def cross_kv(params, enc_h):
    k = jnp.einsum("bsd,dhe->bshe", enc_h, params["wk"])
    v = jnp.einsum("bsd,dhe->bshe", enc_h, params["wv"])
    return {"ck": k, "cv": v}


def cross_apply(params, x, kv):
    B, S, _ = x.shape
    H, dh = params["wq"].shape[1], params["wq"].shape[2]
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"])
    scores = jnp.einsum("bqhe,bshe->bhqs", q, kv["ck"]).astype(jnp.float32)
    apm = jax.nn.softmax(scores * dh ** -0.5, -1)
    out = jnp.einsum("bhqs,bshe->bqhe", apm.astype(x.dtype), kv["cv"])
    return jnp.einsum("bshe,hed->bsd", out, params["wo"])


# ---------------------------------------------------------------------------
# init / specs
# ---------------------------------------------------------------------------

def encdec_init(key, cfg, max_seq=4096, dtype=jnp.float32):
    e = cfg.encoder
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    # encoder layers are homogeneous (scan-stacked)
    ecfg = cfg.replace(d_model=e.d_model, n_heads=e.n_heads,
                       n_kv_heads=e.n_heads, d_head=e.d_model // e.n_heads,
                       qkv_bias=False, qk_norm=False)

    def enc_layer(k):
        k1, k2 = jax.random.split(k)
        return {"norm1": norm_init(e.d_model, cfg.norm, dtype),
                "attn": attn.gqa_init(k1, ecfg, dtype),
                "norm2": norm_init(e.d_model, cfg.norm, dtype),
                "mlp": mlp_init(k2, e.d_model, e.d_ff, cfg.glu, dtype)}

    def dec_layer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"norm1": norm_init(d, cfg.norm, dtype),
                "attn": attn.gqa_init(k1, cfg, dtype),
                "norm_x": norm_init(d, cfg.norm, dtype),
                "cross": cross_init(k2, d, e.d_model, cfg.n_heads,
                                    cfg.head_dim, dtype),
                "norm2": norm_init(d, cfg.norm, dtype),
                "mlp": mlp_init(k3, d, cfg.d_ff, cfg.glu, dtype)}

    return {
        "enc_pos": (jax.random.normal(ks[0], (e.n_frames, e.d_model))
                    * 0.02).astype(dtype),
        "enc_layers": jax.vmap(enc_layer)(jax.random.split(ks[1], e.n_layers)),
        "enc_norm": norm_init(e.d_model, cfg.norm, dtype),
        "embed": embed_init(ks[2], cfg.vocab, d, dtype),
        "dec_pos": (jax.random.normal(ks[3], (max_seq, d)) * 0.02
                    ).astype(dtype),
        "dec_layers": jax.vmap(dec_layer)(jax.random.split(ks[4],
                                                           cfg.n_layers)),
        "final_norm": norm_init(d, cfg.norm, dtype),
    }, ecfg


def encdec_specs(cfg):
    enc = {"norm1": norm_specs(cfg.norm),
           "attn": attn.gqa_specs(cfg.replace(qkv_bias=False,
                                              qk_norm=False)),
           "norm2": norm_specs(cfg.norm),
           "mlp": mlp_specs(cfg.glu)}
    enc_layers = jax.tree.map(lambda t: ("layers",) + t, enc,
                              is_leaf=lambda t: isinstance(t, tuple))
    dec = {"norm1": norm_specs(cfg.norm),
           "attn": attn.gqa_specs(cfg),
           "norm_x": norm_specs(cfg.norm),
           "cross": cross_specs(),
           "norm2": norm_specs(cfg.norm),
           "mlp": mlp_specs(cfg.glu)}
    dec_layers = jax.tree.map(lambda t: ("layers",) + t, dec,
                              is_leaf=lambda t: isinstance(t, tuple))
    return {"enc_pos": ("frames", "embed"), "enc_layers": enc_layers,
            "enc_norm": norm_specs(cfg.norm), "embed": ("vocab", "embed"),
            "dec_pos": ("seq", "embed"), "dec_layers": dec_layers,
            "final_norm": norm_specs(cfg.norm)}


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------

def encode(params, frames, cfg, ecfg, *, capture=False, memo_plan=None,
           layer_loop="scan"):
    """frames: (B, n_frames, d_enc) stub embeddings → (enc_h, apms)."""
    B, S, _ = frames.shape
    h = frames.astype(params["enc_pos"].dtype) + params["enc_pos"][None, :S]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    apms: Dict[int, Any] = {}

    def one(lp, hh, li=None, cap=False, memo=None):
        x = norm_apply(lp["norm1"], hh, cfg.norm)
        y, apm = attn.gqa_apply(lp["attn"], x, ecfg, positions=positions,
                                mask_kind="bidir", memo=memo,
                                return_apm=cap, use_rope=False)
        hh = hh + y
        x = norm_apply(lp["norm2"], hh, cfg.norm)
        return hh + mlp_apply(lp["mlp"], x, cfg.act, cfg.glu), apm

    if layer_loop == "unroll":
        for li in range(cfg.encoder.n_layers):
            lp = jax.tree.map(lambda a: a[li], params["enc_layers"])
            memo = memo_plan.get(li) if memo_plan else None
            x_in = norm_apply(lp["norm1"], h, cfg.norm)
            h, apm = one(lp, h, li, cap=capture, memo=memo)
            if apm is not None:
                apms[li] = {"apm": apm, "hidden": x_in}
    else:
        def body(hh, lp):
            hh2, _ = one(lp, hh)
            return hh2, ()
        h, _ = jax.lax.scan(body, h, params["enc_layers"],
                            unroll=(layer_loop == "scan_unroll"))
    return norm_apply(params["enc_norm"], h, cfg.norm), apms


# ---------------------------------------------------------------------------
# decoder
# ---------------------------------------------------------------------------

def dec_layer_apply(lp, h, cfg, kv, *, mode, positions, pos, cache,
                    window=None):
    x = norm_apply(lp["norm1"], h, cfg.norm)
    if mode == "decode":
        y, cache_sa = attn.gqa_decode(lp["attn"], x, cfg, cache["sa"], pos,
                                      window=window, use_rope=False)
    else:
        y, _ = attn.gqa_apply(lp["attn"], x, cfg, positions=positions,
                              mask_kind="causal", window=window,
                              use_rope=False)
        cache_sa = (attn.gqa_prefill_cache(
            lp["attn"], x, cfg, positions,
            cache["sa"]["k"].shape[1], use_rope=False)
            if mode == "prefill" else None)
    h = h + y
    x = norm_apply(lp["norm_x"], h, cfg.norm)
    h = h + cross_apply(lp["cross"], x, kv)
    x = norm_apply(lp["norm2"], h, cfg.norm)
    h = h + mlp_apply(lp["mlp"], x, cfg.act, cfg.glu)
    new_cache = {"sa": cache_sa, "kv": kv} if mode != "full" else None
    return h, new_cache


def decode_tokens(params, tokens, enc_h, cfg, *, mode="full", caches=None,
                  pos=None, window=None, remat=False, unroll=False):
    """tokens: (B,S) ids. enc_h: (B,F,d_enc) or None (decode mode uses cached
    cross-kv). Returns (h, new_caches)."""
    B, S = tokens.shape
    if mode == "decode":
        positions = None
        pidx = jnp.asarray(pos, jnp.int32)
        pos_emb = jax.lax.dynamic_slice(
            params["dec_pos"], (jnp.minimum(pidx,
                                            params["dec_pos"].shape[0] - 1), 0),
            (1, params["dec_pos"].shape[1]))[None]
    else:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        pos_emb = params["dec_pos"][None, :S]
    h = params["embed"][tokens] + pos_emb

    if mode == "decode":
        def body(hh, xs):
            lp, gc = xs
            hh2, c = dec_layer_apply(lp, hh, cfg, gc["kv"], mode=mode,
                                     positions=positions, pos=pos, cache=gc,
                                     window=window)
            return hh2, c
        h, cs = jax.lax.scan(body, h, (params["dec_layers"], caches),
                             unroll=unroll)
        return h, cs

    def body(hh, xs):
        lp, gc = xs
        kv = cross_kv(lp["cross"], enc_h)
        hh2, c = dec_layer_apply(lp, hh, cfg, kv, mode=mode,
                                 positions=positions, pos=pos, cache=gc,
                                 window=window)
        return hh2, c
    bodyf = jax.checkpoint(body) if remat else body
    if mode == "full":
        def body_nc(hh, lp):
            kv = cross_kv(lp["cross"], enc_h)
            hh2, _ = dec_layer_apply(lp, hh, cfg, kv, mode="full",
                                     positions=positions, pos=pos, cache=None,
                                     window=window)
            return hh2, ()
        bodyf2 = jax.checkpoint(body_nc) if remat else body_nc
        h, _ = jax.lax.scan(bodyf2, h, params["dec_layers"],
                            unroll=unroll)
        return h, None
    h, cs = jax.lax.scan(bodyf, h, (params["dec_layers"], caches),
                         unroll=unroll)
    return h, cs


def encdec_init_caches(cfg, batch, seq, dtype=jnp.float32):
    e = cfg.encoder
    L, Hkv, dh, H = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim, cfg.n_heads
    return {
        "sa": {"k": jnp.zeros((L, batch, seq, Hkv, dh), dtype),
               "v": jnp.zeros((L, batch, seq, Hkv, dh), dtype)},
        "kv": {"ck": jnp.zeros((L, batch, e.n_frames, H, dh), dtype),
               "cv": jnp.zeros((L, batch, e.n_frames, H, dh), dtype)},
    }
