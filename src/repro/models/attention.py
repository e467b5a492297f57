"""Attention mixers: GQA/MQA/MHA and MLA (Multi-head Latent Attention).

All functions are pure; params are dicts. Each full-sequence apply can
  * capture the attention-probability matrix (APM) — AttMemo's memoized
    quantity — via ``return_apm=True``;
  * consume a memoized APM override via ``memo=(apm, hit)`` where
    ``apm: (B, H, S, S)`` and ``hit: (B,) bool``: sequences with hit=True skip
    QK^T + softmax entirely (engine-level bucketing makes that skip real; in
    the fused Pallas kernel the skip is per-sequence via pl.when).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init


class Memo(NamedTuple):
    apm: jnp.ndarray          # (B, H, Sq, Sk) memoized probabilities
    hit: jnp.ndarray          # (B,) bool
    idx: jnp.ndarray = None   # (B,) DB indices (device-DB kernel path)


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------

def make_mask(sq: int, sk: int, kind: str, window: Optional[int] = None,
              offset: int = 0):
    """(sq, sk) boolean mask. kind: causal | bidir. ``offset`` is the absolute
    position of query 0 (prefill chunking / decode)."""
    if kind == "bidir" and window is None:
        return jnp.ones((sq, sk), bool)
    qpos = jnp.arange(sq)[:, None] + offset
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if kind == "causal":
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    return mask


def _sdpa(q, k, v, mask, scale, memo: Optional[Memo] = None,
          return_apm: bool = False):
    """q: (B,Sq,Hkv,G,dh)  k,v: (B,Sk,Hkv,dh)  mask: (Sq,Sk) or (B,Sq,Sk)."""
    B, Sq, Hkv, G, dh = q.shape
    scores = jnp.einsum("bqhgd,bshd->bhgqs", q, k).astype(jnp.float32) * scale
    if mask.ndim == 2:
        mask = mask[None]
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.where(mask[:, None, None], scores, neg)
    apm = jax.nn.softmax(scores, axis=-1)
    if memo is not None:
        memo_apm = memo.apm.reshape(B, Hkv, G, Sq, -1).astype(jnp.float32)
        apm = jnp.where(memo.hit[:, None, None, None, None], memo_apm, apm)
    out = jnp.einsum("bhgqs,bshd->bqhgd", apm.astype(v.dtype), v)
    apm_full = apm.reshape(B, Hkv * G, Sq, -1) if return_apm else None
    return out, apm_full


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_init(key, cfg, dtype=jnp.float32):
    d, H, Hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], (d, H, dh), scale=d ** -0.5, dtype=dtype),
         "wk": dense_init(ks[1], (d, Hkv, dh), scale=d ** -0.5, dtype=dtype),
         "wv": dense_init(ks[2], (d, Hkv, dh), scale=d ** -0.5, dtype=dtype),
         "wo": dense_init(ks[3], (H, dh, d), scale=(H * dh) ** -0.5, dtype=dtype)}
    if cfg.qkv_bias:
        p.update(bq=jnp.zeros((H, dh), dtype), bk=jnp.zeros((Hkv, dh), dtype),
                 bv=jnp.zeros((Hkv, dh), dtype))
    if cfg.qk_norm:
        p.update(q_norm=jnp.ones((dh,), dtype), k_norm=jnp.ones((dh,), dtype))
    return p


def gqa_specs(cfg):
    s = {"wq": ("embed", "heads", "head_dim"),
         "wk": ("embed", "kv_heads", "head_dim"),
         "wv": ("embed", "kv_heads", "head_dim"),
         "wo": ("heads", "head_dim", "embed")}
    if cfg.qkv_bias:
        s.update(bq=("heads", "head_dim"), bk=("kv_heads", "head_dim"),
                 bv=("kv_heads", "head_dim"))
    if cfg.qk_norm:
        s.update(q_norm=("head_dim",), k_norm=("head_dim",))
    return s


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


def _qkv(params, x, cfg, positions, use_rope=True):
    q = jnp.einsum("bsd,dhe->bshe", x, params["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, params["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    if cfg.qk_norm:
        q, k = _rms(q, params["q_norm"]), _rms(k, params["k_norm"])
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_apply(params, x, cfg, *, positions, mask_kind="causal",
              window=None, memo: Optional[Memo] = None, return_apm=False,
              use_rope=True, kpad=None):
    """Full-sequence GQA. x: (B,S,D) → (B,S,D).

    ``kpad``: optional (B, S) bool key-validity mask for padded
    variable-length batches — False keys are excluded from the softmax,
    so a sequence padded to a bucket length produces the same APM rows
    (and zero probability mass on pad columns) as its unpadded run."""
    B, S, _ = x.shape
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = _qkv(params, x, cfg, positions, use_rope)
    qg = q.reshape(B, S, Hkv, H // Hkv, dh)
    mask = make_mask(S, S, mask_kind, window)
    if kpad is not None:
        mask = mask[None] & kpad[:, None, :]
    out, apm = _sdpa(qg, k, v, mask, dh ** -0.5, memo, return_apm)
    out = out.reshape(B, S, H, dh)
    y = jnp.einsum("bshe,hed->bsd", out, params["wo"])
    return y, apm


def gqa_decode(params, x, cfg, cache, pos, *, window=None, use_rope=True):
    """One-token decode. x: (B,1,D); cache: {'k','v'}: (B,Sc,Hkv,dh).
    ``pos``: scalar absolute position. Rolling buffer iff Sc < pos allowed:
    writes at pos % Sc and masks by recency window == Sc."""
    B, _, _ = x.shape
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _qkv(params, x, cfg, positions, use_rope)
    Sc = cache["k"].shape[1]
    slot = jnp.mod(pos, Sc)
    ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    # absolute position of each cache slot under rolling writes
    idx = jnp.arange(Sc)
    wrap = (pos // Sc) * Sc
    abs_pos = jnp.where(idx <= slot, wrap + idx, wrap - Sc + idx)
    valid = (abs_pos >= 0) & (abs_pos <= pos)
    if window is not None:
        valid &= abs_pos > pos - window
    qg = q.reshape(B, 1, Hkv, H // Hkv, dh)
    out, _ = _sdpa(qg, ck, cv, valid[None, :][None], dh ** -0.5)
    out = out.reshape(B, 1, H, dh)
    y = jnp.einsum("bshe,hed->bsd", out, params["wo"])
    return y, {"k": ck, "v": cv}


def gqa_init_cache(cfg, batch, seq, dtype=jnp.float32):
    Hkv, dh = cfg.n_kv_heads, cfg.head_dim
    z = jnp.zeros((batch, seq, Hkv, dh), dtype)
    return {"k": z, "v": z}


def gqa_prefill_cache(params, x, cfg, positions, seq_total, use_rope=True):
    """Build the decode cache from a full prompt (cheaper than re-decode)."""
    _, k, v = _qkv(params, x, cfg, positions, use_rope)
    pad = seq_total - k.shape[1]
    if pad > 0:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return {"k": k, "v": v}


def gqa_apply_memo(params, x, cfg, apm):
    """Memo-only fast path: the APM is fully known, so Q/K projections,
    QKᵀ and softmax are all skipped — only V and the APM·V matmul run.
    This is the compute the paper's memoization actually saves.
    x: (B,S,D); apm: (B,H,S,S) → (B,S,D)."""
    B, S, _ = x.shape
    H, dh = cfg.n_heads, cfg.head_dim
    v = jnp.einsum("bsd,dhe->bshe", x, params["wv"])
    if cfg.qkv_bias:
        v = v + params["bv"]
    Hkv = cfg.n_kv_heads
    apm_g = apm.reshape(B, Hkv, H // Hkv, S, S).astype(v.dtype)
    out = jnp.einsum("bhgqs,bshd->bqhgd", apm_g, v).reshape(B, S, H, dh)
    return jnp.einsum("bshe,hed->bsd", out, params["wo"])


def mla_apply_memo(params, x, cfg, apm):
    """Memo-only MLA fast path: skip q path, QKᵀ and softmax; compute the
    compressed kv and expand V only."""
    m = cfg.mla
    c_kv = _rms(x @ params["w_dkv"], params["kv_norm"])
    v = jnp.einsum("bsr,rhe->bshe", c_kv, params["w_uv"])
    out = jnp.einsum("bhqs,bshe->bqhe", apm.astype(v.dtype), v)
    return jnp.einsum("bshe,hed->bsd", out, params["wo"])


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 / MiniCPM3)
# ---------------------------------------------------------------------------

def mla_init(key, cfg, dtype=jnp.float32):
    d, H = cfg.d_model, cfg.n_heads
    m = cfg.mla
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 7)
    return {
        "w_dq": dense_init(ks[0], (d, m.q_lora_rank), dtype=dtype),
        "q_norm": jnp.ones((m.q_lora_rank,), dtype),
        "w_uq": dense_init(ks[1], (m.q_lora_rank, H, qk),
                           scale=m.q_lora_rank ** -0.5, dtype=dtype),
        "w_dkv": dense_init(ks[2], (d, m.kv_lora_rank), dtype=dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "w_kr": dense_init(ks[3], (d, m.qk_rope_head_dim), dtype=dtype),
        "w_uk": dense_init(ks[4], (m.kv_lora_rank, H, m.qk_nope_head_dim),
                           scale=m.kv_lora_rank ** -0.5, dtype=dtype),
        "w_uv": dense_init(ks[5], (m.kv_lora_rank, H, m.v_head_dim),
                           scale=m.kv_lora_rank ** -0.5, dtype=dtype),
        "wo": dense_init(ks[6], (H, m.v_head_dim, d),
                         scale=(H * m.v_head_dim) ** -0.5, dtype=dtype),
    }


def mla_specs(cfg):
    return {"w_dq": ("embed", "q_lora"), "q_norm": ("q_lora",),
            "w_uq": ("q_lora", "heads", "head_dim"),
            "w_dkv": ("embed", "kv_lora"), "kv_norm": ("kv_lora",),
            "w_kr": ("embed", "head_dim"),
            "w_uk": ("kv_lora", "heads", "head_dim"),
            "w_uv": ("kv_lora", "heads", "head_dim"),
            "wo": ("heads", "head_dim", "embed")}


def _mla_qkr(params, x, cfg, positions):
    m = cfg.mla
    cq = _rms(x @ params["w_dq"], params["q_norm"])
    q = jnp.einsum("bsr,rhe->bshe", cq, params["w_uq"])
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    c_kv = _rms(x @ params["w_dkv"], params["kv_norm"])
    k_rope = apply_rope(x @ params["w_kr"], positions, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope


def mla_apply(params, x, cfg, *, positions, mask_kind="causal", window=None,
              memo: Optional[Memo] = None, return_apm=False, kpad=None):
    B, S, _ = x.shape
    m, H = cfg.mla, cfg.n_heads
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    q_nope, q_rope, c_kv, k_rope = _mla_qkr(params, x, cfg, positions)
    k_nope = jnp.einsum("bsr,rhe->bshe", c_kv, params["w_uk"])
    v = jnp.einsum("bsr,rhe->bshe", c_kv, params["w_uv"])
    scores = (jnp.einsum("bqhe,bshe->bhqs", q_nope, k_nope)
              + jnp.einsum("bqhe,bse->bhqs", q_rope, k_rope))
    scores = scores.astype(jnp.float32) * scale
    mask = make_mask(S, S, mask_kind, window)
    if kpad is not None:
        mask = mask[None] & kpad[:, None, :]
    scores = jnp.where(mask[None, None] if mask.ndim == 2
                       else mask[:, None], scores,
                       jnp.finfo(jnp.float32).min)
    apm = jax.nn.softmax(scores, -1)
    if memo is not None:
        apm = jnp.where(memo.hit[:, None, None, None],
                        memo.apm.astype(jnp.float32), apm)
    out = jnp.einsum("bhqs,bshe->bqhe", apm.astype(v.dtype), v)
    y = jnp.einsum("bshe,hed->bsd", out, params["wo"])
    return y, (apm if return_apm else None)


def mla_decode(params, x, cfg, cache, pos, *, window=None):
    """Absorbed-matmul MLA decode: attention runs in the kv_lora latent space,
    cache holds (c_kv, k_rope) only — the MLA serving advantage."""
    B = x.shape[0]
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    positions = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkr(params, x, cfg, positions)
    Sc = cache["c_kv"].shape[1]
    slot = jnp.mod(pos, Sc)
    c_kv = jax.lax.dynamic_update_slice(cache["c_kv"], c_kv_new, (0, slot, 0))
    k_rope = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope_new,
                                          (0, slot, 0))
    idx = jnp.arange(Sc)
    wrap = (pos // Sc) * Sc
    abs_pos = jnp.where(idx <= slot, wrap + idx, wrap - Sc + idx)
    valid = (abs_pos >= 0) & (abs_pos <= pos)
    if window is not None:
        valid &= abs_pos > pos - window
    # absorbed: q ⋅ W_uk projected into latent space once per step
    q_abs = jnp.einsum("bqhe,rhe->bqhr", q_nope, params["w_uk"])
    scores = (jnp.einsum("bqhr,bsr->bhqs", q_abs, c_kv)
              + jnp.einsum("bqhe,bse->bhqs", q_rope, k_rope))
    scores = scores.astype(jnp.float32) * scale
    scores = jnp.where(valid[None, None, None], scores,
                       jnp.finfo(jnp.float32).min)
    apm = jax.nn.softmax(scores, -1).astype(x.dtype)
    ctx = jnp.einsum("bhqs,bsr->bqhr", apm, c_kv)
    out = jnp.einsum("bqhr,rhe->bqhe", ctx, params["w_uv"])
    y = jnp.einsum("bshe,hed->bsd", out, params["wo"])
    return y, {"c_kv": c_kv, "k_rope": k_rope}


def mla_init_cache(cfg, batch, seq, dtype=jnp.float32):
    m = cfg.mla
    return {"c_kv": jnp.zeros((batch, seq, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, seq, m.qk_rope_head_dim), dtype)}


def mla_prefill_cache(params, x, cfg, positions, seq_total):
    _, _, c_kv, k_rope = _mla_qkr(params, x, cfg, positions)
    pad = seq_total - c_kv.shape[1]
    if pad > 0:
        c_kv = jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
    return {"c_kv": c_kv, "k_rope": k_rope}
