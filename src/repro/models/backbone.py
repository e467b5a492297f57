"""Decoder-LM backbone: assembles mixers + channel mixers into a model.

Layers are grouped into *segments* for compile-time efficiency:
homogeneous runs are stacked and driven by ``lax.scan`` (keeps the HLO an
O(1) function of depth — essential for the 61-layer dry-runs); hybrid
patterns scan over repeating units; leading dense layers of MoE models are
single segments. ``layer_loop='unroll'`` switches to a python loop so the
AttMemo engine can capture / override per-layer APMs.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv as rwkv_mod
from repro.models.layers import (
    dense_init, embed_init, mlp_apply, mlp_init, mlp_specs, norm_apply,
    norm_init, norm_specs,
)


# ---------------------------------------------------------------------------
# segment plan
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Segment:
    kind: str            # "single" | "scan"
    start: int           # first layer index
    unit: Tuple[str, ...]  # mixer kinds inside one step
    reps: int            # scan repeats (1 for single)


def scan_plan(cfg) -> List[Segment]:
    kinds = cfg.layer_kinds()
    n = cfg.n_layers
    segs: List[Segment] = []
    start = cfg.dense_first_n
    for i in range(start):
        segs.append(Segment("single", i, (kinds[i],), 1))
    unit = len(cfg.layer_pattern) if cfg.layer_pattern != ("mix",) else 1
    reps = (n - start) // unit
    if reps > 0:
        segs.append(Segment("scan", start, tuple(kinds[start:start + unit]),
                            reps))
    for i in range(start + reps * unit, n):
        segs.append(Segment("single", i, (kinds[i],), 1))
    return segs


def _chan_kind(cfg, layer_idx: int) -> str:
    if cfg.layer_kinds()[layer_idx] == "rwkv6":
        return "rwkvc"
    if cfg.moe is not None and layer_idx >= cfg.dense_first_n:
        return "moe"
    return "mlp"


def _dense_ff(cfg, layer_idx: int) -> int:
    if (cfg.moe is not None and layer_idx < cfg.dense_first_n
            and cfg.dense_d_ff):
        return cfg.dense_d_ff
    return cfg.d_ff


# ---------------------------------------------------------------------------
# per-layer init / specs / apply
# ---------------------------------------------------------------------------

_MIX_INIT = {"attn": attn.gqa_init, "mla": attn.mla_init,
             "rwkv6": rwkv_mod.rwkv_time_init, "rglru": rglru_mod.rglru_init}
_MIX_SPECS = {"attn": attn.gqa_specs, "mla": attn.mla_specs,
              "rwkv6": rwkv_mod.rwkv_time_specs, "rglru": rglru_mod.rglru_specs}


def _layer_init(key, cfg, layer_idx, kind, dtype):
    d = cfg.d_model
    k1, k2 = jax.random.split(key)
    p = {"norm1": norm_init(d, cfg.norm, dtype),
         "norm2": norm_init(d, cfg.norm, dtype),
         "mix": _MIX_INIT[kind](k1, cfg, dtype)}
    ck = _chan_kind(cfg, layer_idx)
    if ck == "rwkvc":
        p["chan"] = rwkv_mod.rwkv_channel_init(k2, cfg, dtype)
    elif ck == "moe":
        p["chan"] = moe_mod.moe_init(k2, cfg, dtype)
    else:
        p["chan"] = mlp_init(k2, d, _dense_ff(cfg, layer_idx), cfg.glu, dtype)
    return p


def _layer_specs(cfg, layer_idx, kind):
    s = {"norm1": norm_specs(cfg.norm), "norm2": norm_specs(cfg.norm),
         "mix": _MIX_SPECS[kind](cfg)}
    ck = _chan_kind(cfg, layer_idx)
    if ck == "rwkvc":
        s["chan"] = rwkv_mod.rwkv_channel_specs(cfg)
    elif ck == "moe":
        s["chan"] = moe_mod.moe_specs(cfg)
    else:
        s["chan"] = mlp_specs(cfg.glu)
    return s


def _layer_apply(lp, h, cfg, kind, layer_idx, *, mode, positions, pos, cache,
                 memo=None, capture=False, mesh=None, dp_axes=("data",),
                 window=None, kpad=None):
    """Returns (h, new_cache, apm, aux_loss)."""
    mask_kind = "causal" if cfg.causal else "bidir"
    if cfg.act_shard_batch and mode == "full" and h.ndim == 3:
        from jax.sharding import PartitionSpec as P
        h = jax.lax.with_sharding_constraint(
            h, P(cfg.act_shard_batch, None, None))
    x = norm_apply(lp["norm1"], h, cfg.norm)
    apm = None
    aux = jnp.zeros((), jnp.float32)
    if kind == "attn":
        win = cfg.sliding_window if cfg.sliding_window else window
        if mode == "decode":
            y, cache = attn.gqa_decode(lp["mix"], x, cfg, cache, pos,
                                       window=win)
        else:
            y, apm = attn.gqa_apply(lp["mix"], x, cfg, positions=positions,
                                    mask_kind=mask_kind, window=win,
                                    memo=memo, return_apm=capture,
                                    kpad=kpad)
            if mode == "prefill":
                cache = attn.gqa_prefill_cache(
                    lp["mix"], x, cfg, positions, cache_len_from(cache))
    elif kind == "mla":
        win = window
        if mode == "decode":
            y, cache = attn.mla_decode(lp["mix"], x, cfg, cache, pos,
                                       window=win)
        else:
            y, apm = attn.mla_apply(lp["mix"], x, cfg, positions=positions,
                                    mask_kind=mask_kind, window=win,
                                    memo=memo, return_apm=capture,
                                    kpad=kpad)
            if mode == "prefill":
                cache = attn.mla_prefill_cache(
                    lp["mix"], x, cfg, positions, cache_len_from(cache))
    elif kind == "rwkv6":
        y, cache_t = rwkv_mod.rwkv_time_apply(
            lp["mix"], x, cfg, None if mode == "full" else cache and
            cache.get("time"))
        cache = dict(cache or {}, time=cache_t)
    elif kind == "rglru":
        y, cache_r = rglru_mod.rglru_apply(
            lp["mix"], x, cfg, None if mode == "full" else cache and
            cache.get("rec"))
        cache = dict(cache or {}, rec=cache_r)
    else:
        raise ValueError(kind)
    if apm is not None:
        # AttMemo capture: the memo key is the attention input hidden state
        apm = {"apm": apm, "hidden": x}
    h = h + y

    x = norm_apply(lp["norm2"], h, cfg.norm)
    ck = _chan_kind(cfg, layer_idx)
    if ck == "rwkvc":
        y, cache_c = rwkv_mod.rwkv_channel_apply(
            lp["chan"], x, cfg, None if mode == "full" else cache and
            cache.get("chan"))
        cache = dict(cache or {}, chan=cache_c)
    elif ck == "moe":
        y, aux = moe_mod.moe_apply(lp["chan"], x, cfg, mesh=mesh,
                                   dp_axes=dp_axes)
    else:
        y = mlp_apply(lp["chan"], x, cfg.act, cfg.glu)
    h = h + y
    return h, cache, apm, aux


def cache_len_from(cache) -> int:
    """Total cache slots from a cache template (prefill pads up to this)."""
    if cache is None:
        return 0
    for v in jax.tree.leaves(cache):
        return v.shape[1]
    return 0


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def layer_cache(cfg, kind, layer_idx, batch, seq, dtype):
    if kind == "attn":
        return attn.gqa_init_cache(cfg, batch, seq, dtype)
    if kind == "mla":
        return attn.mla_init_cache(cfg, batch, seq, dtype)
    if kind == "rwkv6":
        c = {"time": rwkv_mod.rwkv_time_init_state(cfg, batch, dtype),
             "chan": rwkv_mod.rwkv_channel_init_state(cfg, batch, dtype)}
        return c
    if kind == "rglru":
        return {"rec": rglru_mod.rglru_init_state(cfg, batch, dtype)}
    raise ValueError(kind)


def init_caches(cfg, batch, seq, dtype=jnp.float32, window=None):
    """Caches per segment. Attention caches sized min(seq, window)."""
    caches = {}
    attn_len = min(seq, window) if window else seq
    for si, seg in enumerate(scan_plan(cfg)):
        def one(kind, idx):
            s = attn_len if kind in ("attn", "mla") else seq
            if kind == "attn" and cfg.sliding_window:
                s = min(seq, cfg.sliding_window)
            return layer_cache(cfg, kind, idx, batch, s, dtype)
        group = {f"l{u}": one(kind, seg.start + u)
                 for u, kind in enumerate(seg.unit)}
        if seg.kind == "scan":
            group = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (seg.reps,) + a.shape), group)
        caches[f"seg{si}"] = group
    return caches


# ---------------------------------------------------------------------------
# backbone init / specs
# ---------------------------------------------------------------------------

def backbone_init(key, cfg, dtype=jnp.float32):
    keys = jax.random.split(key, 4)
    p: Dict[str, Any] = {
        "embed": embed_init(keys[0], cfg.vocab, cfg.d_model, dtype),
        "final_norm": norm_init(cfg.d_model, cfg.norm, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(keys[1], (cfg.d_model, cfg.vocab),
                                  dtype=dtype)
    if cfg.n_classes:
        p["cls"] = dense_init(keys[2], (cfg.d_model, cfg.n_classes),
                              dtype=dtype)
    layers = {}
    lkey = keys[3]
    for si, seg in enumerate(scan_plan(cfg)):
        lkey, skey = jax.random.split(lkey)
        def group_init(k):
            ks = jax.random.split(k, len(seg.unit))
            return {f"l{u}": _layer_init(ks[u], cfg, seg.start + u, kind,
                                         dtype)
                    for u, kind in enumerate(seg.unit)}
        if seg.kind == "single":
            layers[f"seg{si}"] = group_init(skey)
        else:
            layers[f"seg{si}"] = jax.vmap(group_init)(
                jax.random.split(skey, seg.reps))
    p["layers"] = layers
    return p


def backbone_specs(cfg):
    s: Dict[str, Any] = {"embed": ("vocab", "embed"),
                         "final_norm": norm_specs(cfg.norm)}
    if not cfg.tie_embeddings:
        s["lm_head"] = ("embed", "vocab")
    if cfg.n_classes:
        s["cls"] = ("embed", None)
    layers = {}
    for si, seg in enumerate(scan_plan(cfg)):
        group = {f"l{u}": _layer_specs(cfg, seg.start + u, kind)
                 for u, kind in enumerate(seg.unit)}
        if seg.kind == "scan":
            group = jax.tree.map(lambda t: ("layers",) + t, group,
                                 is_leaf=lambda t: isinstance(t, tuple))
        layers[f"seg{si}"] = group
    s["layers"] = layers
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def embed_tokens(params, tokens, cfg):
    """tokens: int ids (B,S) or precomputed embeddings (B,S,D) (stub
    frontends feed embeddings directly)."""
    if tokens.ndim == 3:
        return tokens.astype(params["embed"].dtype)
    return params["embed"][tokens]


def forward_hidden(params, h, cfg, *, mode="full", positions=None, pos=None,
                   caches=None, memo_plan=None, capture=False,
                   layer_loop="scan", mesh=None, dp_axes=("data",),
                   window=None, remat=False):
    """Run all layers. Returns (h, new_caches, apms{layer_idx: apm}, aux)."""
    apms: Dict[int, Any] = {}
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = {}
    if positions is None and mode != "decode":
        B, S = h.shape[0], h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    for si, seg in enumerate(scan_plan(cfg)):
        seg_params = params["layers"][f"seg{si}"]
        seg_caches = caches.get(f"seg{si}") if caches else None

        def group_apply(gp, hh, gcaches, rep_idx=0, allow_capture=False):
            out_caches = {}
            local_apms = {}
            aux_sum = jnp.zeros((), jnp.float32)
            for u, kind in enumerate(seg.unit):
                li = seg.start + rep_idx * len(seg.unit) + u
                memo = memo_plan.get(li) if memo_plan else None
                cap = capture and allow_capture and kind in ("attn", "mla")
                hh, c, apm, aux = _layer_apply(
                    gp[f"l{u}"], hh, cfg, kind, li, mode=mode,
                    positions=positions, pos=pos,
                    cache=gcaches.get(f"l{u}") if gcaches else None,
                    memo=memo, capture=cap, mesh=mesh, dp_axes=dp_axes,
                    window=window)
                out_caches[f"l{u}"] = c
                aux_sum = aux_sum + aux
                if apm is not None:
                    local_apms[li] = apm
            return hh, out_caches, aux_sum, local_apms

        if seg.kind == "single" or layer_loop == "unroll":
            if seg.kind == "single":
                h, c, aux, la = group_apply(seg_params, h, seg_caches,
                                            allow_capture=True)
                aux_total = aux_total + aux
                apms.update(la)
                new_caches[f"seg{si}"] = c
            else:
                cs = []
                for r in range(seg.reps):
                    gp = jax.tree.map(lambda a: a[r], seg_params)
                    gc = (jax.tree.map(lambda a: a[r], seg_caches)
                          if seg_caches else None)
                    h, c, aux, la = group_apply(gp, h, gc, rep_idx=r,
                                                allow_capture=True)
                    aux_total = aux_total + aux
                    apms.update(la)
                    cs.append(c)
                new_caches[f"seg{si}"] = jax.tree.map(
                    lambda *a: jnp.stack(a), *cs)
        else:
            def scan_body(carry, xs):
                hh, aux_acc = carry
                gp, gc = xs
                hh2, c, aux, _ = group_apply(gp, hh, gc)
                return (hh2, aux_acc + aux), c
            body = jax.checkpoint(scan_body) if remat else scan_body
            if seg_caches is None:
                template = {f"l{u}": None for u in range(len(seg.unit))}

                def scan_body_nc(carry, gp):
                    hh, aux_acc = carry
                    hh2, _, aux, _ = group_apply(gp, hh, template)
                    return (hh2, aux_acc + aux), ()
                body_nc = (jax.checkpoint(scan_body_nc) if remat
                           else scan_body_nc)
                (h, aux_total), _ = jax.lax.scan(
                    body_nc, (h, aux_total), seg_params)
                new_caches[f"seg{si}"] = None
            else:
                (h, aux_total), cs = jax.lax.scan(
                    body, (h, aux_total), (seg_params, seg_caches))
                new_caches[f"seg{si}"] = cs
    return h, new_caches, apms, aux_total


def iter_layers(params, cfg):
    """Yield (layer_idx, kind, layer_params) in depth order — used by the
    AttMemo engine to run the network layer-by-layer with host round-trips
    to the index/attention databases."""
    for si, seg in enumerate(scan_plan(cfg)):
        sp = params["layers"][f"seg{si}"]
        if seg.kind == "single":
            for u, kind in enumerate(seg.unit):
                yield seg.start + u, kind, sp[f"l{u}"]
        else:
            for r in range(seg.reps):
                gp = jax.tree.map(lambda a: a[r], sp)
                for u, kind in enumerate(seg.unit):
                    yield (seg.start + r * len(seg.unit) + u, kind,
                           gp[f"l{u}"])


def logits_from_hidden(params, h, cfg):
    h = norm_apply(params["final_norm"], h, cfg.norm)
    if cfg.tie_embeddings:
        return h @ params["embed"].T
    return h @ params["lm_head"]


def classify_from_hidden(params, h, cfg, kpad=None):
    """``kpad``: optional (B, S) bool validity mask — padded positions are
    excluded from the mean pool so a padded variable-length batch scores
    each sequence exactly like its unpadded run."""
    h = norm_apply(params["final_norm"], h, cfg.norm)
    if kpad is None:
        pooled = jnp.mean(h, axis=1)
    else:
        m = kpad.astype(h.dtype)[:, :, None]
        pooled = jnp.sum(h * m, axis=1) / jnp.maximum(
            jnp.sum(m, axis=1), 1.0)
    return pooled @ params["cls"]
