"""Unified model interface over the backbone / enc-dec assemblies.

``build_model(cfg)`` → ``Model`` exposing:
    init, specs, forward, train_loss, classify, prefill, decode_step,
    init_caches
All methods are pure and jit-friendly; batch dicts use
{"tokens": (B,S) int32[, "frames": (B,F,d_enc) f32, "labels": (B,) int32]}.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import backbone as bb
from repro.models import encdec as ed


class Model:
    def __init__(self, cfg, *, mesh=None, dp_axes=("data",),
                 layer_loop="scan", remat=False,
                 max_seq=4096):
        self.cfg = cfg
        self.mesh = mesh
        self.dp_axes = dp_axes
        self.layer_loop = layer_loop
        self.remat = remat
        self.max_seq = max_seq
        self.is_encdec = cfg.encoder is not None
        if self.is_encdec:
            self._ecfg = cfg.replace(
                d_model=cfg.encoder.d_model, n_heads=cfg.encoder.n_heads,
                n_kv_heads=cfg.encoder.n_heads,
                d_head=cfg.encoder.d_model // cfg.encoder.n_heads,
                qkv_bias=False, qk_norm=False)

    # -- params ------------------------------------------------------------
    def init(self, key, dtype=jnp.float32):
        if self.is_encdec:
            params, _ = ed.encdec_init(key, self.cfg, self.max_seq, dtype)
            return params
        return bb.backbone_init(key, self.cfg, dtype)

    def specs(self):
        if self.is_encdec:
            return ed.encdec_specs(self.cfg)
        return bb.backbone_specs(self.cfg)

    # -- full-sequence forward ----------------------------------------------
    def forward(self, params, batch, *, capture=False, memo_plan=None,
                window=None):
        """Returns (logits, apms, aux)."""
        if self.is_encdec:
            enc_h, apms = ed.encode(
                params, batch["frames"], self.cfg, self._ecfg,
                capture=capture, memo_plan=memo_plan,
                layer_loop=self.layer_loop)
            h, _ = ed.decode_tokens(params, batch["tokens"], enc_h, self.cfg,
                                    mode="full", window=window,
                                    remat=self.remat,
                                    unroll=(self.layer_loop != "scan"))
            h = bb.norm_apply(params["final_norm"], h, self.cfg.norm)
            logits = h @ params["embed"].T
            return logits, apms, jnp.zeros((), jnp.float32)
        h = bb.embed_tokens(params, batch["tokens"], self.cfg)
        h, _, apms, aux = bb.forward_hidden(
            params, h, self.cfg, mode="full", memo_plan=memo_plan,
            capture=capture, layer_loop=self.layer_loop, mesh=self.mesh,
            dp_axes=self.dp_axes, window=window, remat=self.remat)
        return bb.logits_from_hidden(params, h, self.cfg), apms, aux

    # -- losses --------------------------------------------------------------
    def train_loss(self, params, batch):
        logits, _, aux = self.forward(params, batch)
        tok = batch["tokens"]
        lg = logits[:, :-1].astype(jnp.float32)
        tgt = tok[:, 1:]
        logp = jax.nn.log_softmax(lg, -1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], -1)[..., 0]
        loss = jnp.mean(nll)
        if self.cfg.moe is not None:
            loss = loss + self.cfg.moe.aux_loss_coef * aux
        return loss

    def classify(self, params, batch, *, memo_plan=None, capture=False):
        """Mean-pool classification (AttMemo accuracy experiments)."""
        h = bb.embed_tokens(params, batch["tokens"], self.cfg)
        h, _, apms, _ = bb.forward_hidden(
            params, h, self.cfg, mode="full", memo_plan=memo_plan,
            capture=capture, layer_loop=self.layer_loop, mesh=self.mesh,
            dp_axes=self.dp_axes)
        logits = bb.classify_from_hidden(params, h, self.cfg)
        return (logits, apms) if capture else logits

    def classify_loss(self, params, batch):
        logits = self.classify(params, batch).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(
            jnp.take_along_axis(logp, batch["labels"][:, None], -1))

    # -- serving ---------------------------------------------------------------
    def init_caches(self, batch, cache_len, dtype=jnp.float32, window=None):
        if self.is_encdec:
            return ed.encdec_init_caches(self.cfg, batch,
                                         min(cache_len, window or cache_len),
                                         dtype)
        return bb.init_caches(self.cfg, batch, cache_len, dtype,
                              window=window)

    def prefill(self, params, batch, *, cache_len, window=None,
                dtype=jnp.float32):
        """Process the prompt; returns (last_token_logits, caches)."""
        tokens = batch["tokens"]
        B, S = tokens.shape[0], tokens.shape[1]
        caches = self.init_caches(B, cache_len, dtype, window=window)
        if self.is_encdec:
            enc_h, _ = ed.encode(params, batch["frames"], self.cfg,
                                 self._ecfg, layer_loop=self.layer_loop)
            h, caches = ed.decode_tokens(params, tokens, enc_h, self.cfg,
                                         mode="prefill", caches=caches,
                                         window=window,
                                         unroll=(self.layer_loop != "scan"))
            h = bb.norm_apply(params["final_norm"], h[:, -1:], self.cfg.norm)
            return (h @ params["embed"].T)[:, 0], caches
        h = bb.embed_tokens(params, tokens, self.cfg)
        h, caches, _, _ = bb.forward_hidden(
            params, h, self.cfg, mode="prefill", caches=caches,
            layer_loop=self.layer_loop, mesh=self.mesh,
            dp_axes=self.dp_axes, window=window)
        logits = bb.logits_from_hidden(params, h[:, -1:], self.cfg)
        return logits[:, 0], caches

    def decode_step(self, params, tokens, caches, pos, *, window=None):
        """tokens: (B,1). Returns (logits (B,V), new_caches)."""
        if self.is_encdec:
            h, caches = ed.decode_tokens(params, tokens, None, self.cfg,
                                         mode="decode", caches=caches,
                                         pos=pos, window=window,
                                         unroll=(self.layer_loop != "scan"))
            h = bb.norm_apply(params["final_norm"], h, self.cfg.norm)
            return (h @ params["embed"].T)[:, 0], caches
        h = bb.embed_tokens(params, tokens, self.cfg)
        h, caches, _, _ = bb.forward_hidden(
            params, h, self.cfg, mode="decode", caches=caches, pos=pos,
            layer_loop=self.layer_loop, mesh=self.mesh,
            dp_axes=self.dp_axes, window=window)
        logits = bb.logits_from_hidden(params, h, self.cfg)
        return logits[:, 0], caches


def build_model(cfg, **kw) -> Model:
    return Model(cfg, **kw)
