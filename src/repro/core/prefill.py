"""Prefill memoization — KV-bearing memo entries (AttnCache; DESIGN.md §2.13).

AttMemo memoizes the attention-probability matrix; its sequel AttnCache
(arXiv:2510.25979, PAPERS.md) memoizes LLM *prefill*, where a hit must
hand back more than the attention output: autoregressive decode needs the
layer's K/V cache, so the memo entry becomes "APM + per-layer K/V".

``PrefillCodec`` extends the PR 3 codec-part arena machinery instead of
inventing a second store: it wraps any base APM codec and APPENDS the KV
parts after the base parts, so every consumer of the parts tuple — the
host/device arenas, delta sync, the capacity tier's mmap files + WAL,
save format 3, per-row CRC32s, ``put_parts`` promotion, the sharded
arenas — carries KV without modification. Order matters: the fused memo
kernel indexes ``db_parts[0]``/``db_parts[1]`` positionally (int8
codes/scales), which is why KV parts must come AFTER the base parts;
``decode``/``decode_rows`` keep the base codec's contract (APM out) by
slicing the prefix, and ``decode_kv_rows`` is the new device-side read.

KV layout per entry: one stacked plane ``(2, S, D)`` — plane 0 is K,
plane 1 is V, ``S`` the arena (calibration) sequence length, ``D =
n_kv_heads * head_dim`` flattened. K is stored POST-RoPE (exactly what
``gqa_prefill_cache`` caches): prefill positions are absolute from 0, so
the rotation is identical for every prompt of the same length and the
stored K drops into the decode cache as-is. Rows past an entry's true
length are zero — the same convention as the exact prefill path, which
zero-pads the cache to ``cache_len``.

KV compression mirrors the APM codecs: ``f16`` identity, ``int8``
per-row symmetric quant (rows are the ``D``-vectors of one position ×
plane), and ``lowrank`` an SVD factorization of each ``(S, D)`` plane
with int8-quantized factors. ``kv_codec="auto"`` matches the base codec
(f16 base → f16 KV, compressed base → int8 KV — low-rank KV is opt-in
because K/V spectra decay slower than softmax rows).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.codec import ApmCodec, PartSpec, _quantize_rows, as_f16


def _kv_mode(base_name: str, kv_codec: str,
             kv_rank: Optional[int]) -> str:
    """Resolve the KV storage mode. An explicit rank opts into lowrank."""
    if kv_codec == "auto":
        if kv_rank is not None:
            return "lowrank"
        return "f16" if base_name == "f16" else "int8"
    return kv_codec


class PrefillCodec(ApmCodec):
    """Base APM codec + appended K/V parts (one memo entry serves both
    the memoized attention AND the decode cache)."""

    def __init__(self, base: ApmCodec, kv_dim: int, *,
                 kv_codec: str = "auto", kv_rank: Optional[int] = None):
        super().__init__(base.apm_shape)
        self.base = base
        self.kv_dim = int(kv_dim)
        self.seq_len = int(self.apm_shape[-1])
        self.kv_mode = _kv_mode(base.name, kv_codec, kv_rank)
        if self.kv_mode not in ("f16", "int8", "lowrank"):
            raise ValueError(f"unknown kv codec {self.kv_mode!r} "
                             "(f16 | int8 | lowrank)")
        lim = min(self.seq_len, self.kv_dim)
        self.kv_rank = (min(lim, max(1, int(kv_rank))) if kv_rank
                        else min(lim, max(4, lim // 8)))
        self.n_base_parts = len(base.parts)

    # the wrapped codec's name is THE codec name: the fused kernel path
    # branches on it positionally (parts[0]/parts[1]), which stays valid
    # because KV parts are appended after the base parts
    @property
    def name(self):  # type: ignore[override]
        return self.base.name

    @property
    def key(self):
        kv = (self.kv_mode, self.kv_dim,
              self.kv_rank if self.kv_mode == "lowrank" else None)
        return ("prefill", self.base.key, kv)

    @property
    def parts(self) -> Tuple[PartSpec, ...]:
        s, d = self.seq_len, self.kv_dim
        if self.kv_mode == "f16":
            kv = (PartSpec("kv", (2, s, d), np.dtype(np.float16)),)
        elif self.kv_mode == "int8":
            kv = (PartSpec("kv", (2, s, d), np.dtype(np.int8)),
                  PartSpec("kv_scale", (2, s), np.dtype(np.float16)))
        else:
            r = self.kv_rank
            kv = (PartSpec("kv_u", (2, s, r), np.dtype(np.int8)),
                  PartSpec("kv_us", (2, s), np.dtype(np.float16)),
                  PartSpec("kv_v", (2, r, d), np.dtype(np.int8)),
                  PartSpec("kv_vs", (2, r), np.dtype(np.float16)))
        return self.base.parts + kv

    # ------------------------------------------------------------- encode
    def encode(self, apms, aux=None):
        """``aux``: the stacked KV plane (B, 2, S, D) f32/f16 — K post-
        RoPE in plane 0, V in plane 1, zero past each entry's true
        length. ``None`` falls back to zero KV (legacy callers that
        admit APM-only entries — their decode caches replay as zeros, so
        the engine gates prefill capture to KV-bearing batches)."""
        base_parts = self.base.encode(apms)
        b = np.asarray(apms).shape[0]
        if aux is None:
            kv = np.zeros((b, 2, self.seq_len, self.kv_dim), np.float32)
        else:
            kv = np.asarray(aux, np.float32)
            if kv.shape != (b, 2, self.seq_len, self.kv_dim):
                raise ValueError(
                    f"kv aux shape {kv.shape} != "
                    f"{(b, 2, self.seq_len, self.kv_dim)}")
        if self.kv_mode == "f16":
            kv_parts = (kv.astype(np.float16),)
        elif self.kv_mode == "int8":
            kv_parts = _quantize_rows(kv)
        else:
            r = self.kv_rank
            u, s, vt = np.linalg.svd(kv, full_matrices=False)
            root = np.sqrt(s[..., :r])
            uf = u[..., :, :r] * root[..., None, :]      # (B, 2, S, r)
            vf = vt[..., :r, :] * root[..., :, None]     # (B, 2, r, D)
            uq, us = _quantize_rows(uf)
            vq, vs = _quantize_rows(vf)
            kv_parts = (uq, us, vq, vs)
        return base_parts + kv_parts

    # ------------------------------------------------------------- decode
    def decode(self, parts):
        """Host decode keeps the base contract: parts → f16 APMs. The KV
        suffix is ignored here; ``decode_kv`` is the explicit read."""
        return self.base.decode(tuple(parts)[: self.n_base_parts])

    def decode_rows(self, parts):
        return self.base.decode_rows(tuple(parts)[: self.n_base_parts])

    def _kv_parts(self, parts):
        kv = tuple(parts)[self.n_base_parts:]
        if not kv:
            raise ValueError("parts tuple carries no KV suffix")
        return kv

    def decode_kv(self, parts) -> np.ndarray:
        """Host KV decode: numpy parts → (B, 2, S, D) f16 planes."""
        kv = self._kv_parts(parts)
        if self.kv_mode == "f16":
            return np.asarray(kv[0])
        if self.kv_mode == "int8":
            codes, scales = kv
            return (np.asarray(codes, np.float32)
                    * np.asarray(scales, np.float32)[..., None]
                    ).astype(np.float16)
        uq, us, vq, vs = kv
        u = np.asarray(uq, np.float32) * np.asarray(us, np.float32)[..., None]
        v = np.asarray(vq, np.float32) * np.asarray(vs, np.float32)[..., None]
        return np.einsum("...sr,...rd->...sd", u, v).astype(np.float16)

    def decode_kv_rows(self, parts) -> jnp.ndarray:
        """Device KV decode, traceable: jnp parts → (B, 2, S, D) f16 —
        mirrors ``decode_kv`` op-for-op (the same host/device parity
        contract as the APM codecs)."""
        kv = self._kv_parts(parts)
        if self.kv_mode == "f16":
            return as_f16(kv[0])
        if self.kv_mode == "int8":
            codes, scales = kv
            return (codes.astype(jnp.float32)
                    * as_f16(scales).astype(jnp.float32)[..., None]
                    ).astype(jnp.float16)
        uq, us, vq, vs = kv
        u = uq.astype(jnp.float32) * as_f16(us).astype(jnp.float32)[..., None]
        v = vq.astype(jnp.float32) * as_f16(vs).astype(jnp.float32)[..., None]
        return jnp.einsum("...sr,...rd->...sd", u, v).astype(jnp.float16)


def stack_kv(k: np.ndarray, v: np.ndarray) -> np.ndarray:
    """(B, S, Hkv, dh) K and V → the stored (B, 2, S, Hkv*dh) plane."""
    k = np.asarray(k)
    b, s = k.shape[0], k.shape[1]
    return np.stack([k.reshape(b, s, -1),
                     np.asarray(v).reshape(b, s, -1)], axis=1)


def unstack_kv_rows(kv: jnp.ndarray, n_kv_heads: int,
                    head_dim: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Traceable inverse of ``stack_kv``: (B, 2, S, D) → K, V each
    (B, S, Hkv, dh) — the decode-cache layout ``gqa_decode`` consumes."""
    b, _, s, _ = kv.shape
    shaped = kv.reshape(b, 2, s, n_kv_heads, head_dim)
    return shaped[:, 0], shaped[:, 1]
