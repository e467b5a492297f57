"""APM codecs — compressed storage formats for both memo tiers.

AttMemo's capacity→hit-rate curve (paper Fig. 13) makes the memo DB's
bytes-per-entry the scaling bottleneck: the device tier's HBM holds the
serving copy, and every lookup gathers one entry across the HBM bus.
Attention-map caches tolerate aggressive compression (AttnCache,
arXiv:2510.25979), so the store treats the on-tier representation as a
pluggable codec (DESIGN.md §2.6):

* ``f16``     — identity: one float16 arena (the original layout).
* ``int8``    — symmetric per-row int8 with float16 scales. Each APM row
                (one softmax distribution of length L) quantizes as
                ``codes = round(x / scale)``, ``scale = amax(|row|)/127``
                — rows are probability vectors so ``amax ≤ 1`` and the
                worst-case error is ``scale/2 ≈ 0.004``. ~0.53× the f16
                bytes (codes are half, scales add 1/L).
* ``lowrank`` — rank-r factorization APM ≈ U·Vᵀ (softmax rows
                concentrate mass, so the spectrum decays fast), with the
                factors themselves per-row int8 quantized: bytes ratio
                ≈ (r+2)/L — ~0.19× at L=32, r=4. Lossier than int8;
                the accuracy/bytes trade-off is measured in
                ``benchmarks/serve_compress.py``.

A codec is a set of named *parts* (arena-shaped arrays): the host
``AttentionDB`` allocates one numpy arena per part, ``DeviceDB`` mirrors
them as device arrays, and the delta sync ships part rows — so sync
bytes shrink by the same ratio as storage. ``decode_rows`` is pure jnp
and traceable, which is what lets the engine's fused layer jit (and the
memo_attention kernel for int8) dequantize on device, right before the
APM·V matmul, instead of ever materializing f16 APMs in HBM.

Parity note: ``decode`` (numpy, host path) and ``decode_rows`` (jnp,
device path) perform the identical float32-multiply→float16-round
sequence for ``int8``, so select/bucket/kernel modes consume
bit-identical APMs regardless of which tier served them. ``lowrank``
reconstructs through a matmul whose summation order may differ between
numpy and XLA — parity holds within one float16 ulp, not bit-exactly.

Device form: every device arena (``DeviceDB`` and the sharded
``ShardedDeviceDB``) keeps each float16 part in HBM as its int16 bit
pattern (``hbm_form``; the memo kernel reads the f16 arena, and Mosaic
loads no float16), so ``decode_rows`` reads its float16 parts through
``as_f16`` and accepts either form.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class PartSpec:
    """One arena of a codec: per-entry shape suffix + storage dtype."""
    name: str
    shape: Tuple[int, ...]
    dtype: np.dtype

    @property
    def entry_nbytes(self) -> int:
        return int(np.prod(self.shape)) * np.dtype(self.dtype).itemsize


def hbm_form(part: np.ndarray) -> np.ndarray:
    """The device-tier form of a host part: float16 as its int16 bit
    pattern (same bytes, a numpy view), anything else unchanged."""
    part = np.asarray(part)
    return part.view(np.int16) if part.dtype == np.float16 else part


def as_f16(x):
    """The inverse view on device: int16 float16-bit patterns → float16;
    other dtypes pass unchanged."""
    if x.dtype == jnp.int16:
        return jax.lax.bitcast_convert_type(x, jnp.float16)
    return x


def _quantize_rows(x: np.ndarray):
    """Symmetric per-row int8: x (..., n) → (codes int8 (..., n),
    scales f16 (...)). The f16-rounded scale is the one used for
    encoding, so decode(encode(x)) is exactly reproducible. The scale
    floor 1e-4 keeps all-zero/near-zero rows finite: a tinier floor
    underflows float16 to 0 and the code divide becomes 0/0."""
    x = np.asarray(x, np.float32)
    amax = np.max(np.abs(x), axis=-1)
    scale = np.maximum(amax / 127.0, 1e-4).astype(np.float16)
    codes = np.clip(np.rint(x / scale.astype(np.float32)[..., None]),
                    -127, 127).astype(np.int8)
    return codes, scale


class ApmCodec:
    """Base: a codec is its part specs + encode/decode both ways."""

    name = "abstract"

    def __init__(self, apm_shape: Tuple[int, ...]):
        self.apm_shape = tuple(apm_shape)

    @property
    def parts(self) -> Tuple[PartSpec, ...]:
        raise NotImplementedError

    @property
    def entry_nbytes(self) -> int:
        """Codec-true bytes per entry (what budgets and sync receipts
        must report — NOT the logical f16 shape)."""
        return sum(p.entry_nbytes for p in self.parts)

    @property
    def key(self):
        """Hashable identity for jit-cache keys."""
        return (self.name, self.apm_shape)

    def encode(self, apms: np.ndarray, aux=None) -> Tuple[np.ndarray, ...]:
        """Encode a batch of APMs into per-part rows. ``aux`` carries
        side-channel payload for codecs whose entries hold more than the
        APM (the prefill KV codec, ``core/prefill.py``); plain APM codecs
        ignore it."""
        raise NotImplementedError

    def decode(self, parts) -> np.ndarray:
        """Host decode: numpy parts (B, ...) → f16 APMs (B, *apm_shape)."""
        raise NotImplementedError

    def decode_rows(self, parts):
        """Device decode, traceable: jnp parts → f16 APM rows. Must
        mirror ``decode`` op-for-op (see parity note in module doc)."""
        raise NotImplementedError


class F16Codec(ApmCodec):
    """Identity storage (optionally in a caller-chosen dtype)."""

    name = "f16"

    def __init__(self, apm_shape, dtype=np.float16):
        super().__init__(apm_shape)
        self.dtype = np.dtype(dtype)

    @property
    def parts(self):
        return (PartSpec("apm", self.apm_shape, self.dtype),)

    def encode(self, apms, aux=None):
        return (np.asarray(apms, self.dtype),)

    def decode(self, parts):
        return np.asarray(parts[0])

    def decode_rows(self, parts):
        return as_f16(parts[0])


class Int8Codec(ApmCodec):
    """Symmetric per-row int8 codes + per-row f16 scales."""

    name = "int8"

    @property
    def parts(self):
        h, l, _ = self.apm_shape
        return (PartSpec("codes", self.apm_shape, np.dtype(np.int8)),
                PartSpec("scales", (h, l), np.dtype(np.float16)))

    def encode(self, apms, aux=None):
        return _quantize_rows(np.asarray(apms, np.float32))

    def decode(self, parts):
        codes, scales = parts
        return (np.asarray(codes, np.float32)
                * np.asarray(scales, np.float32)[..., None]
                ).astype(np.float16)

    def decode_rows(self, parts):
        codes, scales = parts
        return (codes.astype(jnp.float32)
                * as_f16(scales).astype(jnp.float32)[..., None]
                ).astype(jnp.float16)


class LowRankCodec(ApmCodec):
    """Rank-r factorization with int8-quantized factors.

    APM ≈ U·Vᵀ where U, V absorb √Σ from the SVD; each factor row is
    then per-row int8 quantized. Decoded rows approximately (not
    exactly) sum to 1 — consumers that rely on the rows-sum-to-1
    shortcut (the memo kernel's no-renormalization finalizer) stay
    within the documented tolerance because the truncation error is
    bounded by the discarded singular mass."""

    name = "lowrank"

    def __init__(self, apm_shape, rank=None):
        super().__init__(apm_shape)
        l = self.apm_shape[-1]
        # clamp to [1, L]: an (L, L) matrix has L singular values, so a
        # larger rank would declare arenas the SVD cannot fill
        self.rank = min(l, max(1, int(rank))) if rank else min(
            l, max(4, l // 8))

    @property
    def key(self):
        return (self.name, self.apm_shape, self.rank)

    @property
    def parts(self):
        h, l, _ = self.apm_shape
        r = self.rank
        return (PartSpec("u", (h, l, r), np.dtype(np.int8)),
                PartSpec("us", (h, l), np.dtype(np.float16)),
                PartSpec("v", (h, l, r), np.dtype(np.int8)),
                PartSpec("vs", (h, l), np.dtype(np.float16)))

    def encode(self, apms, aux=None):
        x = np.asarray(apms, np.float32)
        u, s, vt = np.linalg.svd(x)                    # batched over (B, H)
        r = self.rank
        root = np.sqrt(s[..., :r])
        uf = u[..., :, :r] * root[..., None, :]        # (..., L, r)
        vf = np.swapaxes(vt[..., :r, :], -1, -2) * root[..., None, :]
        uq, us = _quantize_rows(uf)
        vq, vs = _quantize_rows(vf)
        return uq, us, vq, vs

    def decode(self, parts):
        uq, us, vq, vs = parts
        u = np.asarray(uq, np.float32) * np.asarray(us, np.float32)[..., None]
        v = np.asarray(vq, np.float32) * np.asarray(vs, np.float32)[..., None]
        return np.einsum("...qr,...kr->...qk", u, v).astype(np.float16)

    def decode_rows(self, parts):
        uq, us, vq, vs = parts
        u = uq.astype(jnp.float32) * as_f16(us).astype(jnp.float32)[..., None]
        v = vq.astype(jnp.float32) * as_f16(vs).astype(jnp.float32)[..., None]
        return jnp.einsum("...qr,...kr->...qk", u, v).astype(jnp.float16)


# --- registry wiring (repro.memo public API v1) -------------------------
# Built-in codecs self-register; third-party codecs use
# ``repro.memo.register_codec`` with the same factory contract.
from repro.core.registry import CODECS  # noqa: E402

CODECS.register("f16",
                lambda shape, *, rank=None, dtype=np.float16, **_:
                F16Codec(shape, dtype=dtype))
CODECS.register("int8",
                lambda shape, *, rank=None, dtype=None, **_:
                Int8Codec(shape))
CODECS.register("lowrank",
                lambda shape, *, rank=None, dtype=None, **_:
                LowRankCodec(shape, rank=rank))


def get_codec(name, apm_shape, *, rank=None, dtype=np.float16) -> ApmCodec:
    """Resolve a codec key through the registry (``f16`` | ``int8`` |
    ``lowrank`` | anything registered via ``register_codec``); an
    ApmCodec instance passes through. Unknown keys raise with the
    registered choices listed."""
    if isinstance(name, ApmCodec):
        return name
    if name in ("none", None):
        name = "f16"
    return CODECS.resolve(name)(apm_shape, rank=rank, dtype=dtype)
