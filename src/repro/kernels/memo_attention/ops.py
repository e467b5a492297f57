"""Jit'd wrappers for the fused memo-attention dispatch.

Two interchangeable implementations of one contract (q (B,S,H,dh), k/v
(B,S,Hkv,dh), db (N,H,L,L), hit_idx/hit (B,) → (B,S,H,dh)):

* ``impl="pallas"`` — the tiled kernel (kernel.py): hit-conditioned
  index maps, scalar-prefetched gather, in-VMEM int8 dequant. The
  compile target for TPU/GPU serving and the parity-test subject
  (interpret mode on CPU).
* ``impl="xla"``    — the one-formulation XLA form: full masked probs,
  a ``where(hit)`` combine against the gathered (dequantized) APM rows,
  and ONE AV matmul shared by hits and misses. Semantically identical
  to the kernel; on CPU the Pallas interpreter is ~30x slower than
  XLA's fused ops, so serving uses this form there — the same backend
  split DeviceIndex documents for ``nn_search``.

``impl=None`` resolves per backend ("xla" on CPU, "pallas" otherwise)
unless ``interpret`` was passed explicitly, which pins the Pallas path
(that is how the kernel tests keep testing the kernel).

Ragged sequence lengths are handled HERE (the kernel asserts tile
alignment): q/k/v are zero-padded up to the DB's own sequence length L
(or, past L, to the block grid), and the padded key positions are
masked through the per-sequence ``lengths`` operand. A bucket shorter
than the arena therefore pads the small q/k/v, never the (N, H, L, L)
DB: stored APMs are hard zeros past their entry's length, so the padded
tile replays the same rows. Misses never fetch DB tiles at all (the hit
flag aliases the gather index map), so no clamp of ``hit_idx`` is needed.

A float16 DB enters only as its int16 bit pattern (``codec.hbm_form``),
which is how the device arenas keep it in HBM: Mosaic loads no float16,
and converting a float16 operand here would copy the whole DB on TPU.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.memo_attention.kernel import (NEG_INF, db_to_f32,
                                                 memo_attention_bhsd)


def _pad_axis(x, axis, pad):
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _fit_db(db, target, n_tail_dims):
    """Slice or zero-pad the trailing ``n_tail_dims`` sequence dims of a
    DB part to ``target``. Stored APMs are hard zeros past their entry's
    true length (and the engine's length gate only admits exact-length
    matches), so zero padding is exact."""
    L = db.shape[-1]
    if L == target:
        return db
    if L > target:
        sl = (Ellipsis,) + (slice(0, target),) * n_tail_dims
        return db[sl]
    widths = ([(0, 0)] * (db.ndim - n_tail_dims)
              + [(0, target - L)] * n_tail_dims)
    return jnp.pad(db, widths)


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret", "has_scales", "has_lengths"))
def _memo_attention_pallas(q, k, v, db_apm, db_scales, hit_idx, hit, lengths,
                           *, causal, window, block_q, block_k, interpret,
                           has_scales, has_lengths):
    B, S, H, dh = q.shape
    T = max(S, db_apm.shape[-1])          # run at the DB's own length
    bq = min(block_q, T)
    bk = min(block_k, T)
    Sp = -(-T // math.lcm(bq, bk)) * math.lcm(bq, bk)   # ragged → pad up
    q = _pad_axis(q, 1, Sp - S)
    k = _pad_axis(k, 1, Sp - S)
    v = _pad_axis(v, 1, Sp - S)
    db_apm = _fit_db(db_apm, Sp, 2)       # pads only past the DB's length
    if has_scales:
        db_scales = _fit_db(db_scales, Sp, 1)
    if not has_lengths:        # fixed length: mask exactly the padding
        lengths = jnp.full((B,), S, jnp.int32)
    out = memo_attention_bhsd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), db_apm, hit_idx, hit, lengths=lengths,
        db_scales=db_scales if has_scales else None, causal=causal,
        window=window, block_q=bq, block_k=bk, interpret=interpret)
    return out.transpose(0, 2, 1, 3)[:, :S]


@partial(jax.jit, static_argnames=("causal", "window", "has_scales",
                                   "has_lengths"))
def _memo_attention_xla(q, k, v, db_apm, db_scales, hit_idx, hit, lengths, *,
                        causal, window, has_scales, has_lengths):
    """The kernel's math in one XLA dispatch. Numerics mirror the kernel:
    f32 compute, NEG_INF masking with explicit zeroing of fully-masked
    rows, hits consume the raw APM rows (already row-stochastic — no
    renormalization), and ONE probs·V matmul serves both paths."""
    B, S, H, dh = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qf = (q.astype(jnp.float32).transpose(0, 2, 1, 3)
          .reshape(B, Hkv, group, S, dh))
    kf = k.astype(jnp.float32).transpose(0, 2, 1, 3)
    vf = v.astype(jnp.float32).transpose(0, 2, 1, 3)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qf, kf) * dh ** -0.5
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    mask = jnp.broadcast_to(mask[None, None, None], (B, 1, 1, S, S))
    if has_lengths:
        mask = mask & (jnp.arange(S)[None, :]
                       < lengths[:, None])[:, None, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, -1, keepdims=True)
    p = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - m))
    p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    apm = db_to_f32(jnp.take(db_apm, hit_idx, axis=0))
    if has_scales:
        apm = apm * db_to_f32(jnp.take(db_scales, hit_idx, axis=0))[..., None]
    apm = _fit_db(apm, S, 2)
    p = jnp.where((hit == 1)[:, None, None, None],
                  apm, p.reshape(B, H, S, S))
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p.reshape(B, Hkv, group, S, S), vf)
    return (out.reshape(B, H, S, dh).transpose(0, 2, 1, 3).astype(q.dtype))


def memo_attention(q, k, v, db_apm, hit_idx, hit, *, db_scales=None,
                   lengths=None, causal=True, window=None, block_q=128,
                   block_k=128, interpret=None, impl=None):
    """Model layout: q (B,S,H,dh), k/v (B,S,Hkv,dh), db_apm (N,H,L,L)
    (int16 = float16 bit patterns; a float16 array is refused), hit_idx/hit
    (B,). With ``db_scales`` (N,H,L) the DB is int8-quantized (the
    ``int8`` APM codec) and tiles dequantize in VMEM — the fused-dequant
    gather (DESIGN.md §2.6). ``lengths`` (B,) serves
    variable-length batches: padded key positions are masked out of the
    miss path per sequence (hit APMs are already zero past their length).

    ``impl`` picks the implementation ("pallas" | "xla", see module
    docstring); None auto-resolves by backend, except that an explicit
    ``interpret`` pins the Pallas path. ``interpret=None`` resolves per
    backend: Pallas interpreter on CPU, compiled on TPU."""
    if db_apm.dtype == jnp.float16:
        raise TypeError("memo_attention takes a float16 DB as its int16 bit "
                        "pattern (repro.core.codec.hbm_form), as the device "
                        "arenas hold it")
    if impl is None:
        impl = ("pallas" if interpret is not None
                else ("xla" if jax.default_backend() == "cpu" else "pallas"))
    has_scales = db_scales is not None
    has_lengths = lengths is not None
    if db_scales is None:      # static placeholder keeps the jit signature
        db_scales = jnp.zeros((1, 1, 1), jnp.float16)
    if lengths is None:
        lengths = jnp.zeros((q.shape[0],), jnp.int32)
    else:
        lengths = jnp.asarray(lengths, jnp.int32)
    hit_idx = jnp.asarray(hit_idx, jnp.int32)
    hit = jnp.asarray(hit, jnp.int32)
    if impl == "xla":
        return _memo_attention_xla(q, k, v, db_apm, db_scales, hit_idx, hit,
                                   lengths, causal=causal, window=window,
                                   has_scales=has_scales,
                                   has_lengths=has_lengths)
    if impl != "pallas":
        raise ValueError(f"impl must be None|'pallas'|'xla': {impl!r}")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return _memo_attention_pallas(q, k, v, db_apm, db_scales, hit_idx, hit,
                                  lengths, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret, has_scales=has_scales,
                                  has_lengths=has_lengths)
