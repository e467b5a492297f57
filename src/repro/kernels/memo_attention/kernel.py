"""Fused memoized attention (the paper's hot path, TPU-native).

ONE Pallas dispatch serves the whole mixed hit/miss batch. The grid is
(batch, head, q-tile, k-tile) with three scalar-prefetch operands — the
per-sequence gather index, hit flag and true length — and the hit flag
drives the BlockSpec *index maps*, not just ``pl.when``, so each
program only streams the tiles its path actually consumes:

* hit  — the APM tile is gathered straight out of the HBM-resident
  attention database by ``db_apm[hit_idx[b], h, iq, ik]`` in the
  BlockSpec index_map and consumed by the APM·V matmul in VMEM. The
  gathered APM never materializes in HBM — this is the TPU analogue of
  the paper's mmap zero-copy gathering (DESIGN.md §2). QKᵀ and softmax
  are skipped via ``pl.when`` AND the Q/K index maps alias to block
  (0, 0, 0, 0): Pallas skips a re-fetch when consecutive grid steps map
  to the same block, so a hit program re-uses whatever Q/K tile is
  already resident instead of streaming S·d bytes of keys it would
  ignore through every k-iteration. V still streams — APM·V consumes
  every V tile.
* miss — inline flash attention (online softmax). The APM (and int8
  scale-sliver) index maps alias to block 0 for misses, so a miss moves
  at most ONE boundary DB tile instead of speculatively streaming entry
  0's full tile row per program (the previous design clamped
  ``hit_idx`` to 0 in ops.py and paid that fetch on every miss).

Variable length rides the same dispatch: ``lengths`` (B,) bounds the
miss path's key mask per sequence. The hit path needs no mask — stored
APM rows/cols past an entry's length are hard zeros, and the engine's
length gate only admits hits whose entry length equals the query's.

Quantized DB (DESIGN.md §2.6): with ``db_scales`` the database holds
int8 codes + per-row f16 scales (the ``int8`` APM codec); the kernel
gathers the int8 tile (half the HBM→VMEM bytes) plus its (block_q,)
scale sliver and dequantizes IN VMEM immediately before the APM·V
matmul — the f16 APM never exists anywhere, on either memory level.
The scales of the B gathered entries are taken out of the arena before
the dispatch as a (B, H, 1, S) float32 operand: Mosaic refuses a
``(1, 1, block_q)`` block of the (N, H, S) arena and loads no float16.

Float16 DB: Mosaic on TPU v5e loads no float16 either, so an f16
arena arrives as its int16 bit pattern (``DeviceDB`` keeps it that way
in HBM, same bytes) and each gathered tile is decoded to float32 in
VMEM with integer ops (``f16_bits_to_f32``) — exact, so hits replay the
stored APM bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def f16_bits_to_f32(bits):
    """float16 bit patterns (int16) → the float32 values they encode,
    with integer ops only (exact, subnormals and inf/nan included)."""
    x = bits.astype(jnp.int32) & 0xFFFF
    sign = (x >> 15) << 31
    e = (x >> 10) & 0x1F
    m = x & 0x3FF
    e32 = jnp.where(e == 0x1F, 0xFF, e + 112)        # rebias 15 → 127
    normal = jax.lax.bitcast_convert_type(sign | (e32 << 23) | (m << 13),
                                          jnp.float32)
    sub = m.astype(jnp.float32) * 2.0 ** -24         # e == 0: m · 2⁻²⁴
    return jnp.where(e == 0, jnp.where(sign != 0, -sub, sub), normal)


def db_to_f32(x):
    """A slice of a DB part as float32: an int16 part holds float16 bit
    patterns (the arena's HBM form) and decodes exactly; int8 codes and
    float parts convert."""
    if x.dtype == jnp.int16:
        return f16_bits_to_f32(x)
    return x.astype(jnp.float32)


def _memo_kernel(hit_idx_ref, hit_ref, len_ref, q_ref, k_ref, v_ref,
                 apm_ref, *rest, scale, causal, window, block_q, block_k,
                 quantized=False):
    if quantized:      # static: the int8 variant carries a scale sliver
        sc_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
        sc_ref = None
    b = pl.program_id(0)
    iq, ik = pl.program_id(2), pl.program_id(3)
    hit = hit_ref[b] == 1

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    v = v_ref[0, 0].astype(jnp.float32)

    @pl.when(hit)
    def _memo_path():
        apm = db_to_f32(apm_ref[0, 0])                   # (block_q, block_k)
        if quantized:
            # fused dequant: int8 codes × per-row scale, in VMEM, right
            # before the APM·V matmul
            apm = apm * sc_ref[0, 0, 0][:, None]
        acc_scr[...] += jax.lax.dot_general(
            apm, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_not(hit))
    def _flash_path():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = kpos < len_ref[b]        # per-sequence true length (varlen)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - m_new[:, None]))
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == pl.num_programs(3) - 1)
    def _fin():
        # hit: APM rows already sum to 1 — no normalization
        denom = jnp.where(hit, 1.0, jnp.maximum(l_scr[...], 1e-30))
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def memo_attention_bhsd(q, k, v, db_apm, hit_idx, hit, *, lengths=None,
                        db_scales=None, causal=True, window=None,
                        block_q=128, block_k=128, interpret=False):
    """q: (B, H, S, d); k, v: (B, Hkv, S, d); db_apm: (N, H, S, S) —
    the device-resident attention DB (an int16 DB holds float16 bit
    patterns); hit_idx, hit: (B,) int32; ``lengths`` (B,) int32 bounds
    the miss path's key mask per sequence (None → every sequence is
    full-length S).

    ``db_scales`` (N, H, S) f16 (or its int16 bits) switches the DB to
    the int8 codec: ``db_apm`` holds int8 codes and each gathered tile is
    dequantized in VMEM against its per-row scale sliver (fused-dequant
    gather).

    The hit flag conditions every index map (see module docstring): hit
    programs alias Q/K to one resident tile and stream only APM tiles;
    miss programs alias the APM (and scale sliver) and stream only Q/K/V.
    """
    B, H, S, d = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, \
        "ragged S is padded by ops.memo_attention"
    assert db_apm.shape[-2] == S and db_apm.shape[-1] == S, \
        "DB tiles must cover the (padded) sequence: pad/slice in ops"
    nq, nk = S // block_q, S // block_k
    quantized = db_scales is not None
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)

    kernel = functools.partial(
        _memo_kernel, scale=d ** -0.5, causal=causal, window=window,
        block_q=block_q, block_k=block_k, quantized=quantized)

    # Index maps — the aliasing core. A Pallas program whose index map
    # resolves to the same block as the previous grid step re-uses the
    # resident tile; a CONSTANT block for the never-read operand of a
    # path therefore reduces that operand's HBM traffic to (at most) one
    # fetch per hit↔miss boundary in grid order, instead of one per
    # program.
    def q_map(b, h, iq, ik, hit_idx, hit, lens):
        m = hit[b] == 1          # hit never reads Q: alias to block 0
        return (jnp.where(m, 0, b), jnp.where(m, 0, h),
                jnp.where(m, 0, iq), 0)

    def k_map(b, h, iq, ik, hit_idx, hit, lens):
        m = hit[b] == 1          # hit never reads K: alias to block 0
        return (jnp.where(m, 0, b), jnp.where(m, 0, h // group),
                jnp.where(m, 0, ik), 0)

    def v_map(b, h, iq, ik, hit_idx, hit, lens):
        return (b, h // group, ik, 0)      # both paths consume V

    def apm_map(b, h, iq, ik, hit_idx, hit, lens):
        m = hit[b] == 1          # miss never reads the APM: alias to 0
        return (jnp.where(m, hit_idx[b], 0), jnp.where(m, h, 0),
                jnp.where(m, iq, 0), jnp.where(m, ik, 0))

    def sc_map(b, h, iq, ik, hit_idx, hit, lens):
        m = hit[b] == 1          # quantized misses move zero scale bytes
        return (jnp.where(m, b, 0), jnp.where(m, h, 0), 0,
                jnp.where(m, iq, 0))

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), q_map),
        pl.BlockSpec((1, 1, block_k, d), k_map),
        pl.BlockSpec((1, 1, block_k, d), v_map),
        # the DB gather: data-dependent entry via scalar prefetch
        pl.BlockSpec((1, 1, block_q, block_k), apm_map),
    ]
    operands = [q, k, v, db_apm]
    if quantized:
        # the B gathered entries' scales, one (1, block_q) row per tile
        sc = db_to_f32(jnp.take(db_scales, hit_idx, axis=0, mode="clip"))
        in_specs.append(pl.BlockSpec((1, 1, 1, block_q), sc_map))
        operands.append(sc[:, :, None, :])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, h, iq, ik, *_: (b, h, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(hit_idx.astype(jnp.int32), hit.astype(jnp.int32),
      lengths.astype(jnp.int32), *operands)
