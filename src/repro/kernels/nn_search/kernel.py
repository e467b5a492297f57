"""Streaming L2 top-1 search over a big HBM-resident embedding DB.

Flash-attention-style streaming: the query tile (block_q × dim) stays in
VMEM while DB tiles (block_n × dim) stream HBM→VMEM; squared distances are
one MXU matmul (‖q‖² − 2·q·Dᵀ + ‖d‖²) and the running (min, argmin) lives
in VMEM scratch across the sequential N-grid dimension. This is the index
database's TPU-native search primitive (paper §5.3 uses Faiss HNSW; see
DESIGN.md §2 for why HNSW does not transfer).

``db_norms`` optionally carries precomputed per-row ‖d‖² (the DeviceIndex
caches them per mutation generation): the kernel then streams a (block_n,)
sliver instead of recomputing the reduction over every (block_n, dim) tile
for every query block — the norms are O(N) work total but the naive form
pays O(nb·N·dim) per search.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1e30


def _nn_kernel(q_ref, db_ref, *rest, block_q, block_n, n_total, has_norms):
    if has_norms:      # static: precomputed ‖d‖² rides as a sliver
        dn_ref, od_ref, oi_ref, bd_scr, bi_scr = rest
    else:
        od_ref, oi_ref, bd_scr, bi_scr = rest
        dn_ref = None
    iN = pl.program_id(1)

    @pl.when(iN == 0)
    def _init():
        bd_scr[...] = jnp.full_like(bd_scr, BIG)
        bi_scr[...] = jnp.zeros_like(bi_scr)

    q = q_ref[...].astype(jnp.float32)               # (block_q, dim)
    d = db_ref[...].astype(jnp.float32)              # (block_n, dim)
    qn = jnp.sum(q * q, axis=-1, keepdims=True)
    dn = (dn_ref[...] if has_norms                   # (1, block_n)
          else jnp.sum(d * d, axis=-1)[None, :])
    d2 = qn - 2.0 * jax.lax.dot_general(
        q, d, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) + dn
    npos = iN * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_n), 1)
    d2 = jnp.where(npos < n_total, d2, BIG)

    local_min = jnp.min(d2, axis=-1)
    local_arg = (iN * block_n + jnp.argmin(d2, axis=-1)).astype(jnp.int32)
    upd = local_min < bd_scr[...]
    bd_scr[...] = jnp.where(upd, local_min, bd_scr[...])
    bi_scr[...] = jnp.where(upd, local_arg, bi_scr[...])

    @pl.when(iN == pl.num_programs(1) - 1)
    def _fin():
        od_ref[...] = bd_scr[...][:, None]
        oi_ref[...] = bi_scr[...][:, None]


def nn_search_kernel(q, db, *, db_norms=None, block_q=128, block_n=512,
                     interpret=False):
    """q: (B, dim), db: (N, dim) → (sq_dists (B,), idx (B,)).
    ``db_norms`` (N,) f32: precomputed per-row squared norms (padded rows
    are masked by ``n_total``, so their norm values never matter)."""
    B, dim = q.shape
    N = db.shape[0]
    block_q = min(block_q, B)
    block_n = min(block_n, N)
    pad_b = (-B) % block_q
    pad_n = (-N) % block_n
    if pad_b:
        q = jnp.pad(q, ((0, pad_b), (0, 0)))
    if pad_n:
        db = jnp.pad(db, ((0, pad_n), (0, 0)))
        if db_norms is not None:
            db_norms = jnp.pad(db_norms, ((0, pad_n),))
    nb = q.shape[0] // block_q
    nN = db.shape[0] // block_n
    has_norms = db_norms is not None

    kernel = functools.partial(_nn_kernel, block_q=block_q, block_n=block_n,
                               n_total=N, has_norms=has_norms)
    in_specs = [
        pl.BlockSpec((block_q, dim), lambda ib, iN: (ib, 0)),
        pl.BlockSpec((block_n, dim), lambda ib, iN: (iN, 0)),
    ]
    operands = [q, db]
    if has_norms:
        # a (1, N) row, not (N,): a 1-D block must match XLA's T(1024)
        # vector tiling, while (1, block_n) meets the (8, 128) block rule
        # for any block_n that is a multiple of 128 or all of N
        in_specs.append(pl.BlockSpec((1, block_n), lambda ib, iN: (0, iN)))
        operands.append(db_norms.astype(jnp.float32).reshape(1, -1))
    # (B, 1) columns for the same reason: a (block_q,) block of a longer
    # 1-D output would not match its vector tiling
    od, oi = pl.pallas_call(
        kernel,
        grid=(nb, nN),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_q, 1), lambda ib, iN: (ib, 0)),
            pl.BlockSpec((block_q, 1), lambda ib, iN: (ib, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q.shape[0], 1), jnp.float32),
            jax.ShapeDtypeStruct((q.shape[0], 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.int32),
        ],
        interpret=interpret,
    )(*operands)
    return od[:B, 0], oi[:B, 0]
