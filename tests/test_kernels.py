"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties.

All kernels run in interpret mode on CPU (TPU is the compile target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.codec import hbm_form
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.memo_attention.ops import memo_attention
from repro.kernels.memo_attention.ref import memo_attention_ref
from repro.kernels.nn_search.ops import nn_search
from repro.kernels.nn_search.ref import nn_search_ref


def _qkv(key, B, S, H, Hkv, dh, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, dh), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, dh), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, dh), dtype)
    return q, k, v


def _ref_bshd(q, k, v, **kw):
    B, S, H, dh = q.shape
    Hkv = k.shape[2]
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, dh)
    kt = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, dh)
    vt = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, dh)
    return attention_ref(qt, kt, vt, **kw).reshape(B, H, S, dh).transpose(
        0, 2, 1, 3)


# ------------------------------------------------------------ flash_attention

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("S,H,Hkv,dh,bq,bk", [
    (64, 4, 2, 32, 32, 16),
    (48, 2, 2, 64, 16, 16),     # S not a multiple of bigger blocks
    (33, 4, 1, 16, 16, 16),     # ragged S -> padding path
    (128, 8, 8, 64, 128, 128),  # MXU-aligned
])
def test_flash_matches_ref(dtype, tol, S, H, Hkv, dh, bq, bk):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, S, H, Hkv, dh, dtype)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True)
    ref = _ref_bshd(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [None, 8, 16])
def test_flash_masks(causal, window):
    q, k, v = _qkv(jax.random.PRNGKey(1), 1, 64, 4, 2, 32, jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=32, block_k=16, interpret=True)
    ref = _ref_bshd(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@given(S=st.integers(8, 80), H=st.sampled_from([1, 2, 4]),
       g=st.sampled_from([1, 2]), dh=st.sampled_from([16, 32]),
       seed=st.integers(0, 100))
@settings(max_examples=12, deadline=None)
def test_flash_property_rowsums(S, H, g, dh, seed):
    """Output rows are convex combinations of V rows: each output lies in
    [-max|v|, max|v|] per dim and matches the oracle."""
    Hkv = max(1, H // g)
    H = Hkv * g
    q, k, v = _qkv(jax.random.PRNGKey(seed), 1, S, H, Hkv, dh, jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    ref = _ref_bshd(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
    vmax = float(jnp.max(jnp.abs(v))) + 1e-5
    assert float(jnp.max(jnp.abs(out))) <= vmax


# ------------------------------------------------------------ memo_attention

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
def test_memo_matches_ref(dtype, tol):
    B, S, H, Hkv, dh, N = 3, 64, 4, 2, 32, 5
    q, k, v = _qkv(jax.random.PRNGKey(2), B, S, H, Hkv, dh, dtype)
    db = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(3), (N, H, S, S)), -1
    ).astype(dtype)
    hit_idx = jnp.array([4, 0, 2])
    hit = jnp.array([1, 0, 1])
    out = memo_attention(q, k, v, db, hit_idx, hit, causal=True,
                         block_q=32, block_k=32, interpret=True)
    ref = memo_attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), db, hit_idx, hit,
                             causal=True).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_memo_all_hit_equals_apm_matmul():
    """With every sequence hitting, the kernel must reproduce APM·V with no
    dependence on Q/K at all."""
    B, S, H, dh, N = 2, 32, 2, 16, 4
    q, k, v = _qkv(jax.random.PRNGKey(4), B, S, H, H, dh, jnp.float32)
    db = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(5),
                                          (N, H, S, S)), -1)
    hit_idx = jnp.array([1, 3])
    hit = jnp.ones((B,), jnp.int32)
    out = memo_attention(q, k, v, db, hit_idx, hit, block_q=16, block_k=16,
                         interpret=True)
    out_q = memo_attention(q * 100, k * 100, v, db, hit_idx, hit,
                           block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_q),
                               rtol=1e-6, atol=1e-6)
    apm = db[hit_idx]                      # (B,H,S,S)
    expect = jnp.einsum("bhqs,bshd->bqhd", apm, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_memo_no_hit_equals_flash():
    B, S, H, dh = 2, 64, 2, 32
    q, k, v = _qkv(jax.random.PRNGKey(6), B, S, H, H, dh, jnp.float32)
    db = jnp.zeros((1, H, S, S))
    out = memo_attention(q, k, v, db, jnp.zeros((B,), jnp.int32),
                         jnp.zeros((B,), jnp.int32), causal=True,
                         block_q=32, block_k=32, interpret=True)
    ref = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("H,Hkv", [(4, 1), (8, 2)])
def test_memo_matches_ref_gqa_groups(H, Hkv):
    """GQA with group > 2: the hit path's APM·V must consume the RIGHT
    shared K/V head per query head, on both implementations."""
    B, S, dh, N = 3, 64, 16, 4
    q, k, v = _qkv(jax.random.PRNGKey(10), B, S, H, Hkv, dh, jnp.float32)
    db = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(11), (N, H, S, S)), -1)
    hit_idx = jnp.array([2, 0, 3])
    hit = jnp.array([1, 0, 1])
    ref = memo_attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), db, hit_idx, hit,
                             causal=True).transpose(0, 2, 1, 3)
    for impl in ("pallas", "xla"):
        out = memo_attention(q, k, v, db, hit_idx, hit, causal=True,
                             block_q=32, block_k=32,
                             interpret=True if impl == "pallas" else None,
                             impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=impl)


@pytest.mark.parametrize("causal,window", [(True, 16), (False, 8),
                                           (True, None), (False, None)])
def test_memo_masks_causal_sliding_window(causal, window):
    """Mask composition on the miss path (causal × sliding window) with a
    mixed batch: misses must match the masked oracle, hits ignore masks."""
    B, S, H, dh, N = 4, 64, 2, 16, 3
    q, k, v = _qkv(jax.random.PRNGKey(12), B, S, H, H, dh, jnp.float32)
    db = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(13), (N, H, S, S)), -1)
    hit_idx = jnp.array([1, 0, 2, 0])
    hit = jnp.array([0, 1, 1, 0])
    ref = memo_attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), db, hit_idx, hit,
                             causal=causal,
                             window=window).transpose(0, 2, 1, 3)
    for impl in ("pallas", "xla"):
        out = memo_attention(q, k, v, db, hit_idx, hit, causal=causal,
                             window=window, block_q=16, block_k=16,
                             interpret=True if impl == "pallas" else None,
                             impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=impl)


def test_memo_int8_scale_boundaries():
    """int8 fused dequant at the codec's scale boundaries: rows with a
    max-magnitude element (code ±127), near-zero rows riding the 1e-4
    scale floor, and mixed hit/miss — vs the dequantize-then-f32 oracle."""
    from repro.core.codec import _quantize_rows
    from repro.kernels.memo_attention.ref import memo_attention_q8_ref
    B, S, H, dh, N = 3, 32, 2, 16, 4
    q, k, v = _qkv(jax.random.PRNGKey(14), B, S, H, H, dh, jnp.float32)
    apm = np.array(jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(15), (N, H, S, S)), -1))
    apm[0, :, 0, 0] = 1.0          # a full-magnitude element → code 127
    apm[1, :, 1, :] = 0.0          # all-zero row → scale floor path
    codes, scales = _quantize_rows(apm)
    # hits gather entries other than their own batch row, so a scale
    # operand indexed by the wrong one shows
    hit_idx = jnp.array([1, 0, 3])
    hit = jnp.array([1, 1, 0])
    ref = memo_attention_q8_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), jnp.asarray(codes), jnp.asarray(scales),
        hit_idx, hit, causal=True).transpose(0, 2, 1, 3)
    # the scales as float16 and in their HBM form (int16 bit patterns,
    # gathered and decoded before the dispatch)
    for form, sc in (("f16", scales), ("hbm", hbm_form(scales))):
        for impl in ("pallas", "xla"):
            out = memo_attention(q, k, v, jnp.asarray(codes), hit_idx, hit,
                                 db_scales=jnp.asarray(sc), causal=True,
                                 block_q=16, block_k=16,
                                 interpret=True if impl == "pallas" else None,
                                 impl=impl)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=f"{impl} {form} scales")


def test_memo_ragged_seq_padding():
    """S=96 with 64-blocks exercises the ops-level padding (the kernel
    itself asserts tile alignment); parity vs the unpadded oracle."""
    B, S, H, dh, N = 2, 96, 2, 16, 3
    q, k, v = _qkv(jax.random.PRNGKey(16), B, S, H, H, dh, jnp.float32)
    db = jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(17), (N, H, S, S)), -1)
    hit_idx = jnp.array([1, 0])
    hit = jnp.array([1, 0])
    ref = memo_attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3), db, hit_idx, hit,
                             causal=True).transpose(0, 2, 1, 3)
    for impl in ("pallas", "xla"):
        out = memo_attention(q, k, v, db, hit_idx, hit, causal=True,
                             block_q=64, block_k=64,
                             interpret=True if impl == "pallas" else None,
                             impl=impl)
        assert out.shape == q.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=impl)


def test_memo_xla_impl_matches_pallas():
    """The one-matmul XLA form and the tiled kernel are one contract:
    identical outputs on a mixed batch (f32 DB, an f16 DB in its HBM
    form, and int8 DB with float16 or int16-bit scales)."""
    from repro.core.codec import _quantize_rows
    B, S, H, Hkv, dh, N = 4, 48, 4, 2, 16, 5
    q, k, v = _qkv(jax.random.PRNGKey(18), B, S, H, Hkv, dh, jnp.float32)
    apm = np.asarray(jax.nn.softmax(
        jax.random.normal(jax.random.PRNGKey(19), (N, H, S, S)), -1))
    hit_idx = jnp.array([3, 4, 0, 1])     # hits gather other rows than b
    hit = jnp.array([1, 0, 1, 0])
    a = memo_attention(q, k, v, jnp.asarray(apm), hit_idx, hit, causal=True,
                       block_q=16, block_k=16, interpret=True, impl="pallas")
    b = memo_attention(q, k, v, jnp.asarray(apm), hit_idx, hit, causal=True,
                       impl="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)
    # f16 arena as the device tier holds it (int16 bits): the kernel's
    # in-VMEM decode and the XLA form both replay the f16 values exactly
    apm16 = apm.astype(np.float16)
    ref = memo_attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                             v.transpose(0, 2, 1, 3),
                             jnp.asarray(apm16, jnp.float32), hit_idx, hit,
                             causal=True).transpose(0, 2, 1, 3)
    for impl in ("pallas", "xla"):
        out = memo_attention(q, k, v, jnp.asarray(hbm_form(apm16)), hit_idx,
                             hit, causal=True, block_q=16, block_k=16,
                             interpret=True if impl == "pallas" else None,
                             impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=impl)
    codes, scales = _quantize_rows(apm)
    for sc in (scales, hbm_form(scales)):
        aq = memo_attention(q, k, v, jnp.asarray(codes), hit_idx, hit,
                            db_scales=jnp.asarray(sc), causal=True,
                            block_q=16, block_k=16, interpret=True,
                            impl="pallas")
        bq = memo_attention(q, k, v, jnp.asarray(codes), hit_idx, hit,
                            db_scales=jnp.asarray(sc), causal=True,
                            impl="xla")
        np.testing.assert_allclose(np.asarray(aq), np.asarray(bq),
                                   rtol=2e-5, atol=2e-5, err_msg=str(sc.dtype))


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_memo_refuses_float16_db(impl):
    """A float16 DB has one way in, its int16 bit pattern: converting it
    inside the wrapper would copy the whole arena on every call."""
    q, k, v = _qkv(jax.random.PRNGKey(21), 1, 16, 2, 2, 16, jnp.float32)
    db = jnp.zeros((2, 2, 16, 16), jnp.float16)
    zeros = jnp.zeros((1,), jnp.int32)
    with pytest.raises(TypeError, match="hbm_form"):
        memo_attention(q, k, v, db, zeros, zeros, impl=impl,
                       interpret=True if impl == "pallas" else None)


def test_memo_varlen_lengths():
    """Variable-length batches through the ``lengths`` operand: each
    sequence's valid rows match causal flash attention run on its own
    sliced prefix (causal masking makes the slice exact)."""
    B, S, H, dh = 3, 64, 2, 16
    q, k, v = _qkv(jax.random.PRNGKey(20), B, S, H, H, dh, jnp.float32)
    lengths = jnp.array([64, 40, 17])
    db = jnp.zeros((1, H, S, S))
    zeros = jnp.zeros((B,), jnp.int32)
    for impl in ("pallas", "xla"):
        out = memo_attention(q, k, v, db, zeros, zeros, lengths=lengths,
                             causal=True, block_q=16, block_k=16,
                             interpret=True if impl == "pallas" else None,
                             impl=impl)
        for bi, L in enumerate([64, 40, 17]):
            ref = flash_attention(q[bi:bi + 1, :L], k[bi:bi + 1, :L],
                                  v[bi:bi + 1, :L], causal=True,
                                  block_q=16, block_k=16, interpret=True)
            np.testing.assert_allclose(np.asarray(out[bi, :L]),
                                       np.asarray(ref[0]),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=f"{impl} b={bi}")


# ---------------------------------------------------------------- nn_search

@pytest.mark.parametrize("B,N,dim,bq,bn", [
    (17, 1000, 128, 8, 256),
    (4, 64, 32, 4, 16),
    (128, 4096, 128, 128, 512),
])
def test_nn_search_matches_ref(B, N, dim, bq, bn):
    q = jax.random.normal(jax.random.PRNGKey(7), (B, dim))
    db = jax.random.normal(jax.random.PRNGKey(8), (N, dim))
    d, i = nn_search(q, db, block_q=bq, block_n=bn, interpret=True)
    dr, ir = nn_search_ref(q, db)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(d), np.asarray(dr),
                               rtol=1e-4, atol=1e-4)


@given(B=st.integers(1, 9), N=st.integers(2, 200),
       seed=st.integers(0, 1000))
@settings(max_examples=15, deadline=None)
def test_nn_search_property(B, N, seed):
    """Returned index is a true argmin: no DB entry is closer."""
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (B, 16))
    db = jax.random.normal(jax.random.PRNGKey(seed + 1), (N, 16))
    d, i = nn_search(q, db, block_q=4, block_n=32, interpret=True)
    d2_all = np.asarray(
        jnp.sum(jnp.square(q[:, None] - db[None]), -1))
    assert (np.asarray(d) <= d2_all.min(1) + 1e-4).all()
    np.testing.assert_array_equal(np.asarray(i), d2_all.argmin(1))


@pytest.mark.parametrize("B,N,dim,bq,bn", [
    (3, 250, 16, 16, 64),    # B < block_q AND N % block_n != 0 (tail mask)
    (5, 999, 32, 8, 512),    # padded tail close to a full extra block
    (2, 33, 16, 16, 32),     # single ragged DB block
])
def test_nn_search_parity_vs_exact_index(B, N, dim, bq, bn):
    """The serving-tier kernel agrees with the host-tier ExactIndex
    oracle: same argmin, and sqrt(sq_dists) == ExactIndex L2 — including
    the N-padding tail (n_total masking must keep padded DB rows out of
    the argmin) and B < block_q (query padding trimmed)."""
    from repro.core.index import ExactIndex
    rng = np.random.default_rng(B * 1000 + N)
    db = rng.normal(size=(N, dim)).astype(np.float32)
    q = rng.normal(size=(B, dim)).astype(np.float32)
    exact = ExactIndex(dim)
    exact.add(db)
    dist_ref, idx_ref = exact.search(q, 1)
    d2, idx = nn_search(jnp.asarray(q), jnp.asarray(db), block_q=bq,
                        block_n=bn, interpret=True)
    assert d2.shape == (B,) and idx.shape == (B,)
    np.testing.assert_array_equal(np.asarray(idx), idx_ref[:, 0])
    np.testing.assert_allclose(np.sqrt(np.maximum(np.asarray(d2), 0.0)),
                               dist_ref[:, 0], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,N,dim,bq,bn", [
    (3, 250, 16, 16, 64),    # B < block_q AND N % block_n != 0
    (7, 130, 32, 8, 64),     # ragged DB tail with a norms sliver
])
def test_nn_search_with_db_norms(B, N, dim, bq, bn):
    """The precomputed-norms sliver changes HBM traffic, not results:
    bitwise-equal argmin and matching distances vs the norm-free kernel,
    including the padded DB tail (padded norm entries are masked by
    n_total)."""
    rng = np.random.default_rng(B * 77 + N)
    q = jnp.asarray(rng.normal(size=(B, dim)).astype(np.float32))
    db = jnp.asarray(rng.normal(size=(N, dim)).astype(np.float32))
    norms = jnp.sum(db * db, axis=-1)
    d0, i0 = nn_search(q, db, block_q=bq, block_n=bn, interpret=True)
    d1, i1 = nn_search(q, db, db_norms=norms, block_q=bq, block_n=bn,
                       interpret=True)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1),
                               rtol=1e-5, atol=1e-5)
    dr, ir = nn_search_ref(q, db)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(ir))


def test_nn_search_exact_self_query():
    """Querying with DB rows returns identity with ~zero distance."""
    db = jax.random.normal(jax.random.PRNGKey(9), (50, 64))
    d, i = nn_search(db[:10], db, block_q=8, block_n=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(i), np.arange(10))
    assert float(jnp.max(d)) < 1e-3


# ------------------------------------------------------------- rwkv6 wkv

def _wkv_inputs(key, B, S, nh, N, decay_mean=-4.0):
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (B, S, nh, N))
    k = jax.random.normal(ks[1], (B, S, nh, N))
    v = jax.random.normal(ks[2], (B, S, nh, N))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, S, nh, N))
                         + decay_mean))
    u = jax.random.normal(ks[4], (nh, N)) * 0.1
    return r, k, v, w, u


def _wkv_ref_model_layout(r, k, v, w, u):
    from repro.kernels.rwkv6.ref import wkv6_ref
    B, S, nh, N = r.shape
    def to_bh(t):
        return t.transpose(0, 2, 1, 3).reshape(B * nh, S, N)
    ub = jnp.broadcast_to(u[None], (B, nh, N)).reshape(B * nh, N)
    o = wkv6_ref(to_bh(r), to_bh(k), to_bh(v), to_bh(w), ub)
    return o.reshape(B, nh, S, N).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("S,chunk", [(48, 16), (41, 16), (64, 32), (8, 8)])
def test_wkv6_chunked_matches_scan(S, chunk):
    from repro.kernels.rwkv6.ops import wkv6_chunked
    r, k, v, w, u = _wkv_inputs(jax.random.PRNGKey(0), 2, S, 3, 16)
    o = wkv6_chunked(r, k, v, w, u, chunk=chunk, interpret=True)
    ref = _wkv_ref_model_layout(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


@given(seed=st.integers(0, 500), decay=st.floats(-6.0, -1.0))
@settings(max_examples=8, deadline=None)
def test_wkv6_chunked_property(seed, decay):
    """Chunk boundaries are invisible: chunked == sequential for any
    realistic data-dependent decay strength."""
    from repro.kernels.rwkv6.ops import wkv6_chunked
    r, k, v, w, u = _wkv_inputs(jax.random.PRNGKey(seed), 1, 32, 2, 8,
                                decay_mean=decay)
    o = wkv6_chunked(r, k, v, w, u, chunk=8, interpret=True)
    ref = _wkv_ref_model_layout(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=5e-4, atol=5e-4)


def test_f16_bits_decode_is_exact():
    """The in-kernel float16 decode (int16 bit patterns → float32 by
    integer ops) agrees with a float16 → float32 cast on every one of
    the 65536 patterns: normals, subnormals, signed zeros, inf, nan."""
    from repro.kernels.memo_attention.kernel import f16_bits_to_f32
    bits = np.arange(-32768, 32768, dtype=np.int32).astype(np.int16)
    want = bits.view(np.float16).astype(np.float32)
    got = np.asarray(jax.jit(f16_bits_to_f32)(jnp.asarray(bits)))
    np.testing.assert_array_equal(got.view(np.int32)[~np.isnan(want)],
                                  want.view(np.int32)[~np.isnan(want)])
    assert np.isnan(got[np.isnan(want)]).all()
