"""AttMemo core: similarity metric, embedder, indexes, database, engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.database import AttentionDB, DeviceDB
from repro.core.embedding import Embedder, train_embedder
from repro.core.index import ExactIndex, IVFIndex, recall_at_1
from repro.core.similarity import (
    memo_rate, pairwise_similarity, similarity_score)


# ------------------------------------------------------------- similarity

def _rand_apm(key, shape):
    return jax.nn.softmax(jax.random.normal(key, shape), -1)


def test_similarity_identity_and_range():
    a = _rand_apm(jax.random.PRNGKey(0), (4, 16, 16))
    assert float(similarity_score(a, a)) == pytest.approx(1.0, abs=1e-6)
    b = _rand_apm(jax.random.PRNGKey(1), (4, 16, 16))
    s = float(similarity_score(a, b))
    assert 0.0 <= s <= 1.0


@given(seed=st.integers(0, 1000), L=st.integers(2, 24))
@settings(max_examples=20, deadline=None)
def test_similarity_properties(seed, L):
    """Symmetry, [0,1] bounds, and SC(A,A)=1 for arbitrary APMs (Eq. 1)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    a, b = _rand_apm(k1, (L, L)), _rand_apm(k2, (L, L))
    sab, sba = float(similarity_score(a, b)), float(similarity_score(b, a))
    assert sab == pytest.approx(sba, abs=1e-6)
    assert -1e-6 <= sab <= 1.0 + 1e-6
    assert float(similarity_score(a, a)) == pytest.approx(1.0, abs=1e-6)


def test_similarity_disjoint_is_zero():
    """Disjoint one-hot rows -> TV distance 1 -> similarity 0."""
    L = 8
    a = jnp.eye(L)
    b = jnp.roll(jnp.eye(L), 1, axis=1)
    assert float(similarity_score(a, b)) == pytest.approx(0.0, abs=1e-6)


def test_batched_similarity_shape():
    a = _rand_apm(jax.random.PRNGKey(2), (3, 2, 8, 8))
    b = _rand_apm(jax.random.PRNGKey(3), (5, 2, 8, 8))
    m = pairwise_similarity(a, b)
    assert m.shape == (3, 5)
    s00 = float(similarity_score(a[0], b[0]))
    assert float(m[0, 0]) == pytest.approx(s00, abs=1e-5)


def test_memo_rate():
    assert memo_rate(42, 10, 12) == pytest.approx(42 / 120)


# -------------------------------------------------------------- embedding

def test_embedder_shapes_and_training_reduces_loss():
    key = jax.random.PRNGKey(0)
    L, H, n = 32, 64, 96
    hiddens = jax.random.normal(key, (n, L, H))
    apms = _rand_apm(jax.random.PRNGKey(1), (n, 2, L, L))
    emb = Embedder.init(key, L, H, pool=8)
    out = emb(hiddens[:5])
    assert out.shape == (5, 128)
    emb2, hist = train_embedder(jax.random.PRNGKey(2), emb, hiddens, apms,
                                steps=60, pair_batch=32)
    assert hist[-1] < hist[0] * 0.8, (hist[0], hist[-1])


# ------------------------------------------------------------------ index

def test_exact_index_topk():
    idx = ExactIndex(16)
    db = np.random.default_rng(0).normal(size=(100, 16)).astype(np.float32)
    idx.add(db)
    d, i = idx.search(db[:7], k=3)
    assert i.shape == (7, 3)
    np.testing.assert_array_equal(i[:, 0], np.arange(7))
    assert (d[:, 0] <= d[:, 1]).all() and (d[:, 1] <= d[:, 2]).all()


def test_ivf_recall_reasonable():
    rng = np.random.default_rng(1)
    # clustered data (ivf's favourable + realistic regime)
    centers = rng.normal(size=(8, 32)) * 5
    db = (centers[rng.integers(0, 8, 600)]
          + rng.normal(size=(600, 32))).astype(np.float32)
    exact = ExactIndex(32)
    exact.add(db)
    ivf = IVFIndex(32, n_lists=8, nprobe=3)
    ivf.add(db)
    q = (centers[rng.integers(0, 8, 50)]
         + rng.normal(size=(50, 32))).astype(np.float32)
    assert recall_at_1(ivf, exact, q) >= 0.9


# --------------------------------------------------------------- database

def test_attention_db_roundtrip_and_growth():
    db = AttentionDB((2, 8, 8), capacity=4)
    apms = np.random.default_rng(0).random((6, 2, 8, 8)).astype(np.float16)
    idx = db.add(apms)                       # forces growth past capacity
    np.testing.assert_array_equal(idx, np.arange(6))
    got = db.get([3, 1, 3])
    np.testing.assert_array_equal(got[0], apms[3])
    np.testing.assert_array_equal(got[1], apms[1])
    assert db.reuse_counts[3] == 2 and db.reuse_counts[1] == 1
    hist = db.reuse_histogram()
    assert hist.sum() == 6


def test_attention_db_naive_matches_arena_gather():
    db = AttentionDB((1, 4, 4), capacity=8)
    apms = np.random.default_rng(2).random((8, 1, 4, 4)).astype(np.float16)
    db.add(apms)
    ids = [5, 0, 5, 7]
    np.testing.assert_array_equal(db.get(ids, count_reuse=False),
                                  db.get_naive(ids))


def test_device_db_gather():
    apms = jnp.asarray(np.random.default_rng(3).random((5, 2, 4, 4)),
                       jnp.float32)
    ddb = DeviceDB(apms)
    out = ddb.gather(jnp.array([4, 0]))
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(apms[4]))


# ----------------------------------------------------------------- engine

@pytest.fixture(scope="module")
def tiny_engine():
    from repro.configs import get_reduced
    from repro.core.engine import MemoEngine
    from repro.memo import MemoSpec
    from repro.data import TemplateCorpus
    from repro.models import build_model

    cfg = get_reduced("bert_base").replace(n_classes=4, n_layers=2,
                                           d_model=128, d_ff=256, n_heads=4)
    m = build_model(cfg, layer_loop="unroll")
    params = m.init(jax.random.PRNGKey(0))
    corpus = TemplateCorpus(vocab=cfg.vocab, seq_len=32, n_templates=6,
                            slot_fraction=0.2)
    eng = MemoEngine(m, params, MemoSpec.flat(threshold=0.6, embed_steps=40))
    batches = [{"tokens": jnp.asarray(corpus.sample(16)[0])}
               for _ in range(3)]
    eng.build(jax.random.PRNGKey(1), batches)
    return eng, corpus


def test_engine_build_populates(tiny_engine):
    eng, _ = tiny_engine
    assert len(eng.db) == 3 * 16 * 2          # batches × B × layers
    assert len(eng.index) == len(eng.db)


def test_engine_select_vs_no_memo(tiny_engine):
    eng, corpus = tiny_engine
    toks = jnp.asarray(corpus.sample(8)[0])
    logits_on, st = eng.infer({"tokens": toks})
    logits_off, _ = eng.infer({"tokens": toks}, use_memo=False)
    assert logits_on.shape == logits_off.shape
    assert st.n_layer_attempts == 8 * 2
    # memoized run stays numerically close on high-similarity inputs
    assert np.isfinite(np.asarray(logits_on)).all()


def test_engine_threshold_monotone(tiny_engine):
    """Lower threshold -> memo rate can only grow (paper Fig. 4)."""
    eng, corpus = tiny_engine
    toks = jnp.asarray(corpus.sample(16)[0])
    rates = []
    for thr in (0.95, 0.6, 0.0):
        _, st = eng.infer({"tokens": toks}, threshold=thr)
        rates.append(st.memo_rate)
    assert rates[0] <= rates[1] <= rates[2]
    assert rates[2] == 1.0                     # threshold 0 = all memo


def test_engine_bucket_matches_select(tiny_engine):
    eng, corpus = tiny_engine
    toks = jnp.asarray(corpus.sample(8)[0])
    eng.mc.mode = "select"
    a, _ = eng.infer({"tokens": toks})
    eng.mc.mode = "bucket"
    b, _ = eng.infer({"tokens": toks})
    eng.mc.mode = "select"
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                               atol=2e-3)


def test_engine_whisper_encoder_memo():
    """Enc-dec support: whisper's encoder self-attention is memoized (the
    paper's sweet spot — fixed-length bidirectional APMs)."""
    from repro.configs import get_reduced
    from repro.core.engine import MemoEngine
    from repro.memo import MemoSpec
    from repro.models import build_model

    cfg = get_reduced("whisper_medium")
    model = build_model(cfg, layer_loop="unroll")
    params = model.init(jax.random.PRNGKey(0))
    B, S = 4, 12
    key = jax.random.PRNGKey(1)

    def mkbatch(k):
        return {"frames": jax.random.normal(
                    k, (B, cfg.encoder.n_frames, cfg.encoder.d_model)),
                "tokens": jax.random.randint(k, (B, S), 0, cfg.vocab)}

    eng = MemoEngine(model, params, MemoSpec.flat(threshold=0.5,
                                               embed_steps=30))
    eng.build(jax.random.PRNGKey(2), [mkbatch(k) for k in
                                      jax.random.split(key, 2)])
    assert eng.layers == list(range(cfg.encoder.n_layers))
    assert len(eng.db) == 2 * B * cfg.encoder.n_layers
    batch = mkbatch(jax.random.PRNGKey(3))
    logits_m, st = eng.infer(batch)
    logits_p, _ = eng.infer(batch, use_memo=False)
    assert logits_m.shape == (B, S, cfg.vocab)
    assert st.n_layer_attempts == B * cfg.encoder.n_layers
    assert np.isfinite(np.asarray(logits_m)).all()
    # threshold 0 memoizes everything
    _, st_all = eng.infer(batch, threshold=-1.0)
    assert st_all.memo_rate == 1.0


def test_distributed_search_multidevice():
    """Device-sharded DB top-1 == exact search (8 fake devices,
    subprocess-isolated)."""
    import os
    import subprocess
    import sys
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.shard import mesh_search
from repro.kernels.nn_search.ref import nn_search_ref
mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
db = jax.random.normal(jax.random.PRNGKey(0), (256, 32))
q = jax.random.normal(jax.random.PRNGKey(1), (17, 32))
with jax.set_mesh(mesh):
    dbs = jax.device_put(db, NamedSharding(mesh, P("data", None)))
    d, i = jax.jit(lambda a, b: mesh_search(a, b, mesh))(dbs, q)
dr, ir = nn_search_ref(q, db)
np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
np.testing.assert_allclose(np.asarray(d), np.asarray(dr), rtol=1e-4, atol=1e-4)
print("DSEARCH-OK")
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ, PYTHONPATH="src"),
                         cwd=repo, timeout=600)
    assert "DSEARCH-OK" in out.stdout, out.stderr[-2000:]


def test_engine_kernel_mode_matches_select(tiny_engine):
    """'kernel' mode serves hits through the fused Pallas memo_attention
    (device DB, scalar-prefetched gather, interpret on CPU) and must agree
    with the reference select path."""
    eng, corpus = tiny_engine
    toks = jnp.asarray(corpus.sample(8)[0])
    eng.mc.mode = "select"
    a, _ = eng.infer({"tokens": toks}, threshold=0.5)
    eng.mc.mode = "kernel"
    b, st = eng.infer({"tokens": toks}, threshold=0.5)
    eng.mc.mode = "select"
    assert st.n_layer_attempts > 0
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-3,
                               atol=3e-3)


def test_engine_hybrid_recurrentgemma():
    """§Arch-applicability: memoization applies to recurrentgemma's 1-in-3
    local-attention layers; RG-LRU layers pass through untouched."""
    from repro.configs import get_reduced
    from repro.core.engine import MemoEngine
    from repro.memo import MemoSpec
    from repro.data import TemplateCorpus
    from repro.models import build_model

    cfg = get_reduced("recurrentgemma_2b")      # pattern (rglru, rglru, attn)
    model = build_model(cfg, layer_loop="unroll")
    params = model.init(jax.random.PRNGKey(0))
    corpus = TemplateCorpus(vocab=cfg.vocab, seq_len=32, seed=9)
    eng = MemoEngine(model, params, MemoSpec.flat(threshold=0.5,
                                               embed_steps=30))
    assert eng.layers == [2]                     # only the attention layer
    eng.build(jax.random.PRNGKey(1),
              [{"tokens": jnp.asarray(corpus.sample(8)[0])}
               for _ in range(2)])
    toks = jnp.asarray(corpus.sample(8)[0])
    logits_m, st = eng.infer({"tokens": toks}, threshold=-1e9)
    logits_p, _ = eng.infer({"tokens": toks}, use_memo=False)
    assert st.memo_rate == 1.0
    assert logits_m.shape == logits_p.shape
    assert np.isfinite(np.asarray(logits_m)).all()
