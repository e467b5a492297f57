"""MemoServer runtime + variable-length serving (ISSUE 4 / DESIGN.md §2.7).

Covers: mask-aware embedding/lookup/logits parity between padded
variable-length batches and unpadded per-length runs, the
zero-per-layer-host-sync invariant under the runtime, async-vs-sync
maintenance equivalence, the bounded jit-shape set, thread-safe stats
accumulation, and the atomic snapshot publish protocol.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.engine as engine_mod
from repro.core.engine import MemoStats, SimReservoir
from repro.core.runtime import MemoServer, pow2_buckets
from repro.core.store import StoreSnapshot
from repro.models import backbone as bb

SEQ = 32


@pytest.fixture(scope="module")
def vl_engine():
    from repro.configs import get_reduced
    from repro.core.engine import MemoEngine
    from repro.memo import MemoSpec
    from repro.data import TemplateCorpus
    from repro.models import build_model

    cfg = get_reduced("bert_base").replace(n_classes=4, n_layers=2,
                                           d_model=128, d_ff=256, n_heads=4)
    m = build_model(cfg, layer_loop="unroll")
    params = m.init(jax.random.PRNGKey(0))
    corpus = TemplateCorpus(vocab=cfg.vocab, seq_len=SEQ, n_templates=6,
                            slot_fraction=0.2)
    eng = MemoEngine(m, params, MemoSpec.flat(threshold=0.6, embed_steps=40,
                                           mode="bucket", device_slack=8.0))
    eng.build(jax.random.PRNGKey(1),
              [{"tokens": jnp.asarray(corpus.sample(16)[0])}
               for _ in range(3)])
    return eng, corpus


def _varlen_batch(corpus, lens, pad_to):
    toks = np.asarray(corpus.sample(len(lens))[0][:, :pad_to])
    lens = np.asarray(lens, np.int32)
    for i, ln in enumerate(lens):
        toks[i, ln:] = 0
    return toks, lens


# ------------------------------------------- mask-aware padding parity

def test_masked_embedding_parity_padded_vs_unpadded(vl_engine):
    """The same sequence embeds identically whether it arrives padded to
    a bucket or at its exact length — the property that makes the memo
    lookup mask-aware (satellite: embedding parity)."""
    eng, corpus = vl_engine
    cfg = eng.cfg
    lens = [SEQ, SEQ // 2, SEQ - 8, SEQ // 2]
    toks, lens_np = _varlen_batch(corpus, lens, SEQ)
    lp0 = eng._iter_layers()[0][2]
    h = bb.embed_tokens(eng.params, jnp.asarray(toks), cfg)
    x = bb.norm_apply(lp0["norm1"], h, cfg.norm)
    e_pad = np.asarray(eng._embed(x, lengths=lens_np))
    for i, ln in enumerate(lens):
        h_i = bb.embed_tokens(eng.params, jnp.asarray(toks[i:i + 1, :ln]),
                              cfg)
        x_i = bb.norm_apply(lp0["norm1"], h_i, cfg.norm)
        e_i = np.asarray(eng._embed(x_i, lengths=np.asarray([ln])))
        np.testing.assert_allclose(e_pad[i], e_i[0], rtol=1e-5, atol=1e-5)


def test_padded_batch_matches_unpadded_per_length_run(vl_engine):
    """A padded variable-length batch produces the same per-sequence hit
    decisions and logits as running each length group unpadded at its own
    sequence length (acceptance: padded-row APM gather parity)."""
    eng, corpus = vl_engine
    lens = [SEQ, SEQ, SEQ // 2, SEQ // 2]
    toks, lens_np = _varlen_batch(corpus, lens, SEQ)
    batch = {"tokens": jnp.asarray(toks), "lengths": lens_np}
    prep = eng.prepare_batch(batch, threshold=0.6)
    eng.run_layers(prep)
    out_pad, _, _ = eng.finalize(prep)
    hits_pad = np.asarray(jnp.stack([p[2] for p in prep.pend]))  # (L, B)
    out_pad = np.asarray(out_pad)
    for ln in sorted(set(lens)):
        rows = [i for i, x in enumerate(lens) if x == ln]
        sub = {"tokens": jnp.asarray(toks[rows][:, :ln]),
               "lengths": np.full(len(rows), ln, np.int32)}
        prep_u = eng.prepare_batch(sub, threshold=0.6)
        eng.run_layers(prep_u)
        out_u, _, _ = eng.finalize(prep_u)
        hits_u = np.asarray(jnp.stack([p[2] for p in prep_u.pend]))
        np.testing.assert_array_equal(hits_pad[:, rows], hits_u)
        np.testing.assert_allclose(out_pad[rows], np.asarray(out_u),
                                   rtol=2e-3, atol=2e-3)


def test_varlen_fast_path_matches_select(vl_engine):
    """Fast-path logits == select reference on the same padded batch, and
    the length gate forces misses for lengths with no same-length entry
    (the calibration corpus is all full-length)."""
    eng, corpus = vl_engine
    toks, lens_np = _varlen_batch(corpus, [SEQ, SEQ - 4, SEQ // 2, SEQ], SEQ)
    batch = {"tokens": jnp.asarray(toks), "lengths": lens_np}
    out_fast, st = eng.infer(batch, threshold=-1e9)
    eng.mc.mode = "select"
    try:
        out_sel, st_sel = eng.infer(batch, threshold=-1e9)
    finally:
        eng.mc.mode = "bucket"
    np.testing.assert_allclose(np.asarray(out_fast), np.asarray(out_sel),
                               rtol=2e-3, atol=2e-3)
    # threshold −∞ admits everything similarity-wise, so the ONLY misses
    # are length-gate misses: rows 1 and 2 have no same-length entries
    n_layers = len(eng.layers)
    assert st.n_hits == 2 * n_layers
    assert st_sel.n_hits == 2 * n_layers


def test_varlen_admission_learns_new_lengths(vl_engine):
    """Captured misses are admitted at their true length and hit on the
    next same-length batch (the store adapts per length)."""
    eng, corpus = vl_engine
    eng.mc.admit = True
    try:
        toks, lens_np = _varlen_batch(corpus, [SEQ - 8] * 4, SEQ)
        batch = {"tokens": jnp.asarray(toks), "lengths": lens_np}
        _, st1 = eng.infer(batch, threshold=0.6)
        assert st1.n_admitted > 0
        lens_stored = eng.store.entry_lengths(
            np.arange(len(eng.db)))
        assert (lens_stored == SEQ - 8).sum() == st1.n_admitted
        _, st2 = eng.infer(batch, threshold=0.6)
        assert st2.n_hits == len(eng.layers) * 4      # exact replay hits
    finally:
        eng.mc.admit = False


# ------------------------------------------------- runtime invariants

class _Counting:
    """A module stand-in that counts calls to the ``counted`` names."""

    def __init__(self, real, counted):
        self._real, self.counts = real, {n: 0 for n in counted}
        for n in counted:
            def mk(name, fn=getattr(real, n)):
                def f(*a, **k):
                    self.counts[name] += 1
                    return fn(*a, **k)
                return f
            setattr(self, n, mk(n))

    def __getattr__(self, name):
        return getattr(self._real, name)


def test_runtime_zero_per_layer_host_sync(vl_engine, monkeypatch):
    """One batch through MemoServer.step issues exactly ONE
    block_until_ready and at most the two stacked stats transfers —
    the fast path's invariant survives the runtime (acceptance)."""
    eng, corpus = vl_engine
    server = MemoServer(eng, buckets=(SEQ // 2, SEQ), max_batch=4,
                        batch_quantum=4, async_maintenance=False)
    server.warmup(batch_sizes=[4])
    for ln in (SEQ, SEQ - 2, SEQ, SEQ):
        server.submit(np.asarray(corpus.sample(1)[0][0, :ln]))
    server.step(flush=True)           # drain a first batch post-warmup
    assert server.queued == 0
    for ln in (SEQ, SEQ - 2, SEQ, SEQ):
        server.submit(np.asarray(corpus.sample(1)[0][0, :ln]))

    fake_jax = _Counting(jax, ["block_until_ready"])
    fake_np = _Counting(np, ["asarray", "nonzero"])
    monkeypatch.setattr(engine_mod, "jax", fake_jax)
    monkeypatch.setattr(engine_mod, "np", fake_np)
    comps = server.step(flush=True)
    assert len(comps) == 4
    assert fake_jax.counts["block_until_ready"] == 1
    assert fake_np.counts["asarray"] <= 2
    assert fake_np.counts["nonzero"] == 0
    server.close()


def test_drain_issues_no_eager_programs(vl_engine, monkeypatch):
    """The stats drain of a MemoServer step runs no eager jnp.stack or
    astype: one jitted ``memo_drain`` (cached per batch shape) packs the
    stats, and one block_until_ready stays the only barrier."""
    eng, corpus = vl_engine
    server = MemoServer(eng, buckets=(SEQ,), max_batch=4,
                        async_maintenance=False)
    for key in [k for k in eng._jit_cache if k[0] == "drain"]:
        del eng._jit_cache[key]

    def drain_keys():
        return {k for k in eng._jit_cache if k[0] == "drain"}

    calls = {"stack": 0, "astype": 0}

    class _Jnp:
        def __getattr__(self, name):
            return getattr(jnp, name)

        def stack(self, *a, **k):
            calls["stack"] += 1
            return jnp.stack(*a, **k)

    array_t = type(jnp.zeros(1))
    real_astype = array_t.astype

    def counting_astype(self, *a, **k):
        calls["astype"] += 1
        return real_astype(self, *a, **k)

    real_drain = eng._drain_stats

    def counted_drain(*a, **k):
        with monkeypatch.context() as m:
            m.setattr(engine_mod, "jnp", _Jnp())
            m.setattr(array_t, "astype", counting_astype)
            return real_drain(*a, **k)

    monkeypatch.setattr(eng, "_drain_stats", counted_drain)
    fake_jax = None
    for n_new in (1, 0):     # first batch of a shape, then the second
        before = drain_keys()
        for _ in range(4):
            server.submit(np.asarray(corpus.sample(1)[0][0]))
        if n_new == 0:
            fake_jax = _Counting(jax, ["block_until_ready"])
            fake_np = _Counting(np, ["asarray"])
            monkeypatch.setattr(engine_mod, "jax", fake_jax)
            monkeypatch.setattr(engine_mod, "np", fake_np)
        assert len(server.step(flush=True)) == 4
        new = drain_keys() - before
        assert len(new) == n_new
        assert all(eng._jit_cache[k].__name__ == "memo_drain" for k in new)
    assert calls == {"stack": 0, "astype": 0}
    assert fake_jax.counts["block_until_ready"] == 1
    assert fake_np.counts["asarray"] <= 2
    server.close()


def _mixed_threshold(eng, batch, can_hit):
    """A threshold that splits the lookups of ``can_hit`` rows into hits
    and misses: the median of their similarities on this batch."""
    prep = eng.prepare_batch(batch, threshold=1e9)
    eng.run_layers(prep)
    _, st, _ = eng.finalize(prep)
    sims = np.asarray(list(st.sims)).reshape(len(prep.pend), -1)
    return float(np.median(sims[:, can_hit]))


@pytest.mark.parametrize("case", ["fixed", "varlen", "mixed", "capture"])
def test_memo_drain_matches_eager_drain(vl_engine, check_drain_parity,
                                        case):
    """The jitted drain gives the MemoStats counters, sims (bit for bit),
    reuse slots and admissions of the eager drain it replaced: on a
    fixed-length batch, a padded variable-length one with n_valid < rows,
    at a threshold that mixes hits and misses, and under capture."""
    eng, corpus = vl_engine
    lens = [SEQ, SEQ, SEQ - 2, SEQ]
    toks, lens_np = _varlen_batch(corpus, lens, SEQ)
    batch = {"tokens": jnp.asarray(toks)}
    if case in ("varlen", "capture"):
        batch.update(lengths=lens_np, n_valid=3)
    thr = 0.6
    if case in ("mixed", "capture"):
        thr = _mixed_threshold(eng, batch, [0, 1])
    admit0 = eng.mc.admit, eng.mc.admit_every
    eng.mc.admit, eng.mc.admit_every = case == "capture", 1
    try:
        prep = eng.prepare_batch(batch, threshold=thr)
        assert prep.capture == (case == "capture")
        eng.run_layers(prep)
        _, st, payload = check_drain_parity(eng, prep)
    finally:
        eng.mc.admit, eng.mc.admit_every = admit0
    assert st.n_layer_attempts == len(eng.layers) * prep.n_valid
    if case in ("mixed", "capture"):
        assert 0 < st.n_hits < st.n_layer_attempts
    if case == "capture":
        assert payload.admissions and payload.reuse_slots.size


def test_runtime_bounded_jit_shape_set(vl_engine):
    """Arbitrary request lengths compile at most
    len(buckets) x len(row-paddings) fused shapes per layer kind."""
    eng, corpus = vl_engine
    server = MemoServer(eng, buckets=(SEQ // 2, SEQ), max_batch=4,
                        batch_quantum=2, async_maintenance=False)
    rng = np.random.default_rng(3)
    for _ in range(6):
        for __ in range(int(rng.integers(1, 5))):
            ln = int(rng.integers(4, SEQ + 1))
            server.submit(np.asarray(corpus.sample(1)[0][0, :ln]))
        server.step(flush=True)
    fused_shapes = {k[4] for k in eng._jit_cache
                    if isinstance(k, tuple) and k[0] == "fused" and k[-1]}
    # buckets {16, 32} x row paddings {2, 4} = 4 shapes max
    assert len(fused_shapes) <= 4
    server.close()


def test_runtime_async_matches_sync_serving(vl_engine):
    """With maintenance idle (no admission), async and sync runtimes are
    the same serving machine: identical logits for identical requests."""
    eng, corpus = vl_engine
    reqs = [np.asarray(corpus.sample(1)[0][0, :ln])
            for ln in (SEQ, SEQ - 4, SEQ // 2, SEQ)]
    outs = {}
    for mode in (False, True):
        server = MemoServer(eng, buckets=(SEQ // 2, SEQ), max_batch=4,
                            async_maintenance=mode)
        with server:
            for r in reqs:
                server.submit(r)
            comps = []
            while server.queued:
                comps.extend(server.step(flush=True))
        outs[mode] = {c.rid: c.logits for c in comps}
    assert outs[False].keys() == outs[True].keys()
    for rid in outs[False]:
        np.testing.assert_allclose(outs[False][rid], outs[True][rid],
                                   rtol=1e-5, atol=1e-5)


def test_runtime_async_maintenance_applies_and_publishes(vl_engine):
    """Async mode: admissions queued by finalize are applied off-thread;
    after drain the snapshot generation advanced atomically and a repeat
    batch hits on the admitted entries."""
    eng, corpus = vl_engine
    eng.mc.admit = True
    try:
        gen0 = eng.store.snapshot.generation
        n0 = eng.store.stats.n_admitted
        server = MemoServer(eng, buckets=(SEQ // 2, SEQ), max_batch=4,
                            async_maintenance=True)
        toks = [np.asarray(corpus.sample(1)[0][0, :SEQ - 12])
                for _ in range(4)]
        with server:
            for t in toks:
                server.submit(t)
            server.step(flush=True)
            server.drain_maintenance()
            snap = eng.store.snapshot
            assert isinstance(snap, StoreSnapshot)
            assert snap.generation > gen0
            assert eng.store.stats.n_admitted > n0
            for t in toks:                      # same requests again
                server.submit(t)
            comps = server.step(flush=True)
        hit_counts = server.stats.n_hits
        assert len(comps) == 4
        assert hit_counts >= len(eng.layers) * 4   # second pass all hit
        assert not server.maintenance_errors
    finally:
        eng.mc.admit = False


def test_fixed_length_queries_never_replay_shorter_entries(vl_engine):
    """The length gate is ALWAYS on: a fixed-length batch (no lengths)
    must not hit an entry admitted at a shorter true length — its APM
    rows past that length are hard zeros, so replaying it would silently
    zero the query's tail attention."""
    eng, corpus = vl_engine
    store = eng.store
    toks = jnp.asarray(corpus.sample(4)[0])
    # poison the store: entries whose embeddings EXACTLY match this
    # batch's layer-0 fixed-length embeddings, but stored at length 10
    lp0 = eng._iter_layers()[0][2]
    h = bb.embed_tokens(eng.params, toks, eng.cfg)
    x = bb.norm_apply(lp0["norm1"], h, eng.cfg.norm)
    embs = np.asarray(eng._embed(x))
    apms = np.zeros((4,) + store.apm_shape, np.float16)
    store.admit(apms, embs, lengths=np.full(4, 10, np.int32))
    store.sync()
    out, st = eng.infer({"tokens": toks}, threshold=-1e9)
    # layer 0's top-1 is the distance-0 poisoned entry — without the
    # gate all 4 rows would hit it; with it they are length-gated misses
    assert st.per_layer_hits.get(eng.layers[0], 0) == 0
    assert np.isfinite(np.asarray(out)).all()


# ------------------------------------------------- thread-safe stats

def test_sim_reservoir_concurrent_append_is_lossless():
    res = SimReservoir(cap=128)
    n_threads, per = 8, 500

    def work(seed):
        for i in range(per):
            res.append(float(seed * per + i))

    ts = [threading.Thread(target=work, args=(t,)) for t in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert res.seen == n_threads * per
    assert len(res) == 128


def test_memostats_concurrent_merge():
    total = MemoStats()
    n_threads, per = 6, 50

    def work():
        for _ in range(per):
            st = MemoStats(n_layer_attempts=4, n_hits=2,
                           per_layer_hits={0: 1, 1: 1})
            st.sims.extend([0.5, 0.6])
            total.merge(st)
            total.add_admitted(1)

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    n = n_threads * per
    assert total.n_layer_attempts == 4 * n
    assert total.n_hits == 2 * n
    assert total.n_admitted == n
    assert total.per_layer_hits == {0: n, 1: n}
    assert total.sims.seen == 2 * n


# ----------------------------------------------- snapshot publication

def test_snapshot_is_stable_until_next_sync(vl_engine):
    """The published snapshot is immutable: host-tier mutation does not
    change it until the next sync commits a new generation — in-flight
    batches keep serving the arrays they captured."""
    eng, _ = vl_engine
    store = eng.store
    store.sync()
    snap = store.snapshot
    apms = np.random.default_rng(5).random(
        (2,) + store.apm_shape).astype(np.float16)
    embs = np.random.default_rng(6).normal(
        size=(2, store.embed_dim)).astype(np.float32)
    store.admit(apms, embs, lengths=np.asarray([7, 9], np.int32))
    assert store.snapshot is snap                 # not yet published
    assert store.device_stale
    store.sync()
    snap2 = store.snapshot
    assert snap2 is not snap
    assert snap2.generation > snap.generation
    # the superseded snapshot's arrays are still alive and consistent
    assert snap.db_parts[0].shape == snap2.db_parts[0].shape


def test_pow2_buckets():
    assert pow2_buckets(64) == (16, 32, 64)
    assert pow2_buckets(32, n=2) == (16, 32)
    assert pow2_buckets(8) == (8,)


@pytest.mark.parametrize("thr", [-1e9, 0.6, 1e9])
def test_server_kernel_mode_matches_select(vl_engine, thr):
    """MemoServer serves kernel mode (the memo_attention ``lengths``
    operand carries variable length): each completion equals the select
    reference on the padded batch the server built."""
    eng, corpus = vl_engine
    toks, lens = _varlen_batch(corpus, [SEQ, SEQ // 2, SEQ, SEQ // 2],
                               SEQ)
    mode, admit = eng.mc.mode, eng.mc.admit
    eng.mc.mode, eng.mc.admit = "kernel", False
    try:
        server = MemoServer(eng, buckets=(SEQ,), max_batch=4,
                            batch_quantum=4, async_maintenance=False)
        for i, ln in enumerate(lens):
            server.submit(toks[i, :ln])
        eng.mc.threshold = thr
        comps = sorted(server.step(flush=True), key=lambda c: c.rid)
        server.close()
        eng.mc.mode = "select"
        ref, _ = eng.infer({"tokens": jnp.asarray(toks), "lengths": lens,
                            "n_valid": len(lens)})
    finally:
        eng.mc.mode, eng.mc.admit, eng.mc.threshold = mode, admit, 0.6
    assert len(comps) == len(lens)
    for i, c in enumerate(comps):
        np.testing.assert_allclose(c.logits, np.asarray(ref)[i],
                                   rtol=2e-3, atol=2e-3)
