"""Prefill memoization (ISSUE 10 / DESIGN.md §2.13).

Covers: the ``PrefillCodec`` part layout (KV parts appended AFTER the
base parts, so the fused kernel's positional indexing and every arena
consumer stay valid) and its host/device decode parity per KV mode; the
KV stack/unstack helpers; the flat ``prefill_*`` spec fields (inert by
default); engine-level prefill — self-hit decode parity per codec
against exact prefill inside the kernel-parity bounds, the miss path
matching exact prefill, the causal and length-equality hit gates, and
the prefill-only admission-capture gate; MemoServer prefill serving
(per-request cache slices, plain/prefill mixing, the MEMO_DISABLED
exact fallback, the one split program bitwise against eager slices and
compiled per padded row count); session save/load round-tripping the KV arenas; and
the backbone's own prefill+decode == full-forward parity across MHA,
GQA-grouped, and sliding-window attention (RoPE offsets ride the
position bookkeeping in all three).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core.codec import get_codec
from repro.core.engine import MemoEngine
from repro.core.prefill import PrefillCodec, stack_kv, unstack_kv_rows
from repro.core.runtime import Health, MemoServer
from repro.data import TemplateCorpus
from repro.memo import MemoSession, MemoSpec, MemoStats
from repro.models import backbone as bb
from repro.models import build_model

SEQ = 16
BATCH = 8

# per-codec |Δlogits| ceilings — the same numbers the serve_prefill
# benchmark hard-gates: prefill carries the APM codec's error, decode
# the KV codec's (lowrank KV runs at full rank: K/V spectra decay far
# slower than softmax rows, so truncation is a quality knob while the
# parity gate covers the SVD/quantized-factor machinery)
BOUNDS = {
    "f16":     {"prefill": 5e-3, "decode": 5e-3},
    "int8":    {"prefill": 2e-2, "decode": 2e-2},
    "lowrank": {"prefill": 1e-1, "decode": 5e-2},
}


# config overrides by the decode caches' segments: the reduced GPT-2's
# layers are one scan segment; a leading dense layer puts a single-layer
# segment ahead of the scan
SEGMENTS = {"scan": {}, "single": {"n_layers": 3, "dense_first_n": 1}}


@functools.lru_cache(maxsize=4)
def _built(codec: str, **over):
    """Prefill-enabled session over the reduced causal GPT-2 (``over``
    replaces config fields), cached per codec and overrides
    (module-level: several tests share the int8 build)."""
    cfg = get_reduced("gpt2_small").replace(**over)
    model = build_model(cfg, layer_loop="unroll")
    params = model.init(jax.random.PRNGKey(0))
    corpus = TemplateCorpus(vocab=cfg.vocab, seq_len=SEQ, n_templates=8,
                            slot_fraction=0.25, seed=3)
    lowrank = codec == "lowrank"
    spec = MemoSpec.flat(
        threshold=0.6, mode="bucket", embed_steps=40,
        apm_codec=codec, apm_rank=(3 * SEQ) // 4 if lowrank else None,
        prefill_enabled=True,
        prefill_kv_codec="lowrank" if lowrank else "auto",
        prefill_kv_rank=SEQ if lowrank else None)
    rng = np.random.default_rng(17)
    calib = [jnp.asarray(corpus.sample(BATCH, rng)[0]) for _ in range(2)]
    sess = MemoSession.build(model, params, spec,
                             batches=[{"tokens": t} for t in calib],
                             key=jax.random.PRNGKey(1))
    return sess, model, corpus, calib


@pytest.fixture(scope="module")
def pf_engine():
    sess, model, corpus, calib = _built("int8")
    return sess.engine, model, corpus, calib


# ------------------------------------------------------------ codec layer

KV_DIM = 12


def _kv_plane(rng, b, s=SEQ, d=KV_DIM):
    return rng.normal(0, 1.5, (b, 2, s, d)).astype(np.float32)


@pytest.mark.parametrize("kv_mode", ["f16", "int8", "lowrank"])
def test_prefill_codec_roundtrip(kv_mode):
    rng = np.random.default_rng(0)
    base = get_codec("int8", (2, SEQ, SEQ))
    rank = SEQ if kv_mode == "lowrank" else None
    c = PrefillCodec(base, KV_DIM, kv_codec=kv_mode, kv_rank=rank)
    assert c.parts[: c.n_base_parts] == base.parts   # KV strictly appended
    assert c.name == base.name                       # kernel branches on it
    apms = rng.random((3, 2, SEQ, SEQ)).astype(np.float16)
    kv = _kv_plane(rng, 3)
    parts = c.encode(apms, aux=kv)
    # base contract intact: APM decode ignores the KV suffix and matches
    # the base codec bit-for-bit
    np.testing.assert_array_equal(
        np.asarray(c.decode(parts)),
        np.asarray(base.decode(base.encode(apms))))
    got = np.asarray(c.decode_kv(parts), np.float32)
    scale = float(np.abs(kv).max())
    tol = (1e-3 if kv_mode == "f16" else 0.05) * scale
    assert np.abs(got - kv).max() < tol
    # device decode mirrors host decode op-for-op: bit-identical for the
    # elementwise codecs; lowrank reconstructs through a matmul whose
    # float32 summation order differs between numpy and XLA, which can
    # move the float16 result by one ulp
    dev = np.asarray(c.decode_kv_rows(tuple(jnp.asarray(p)
                                            for p in parts)))
    host = np.asarray(c.decode_kv(parts))
    if kv_mode == "lowrank":
        np.testing.assert_array_max_ulp(dev, host, maxulp=1)
    else:
        np.testing.assert_array_equal(dev, host)


def test_prefill_codec_zero_fallback_and_shape_guard():
    base = get_codec("f16", (2, SEQ, SEQ))
    c = PrefillCodec(base, KV_DIM)
    apms = np.random.default_rng(1).random((2, 2, SEQ, SEQ)) \
        .astype(np.float16)
    parts = c.encode(apms)                 # aux=None: legacy APM-only
    assert np.abs(np.asarray(c.decode_kv(parts))).max() == 0.0
    with pytest.raises(ValueError, match="kv aux shape"):
        c.encode(apms, aux=np.zeros((2, 2, SEQ, KV_DIM + 1), np.float32))


def test_stack_unstack_kv_inverse():
    rng = np.random.default_rng(2)
    hkv, dh = 3, 4
    k = rng.normal(size=(2, SEQ, hkv, dh)).astype(np.float32)
    v = rng.normal(size=(2, SEQ, hkv, dh)).astype(np.float32)
    kv = stack_kv(k, v)
    assert kv.shape == (2, 2, SEQ, hkv * dh)
    k2, v2 = unstack_kv_rows(jnp.asarray(kv), hkv, dh)
    np.testing.assert_array_equal(np.asarray(k2), k)
    np.testing.assert_array_equal(np.asarray(v2), v)


# ------------------------------------------------------------- spec layer

def test_prefill_spec_flat_fields_and_roundtrip():
    spec = MemoSpec.flat(threshold=0.5)
    assert spec.prefill.enabled is False        # inert by default
    spec = MemoSpec.flat(prefill_enabled=True, prefill_cache_len=64,
                         prefill_kv_codec="int8")
    assert spec.prefill.enabled and spec.prefill.cache_len == 64
    assert spec.prefill_kv_codec == "int8"      # flat attribute view
    back = MemoSpec.from_dict(spec.to_dict())
    assert back.prefill.enabled is True
    assert back.prefill.cache_len == 64
    assert back.prefill.kv_codec == "int8"


# ----------------------------------------------------------- engine layer

def _teacher_forced_decode(eng, model, lm, cm, le, ce, steps):
    """Greedy decode both cache sets on the exact leg's tokens; returns
    (max |Δlogits| across steps, agreement fraction)."""
    dmax, agree, total = 0.0, 0, 0
    for step in range(steps):
        tm = jnp.argmax(lm, -1).reshape(-1)
        te = jnp.argmax(le, -1).reshape(-1)
        agree += int((tm == te).sum())
        total += int(te.shape[0])
        pos = jnp.int32(SEQ + step)
        lm, cm = model.decode_step(eng.params, te[:, None], cm, pos)
        le, ce = model.decode_step(eng.params, te[:, None], ce, pos)
        dmax = max(dmax, float(jnp.max(jnp.abs(lm - le))))
    return dmax, agree / max(1, total)


@pytest.mark.parametrize("codec", ["f16", "int8", "lowrank"])
def test_prefill_selfhit_decode_parity(codec):
    """Replaying an admitted prompt hits every memoized layer, and the
    decode cache materialized from the stored KV entry carries greedy
    decode inside the per-codec kernel-parity bounds (acceptance)."""
    sess, model, corpus, calib = _built(codec)
    eng = sess.engine
    batch = {"tokens": calib[0]}
    le, ce = eng.prefill_exact(batch)
    st = MemoStats()
    lm, cm, st = eng.prefill(batch, stats=st)
    assert st.n_layer_attempts > 0
    assert st.n_hits == st.n_layer_attempts          # pure self-hits
    b = BOUNDS[codec]
    assert float(jnp.max(jnp.abs(lm - le))) <= b["prefill"]
    dmax, agree = _teacher_forced_decode(eng, model, lm, cm, le, ce, 4)
    assert dmax <= b["decode"]
    assert agree >= (1.0 if codec == "f16" else 0.9)


def test_prefill_miss_matches_exact(pf_engine):
    """All-miss prefill (threshold above every sim) runs the exact layer
    bodies: logits match ``prefill_exact`` and decode caches agree."""
    eng, model, corpus, _ = pf_engine
    batch = {"tokens": jnp.asarray(corpus.sample(4)[0])}
    le, ce = eng.prefill_exact(batch)
    st = MemoStats()
    lm, cm, st = eng.prefill(batch, threshold=1e9, stats=st)
    assert st.n_hits == 0
    np.testing.assert_allclose(np.asarray(lm), np.asarray(le),
                               rtol=2e-3, atol=2e-3)
    dmax, agree = _teacher_forced_decode(eng, model, lm, cm, le, ce, 2)
    assert dmax <= 2e-3 and agree == 1.0


def test_prefill_memo_drain_matches_eager_drain(pf_engine,
                                               check_drain_parity):
    """A capturing prefill batch at a threshold that mixes hits and
    misses: the jitted drain gives the eager drain's counters, sims (bit
    for bit), reuse slots and KV-bearing admissions."""
    eng, _, corpus, _ = pf_engine
    batch = {"tokens": jnp.asarray(
        corpus.sample(4, np.random.default_rng(23))[0])}
    probe = eng.prepare_batch(batch, threshold=1e9, prefill=True)
    eng.run_layers(probe)
    _, st0, _ = eng.finalize(probe)
    thr = float(np.median(list(st0.sims)))
    admit0 = eng.mc.admit, eng.mc.admit_every
    eng.mc.admit, eng.mc.admit_every = True, 1
    try:
        prep = eng.prepare_batch(batch, threshold=thr, prefill=True)
        assert prep.capture
        eng.run_layers(prep)
        _, st, payload = check_drain_parity(eng, prep)
    finally:
        eng.mc.admit, eng.mc.admit_every = admit0
    assert 0 < st.n_hits < st.n_layer_attempts
    assert payload.admissions
    assert all(adm[3] is not None for adm in payload.admissions)


def test_prefill_length_gate(pf_engine):
    """Stored entries were captured at SEQ; a shorter prompt may NEVER
    replay them even when the threshold passes everything — the length
    gate is part of the hit predicate, not a heuristic."""
    eng, _, corpus, _ = pf_engine
    toks = np.asarray(corpus.sample(4)[0])
    toks[:, SEQ - 4:] = 0                       # padded to the bucket
    lens = np.full(4, SEQ - 4, np.int32)
    _, _, st = eng.prefill({"tokens": jnp.asarray(toks), "lengths": lens},
                           threshold=-1e9, stats=MemoStats())
    assert st.n_hits == 0
    # contrast: same-length traffic at the same threshold is all-hit
    _, _, st2 = eng.prefill({"tokens": jnp.asarray(corpus.sample(4)[0])},
                            threshold=-1e9, stats=MemoStats())
    assert st2.n_hits == st2.n_layer_attempts > 0


def test_prefill_requires_causal():
    """The mask-kind gate: a bidirectional model can never replay
    causal-prefill entries, so the engine refuses at build time."""
    cfg = get_reduced("bert_base").replace(n_layers=2, d_model=128,
                                           d_ff=256, n_heads=4)
    model = build_model(cfg, layer_loop="unroll")
    params = model.init(jax.random.PRNGKey(0))
    eng = MemoEngine(model, params,
                     MemoSpec.flat(prefill_enabled=True, embed_steps=10))
    corpus = TemplateCorpus(vocab=cfg.vocab, seq_len=SEQ)
    with pytest.raises(ValueError, match="causal"):
        eng.build(jax.random.PRNGKey(1),
                  [{"tokens": jnp.asarray(corpus.sample(4)[0])}])


def test_capture_gates_to_prefill_batches(pf_engine):
    """With prefill memoization on, ONLY prefill batches may capture for
    admission: an APM-only capture would admit zero-KV entries whose
    later hits replay an empty decode cache."""
    eng, _, _, _ = pf_engine
    admit0 = eng.mc.admit
    eng.mc.admit = True
    try:
        assert eng._capture_now(True, prefill=True)
        assert not eng._capture_now(True, prefill=False)
    finally:
        eng.mc.admit = admit0


# ----------------------------------------------------------- server layer

def test_server_prefill_serving(pf_engine):
    """Prefill requests come back with per-request decode caches that
    decode in lockstep with exact-prefill caches; plain requests carry
    none; prefill and plain requests never share a batch."""
    eng, model, corpus, calib = pf_engine
    srv = MemoServer(eng, buckets=(SEQ,), max_batch=4,
                     async_maintenance=False)
    try:
        cal = np.asarray(calib[0])
        rids_pf = [srv.submit(cal[i], prefill=True) for i in range(4)]
        rids_pl = [srv.submit(cal[i]) for i in range(2)]
        comps = []
        while srv.queued:
            comps.extend(srv.step(flush=True))
        by_rid = {c.rid: c for c in comps}
        pf = [by_rid[r] for r in rids_pf]
        assert all(c.caches is not None for c in pf)
        assert all(by_rid[r].caches is None for r in rids_pl)
        # per-request cache slices decode in lockstep with exact prefill
        le, ce = eng.prefill_exact({"tokens": jnp.asarray(cal[:4])})
        np.testing.assert_allclose(
            np.stack([c.logits for c in pf]), np.asarray(le),
            rtol=0, atol=BOUNDS["int8"]["prefill"])
        te = jnp.argmax(le, -1).reshape(-1)
        by_li = eng._split_caches(ce)
        dmax = 0.0
        for i, c in enumerate(pf):
            lg, _ = model.decode_step(eng.params, te[i: i + 1][:, None],
                                      c.caches, jnp.int32(SEQ))
            ce_i = eng._merge_caches(
                {li: jax.tree.map(lambda a, i=i: a[i: i + 1], cc)
                 for li, cc in by_li.items()})
            lge, _ = model.decode_step(eng.params, te[i: i + 1][:, None],
                                       ce_i, jnp.int32(SEQ))
            dmax = max(dmax, float(jnp.max(jnp.abs(lg - lge))))
        assert dmax <= BOUNDS["int8"]["decode"]
    finally:
        srv.close()


def test_server_prefill_requires_enabled_spec(pf_engine):
    eng, _, corpus, calib = pf_engine
    srv = MemoServer(eng, buckets=(SEQ,), max_batch=4,
                     async_maintenance=False)
    try:
        eng.mc.prefill.enabled = False
        with pytest.raises(RuntimeError, match="prefill"):
            srv.submit(np.asarray(calib[0])[0], prefill=True)
    finally:
        eng.mc.prefill.enabled = True
        srv.close()


def test_server_prefill_memo_disabled_falls_back_exact(pf_engine):
    """Graceful degradation: with the memo path disabled, prefill
    requests serve through ``prefill_exact`` — same response shape,
    caches included, exact logits."""
    eng, _, _, calib = pf_engine
    srv = MemoServer(eng, buckets=(SEQ,), max_batch=4,
                     async_maintenance=False)
    try:
        srv.health = Health.MEMO_DISABLED
        cal = np.asarray(calib[0])
        rids = [srv.submit(cal[i], prefill=True) for i in range(2)]
        comps = []
        while srv.queued:
            comps.extend(srv.step(flush=True))
        by_rid = {c.rid: c for c in comps}
        le, _ = eng.prefill_exact({"tokens": jnp.asarray(cal[:2])})
        for i, r in enumerate(rids):
            assert by_rid[r].caches is not None
            np.testing.assert_allclose(by_rid[r].logits,
                                       np.asarray(le)[i], rtol=0,
                                       atol=1e-5)
    finally:
        srv.close()


@pytest.mark.parametrize("exact", [False, True], ids=["memo", "exact"])
@pytest.mark.parametrize("segments", sorted(SEGMENTS))
def test_server_prefill_split_matches_eager_slices(segments, exact,
                                                   monkeypatch):
    """Each ``Completion.caches`` is, bitwise and leaf by leaf, batch row i
    of the step's decode caches cut by eager slices (the reference
    ``test_server_prefill_serving`` builds as ``ce_i``): batches of 1, 3
    and 4 requests at ``max_batch=4`` (filler rows dropped), on the memo
    path and the MEMO_DISABLED exact path, over scan and single
    segments."""
    sess, _, _, calib = _built("int8", **SEGMENTS[segments])
    eng = sess.engine
    assert {seg.kind for seg in bb.scan_plan(eng.cfg)} == (
        {"scan"} if segments == "scan" else {"single", "scan"})
    leg = "prefill_exact" if exact else "finalize"
    real, batch_caches = getattr(eng, leg), []

    def spy(*a, **k):
        out = real(*a, **k)
        batch_caches.append(out[1] if exact else out[0][1])
        return out
    monkeypatch.setattr(eng, leg, spy)
    srv = MemoServer(eng, buckets=(SEQ,), max_batch=4,
                     async_maintenance=False)
    if exact:
        srv.health = Health.MEMO_DISABLED
    cal = np.asarray(calib[0])
    try:
        for n in (1, 3, 4):
            rids = [srv.submit(cal[i], prefill=True) for i in range(n)]
            comps = srv.step(flush=True)
            assert [c.rid for c in comps] == rids
            by_li = eng._split_caches(batch_caches[-1])
            for i, c in enumerate(comps):
                ref = eng._merge_caches(
                    {li: jax.tree.map(lambda a, i=i: a[i: i + 1], cc)
                     for li, cc in by_li.items()})
                assert jax.tree.structure(c.caches) == \
                    jax.tree.structure(ref)
                for got, want in zip(jax.tree.leaves(c.caches),
                                     jax.tree.leaves(ref)):
                    assert got.dtype == want.dtype
                    np.testing.assert_array_equal(np.asarray(got),
                                                  np.asarray(want))
    finally:
        srv.close()
    assert len(batch_caches) == 3
    assert srv.n_filler_rows == 3 + 1 + 0


def test_server_prefill_split_compiles_per_padded_rows(pf_engine,
                                                       monkeypatch):
    """The split program is keyed on the padded row count, not on the
    number of requests: ``warmup`` compiles ONE ``memo_split_caches``
    entry, prefill batches of every size 1…max_batch at that row padding
    trace nothing more, and ``_execute`` merges no cache per request (a
    step's one eager ``_merge_caches`` is ``finalize``'s)."""
    eng, _, _, calib = pf_engine
    for key in [k for k in eng._jit_cache if k[0] == "split"]:
        del eng._jit_cache[key]
    srv = MemoServer(eng, buckets=(SEQ,), max_batch=4, batch_quantum=4,
                     async_maintenance=False)
    srv.warmup(batch_sizes=[4])
    keys = [k for k in eng._jit_cache if k[0] == "split"]
    # one scan segment: every leaf is (reps, rows, ...)
    assert len(keys) == 1 and {s[1] for s, _ in keys[0][2]} == {4}
    calls = {"eager": 0, "traced": 0}
    merge = eng._merge_caches

    def counting(by_li):
        traced = any(isinstance(a, jax.core.Tracer)
                     for a in jax.tree.leaves(by_li))
        calls["traced" if traced else "eager"] += 1
        return merge(by_li)
    monkeypatch.setattr(eng, "_merge_caches", counting)
    cal = np.asarray(calib[0])
    try:
        for n in range(1, 5):
            for i in range(n):
                srv.submit(cal[i], prefill=True)
            assert len(srv.step(flush=True)) == n
    finally:
        srv.close()
    assert [k for k in eng._jit_cache if k[0] == "split"] == keys
    assert calls == {"eager": 4, "traced": 0}


# ---------------------------------------------------------- session layer

def test_session_save_load_roundtrips_kv(tmp_path, pf_engine):
    """Save format 3 persists the KV parts through the codec-driven
    ``state_dict`` untouched: the loaded engine's prefill (hits + stored
    KV) matches the original bit-for-bit."""
    sess, model, _, calib = _built("int8")
    path = str(tmp_path / "sess.m3")
    sess.save(path)
    sess2 = MemoSession.load(path, model, sess.engine.params)
    assert isinstance(sess2.engine.store.codec, PrefillCodec)
    sd, sd2 = sess.store.state_dict(), sess2.store.state_dict()
    assert set(sd) == set(sd2)
    for k in sd:
        assert np.asarray(sd[k]).tobytes() == np.asarray(sd2[k]).tobytes(), k
    batch = {"tokens": calib[0]}
    lm, _, st = sess.engine.prefill(batch, stats=MemoStats())
    lm2, _, st2 = sess2.engine.prefill(batch, stats=MemoStats())
    assert st2.n_hits == st.n_hits > 0
    np.testing.assert_array_equal(np.asarray(lm), np.asarray(lm2))


# ------------------------------------- backbone prefill/decode (satellite)

@pytest.mark.parametrize("arch,over", [
    ("gpt2_small", {}),                        # MHA
    ("qwen3_8b", {}),                          # GQA: 4 heads over 2 KV
    ("gpt2_small", {"sliding_window": 8}),     # local attention window
])
def test_model_prefill_decode_matches_full_forward(arch, over):
    """The decode path the memoized prefill hands its caches to must
    itself be exact: prefill(S0) + K decode steps reproduces the full
    (S0+K)-sequence forward position by position — across GQA grouping,
    sliding windows, and the RoPE rotations the absolute decode
    positions select."""
    cfg = get_reduced(arch).replace(**over) if over else get_reduced(arch)
    model = build_model(cfg, layer_loop="unroll")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    s0, steps = 8, 4
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, s0 + steps)),
                       jnp.int32)
    full, _, _ = model.forward(params, {"tokens": toks})
    lg, caches = model.prefill(params, {"tokens": toks[:, :s0]},
                               cache_len=s0 + steps)
    np.testing.assert_allclose(np.asarray(lg),
                               np.asarray(full[:, s0 - 1]),
                               rtol=2e-4, atol=2e-4)
    for k in range(steps):
        lg, caches = model.decode_step(params, toks[:, s0 + k][:, None],
                                       caches, jnp.int32(s0 + k))
        np.testing.assert_allclose(np.asarray(lg),
                                   np.asarray(full[:, s0 + k]),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"decode step {k}")
