"""First-light check of the memoized serving path on a TPU.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chip   # four chips: sharded store only

One chip: ``bert_base`` at its published width (12 layers, d_model 768,
12 heads, vocab 30522, random weights from ``--seed``) is calibrated with
``MemoSession.build`` in kernel mode and served through ``MemoServer``
for the int8 and the f16 codec, once at the autotuned threshold and once
with every layer forced to hit; then ``gpt2_small`` at its published
width serves memoized prefill requests (``submit(prefill=True)``) and
decodes greedily from the caches they hand back.

Four chips: a ``ShardedMemoStore`` over the four devices against a
one-chip store at equal total budget (hit gap, occupancy imbalance,
fetched-payload parity), and ``bert_base`` served in kernel mode
through the sharded store against the select reference.

Every check that fails is printed as a ``FAIL`` line and the script
exits 1. It exits 2, printing no result, when JAX finds no TPU. The last
line of a passing run is ``{"ok": true, "device": {...}}``.

Parity is measured with both legs under
``jax.default_matmul_precision("highest")``, so a gap is the serving
path's own error (summation order, and the float16 rounding the select
reference applies to replayed APMs) and not TPU matmul rounding: the
bound is PARITY_RTOL of the reference logits' magnitude. Serving at the
default precision is checked against the same float32 reference on
all-miss traffic, where the bound is set by the plain model's own
default-vs-highest gap on the same input (PRECISION_SLACK of it).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# both legs at HIGHEST: summation order plus the float16 rounding of
# replayed APMs (2^-11 relative), compounded over 12 layers
PARITY_RTOL = 5e-3
# default precision vs the float32 reference, in units of the plain
# model's own default-vs-highest gap, plus 1e-3 of the logits' magnitude
PRECISION_SLACK = 4.0
BUCKETS = (64, 128)
SEQ = 128
BATCH = 8
N_REQUESTS = 32      # per serving pass, cut to the buckets in turn
DECODE_STEPS = 4     # greedy steps from each served gpt2 prefill cache
N_SHARDS = 4         # --four-chip: one store shard per chip


class Checks:
    """Collects failures instead of stopping at the first one, so a
    single run reports every broken check."""

    def __init__(self):
        self.failed = []

    def expect(self, ok, what):
        if not ok:
            self.failed.append(what)
            print(f"FAIL {what}", flush=True)
        return ok


def log(msg):
    print(msg, flush=True)


def gap(got, ref):
    """(max |got - ref|, max(1, max |ref|))."""
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return (float(np.max(np.abs(got - ref))),
            max(1.0, float(np.max(np.abs(ref)))))


def within(chk, what, got, ref, rtol=PARITY_RTOL):
    import numpy as np
    d, scale = gap(got, ref)
    ok = bool(np.all(np.isfinite(np.asarray(got, np.float32)))) \
        and d <= rtol * scale
    log(f"[parity] {what}: max|d| {d:.3e} (bound {rtol * scale:.3e})")
    chk.expect(ok, f"parity {what}: max|d| {d:.3e} > {rtol * scale:.3e}")
    return d


class HloCheck:
    """Wraps a jitted serving step: on its first call, compiles it ahead
    of time with the same arguments and records whether the program
    holds a Pallas kernel (``tpu_custom_call``)."""

    def __init__(self, fn):
        self.fn = fn
        self.custom = None

    def __call__(self, *a, **k):
        if self.custom is None:
            txt = self.fn.lower(*a, **k).compile().as_text()
            self.custom = "tpu_custom_call" in txt
        return self.fn(*a, **k)


def probe_steps(eng):
    """Wrap the memoized-layer steps ('fused' infer, 'fusedpf' prefill)
    compiled so far."""
    for key, fn in list(eng._jit_cache.items()):
        if key[0] in ("fused", "fusedpf") and not isinstance(fn, HloCheck):
            eng._jit_cache[key] = HloCheck(fn)


def check_steps(chk, eng, tag):
    seen = [(k, fn.custom) for k, fn in eng._jit_cache.items()
            if isinstance(fn, HloCheck) and fn.custom is not None]
    chk.expect(bool(seen), f"{tag}: no memoized-layer step was compiled")
    bad = [k for k, c in seen if not c]
    log(f"[{tag}] compiled memoized-layer steps: {len(seen)}, "
        f"with tpu_custom_call: {len(seen) - len(bad)}")
    chk.expect(not bad, f"{tag}: steps without tpu_custom_call: {bad}")


def check_engine(chk, eng, tag):
    log(f"[{tag}] kernel impl {eng._kernel_impl}, "
        f"interpret {eng._interpret}")
    chk.expect(eng._kernel_impl == "pallas",
               f"{tag}: kernel impl {eng._kernel_impl!r} != 'pallas'")
    chk.expect(not eng._interpret, f"{tag}: Pallas runs interpreted")


def check_health(chk, server, tag):
    log(f"[{tag}] health {server.health.value}, exact batches "
        f"{server.n_exact_batches}, batches {server.n_batches}, "
        f"maintenance errors {len(server.maintenance_errors)}")
    chk.expect(server.health.value == "healthy",
               f"{tag}: health {server.health.value}")
    chk.expect(server.n_exact_batches == 0,
               f"{tag}: {server.n_exact_batches} batches served exact")


def pad_batch(reqs, bucket):
    """The padded batch MemoServer builds for these requests (one
    bucket, rows = len(reqs))."""
    import jax.numpy as jnp
    import numpy as np
    toks = np.zeros((len(reqs), bucket), np.int32)
    lens = np.asarray([r.size for r in reqs], np.int32)
    for i, r in enumerate(reqs):
        toks[i, : r.size] = r
    return {"tokens": jnp.asarray(toks), "lengths": lens,
            "n_valid": len(reqs)}


def serve(sess, reqs, buckets=BUCKETS, *, prefill=False,
          async_maintenance=True):
    """Submit ``reqs`` to a fresh MemoServer and drain it. Returns
    (server, completions in submission order)."""
    server = sess.serve(buckets=buckets, max_batch=BATCH,
                        batch_quantum=BATCH,
                        async_maintenance=async_maintenance)
    server.warmup()
    probe_steps(sess.engine)
    comps = []
    with server:
        for toks in reqs:
            server.submit(toks, prefill=prefill)
        while server.queued:
            comps += server.step(flush=True)
        server.drain_maintenance(timeout=120)
    comps.sort(key=lambda c: c.rid)
    return server, comps


def traffic(corpus, rng, n):
    """``n`` requests from the template corpus, cut to the bucket
    lengths in turn (a hit needs an entry of the request's own length,
    so the shorter bucket hits only after admission)."""
    toks = corpus.sample(n, rng)[0]
    return [toks[i, : BUCKETS[i % len(BUCKETS)]] for i in range(n)]


def session(cfg, spec, corpus, rng, *, seed, calib_batches):
    import jax
    import jax.numpy as jnp
    from repro.memo import MemoSession
    from repro.models import build_model

    model = build_model(cfg, layer_loop="unroll")
    params = model.init(jax.random.PRNGKey(seed))
    calib = [{"tokens": jnp.asarray(corpus.sample(BATCH, rng)[0])}
             for _ in range(calib_batches)]
    sess = MemoSession.build(model, params, spec, batches=calib,
                             key=jax.random.PRNGKey(seed + 1))
    levels = sess.autotune(
        [{"tokens": jnp.asarray(corpus.sample(BATCH, rng)[0])}],
        level="aggressive")
    return sess, calib, levels


def served_vs_reference(chk, sess, reqs, thr, tag):
    """Serve ``reqs`` at ``thr`` (admission off, synchronous) and compare
    each completion with the reference on the same padded batch: select
    for memoized thresholds, the memo-off model when ``thr`` forces
    every layer to miss. Returns the server's hit rate."""
    import numpy as np
    eng = sess.engine
    sess.spec.threshold = thr
    server, comps = serve(sess, reqs, async_maintenance=False)
    check_health(chk, server, tag)
    worst = 0.0
    for b in BUCKETS:
        rows = [(r, c) for r, c in zip(reqs, comps) if c.bucket == b]
        if not rows:
            continue
        batch = pad_batch([r for r, _ in rows], b)
        if thr > 1e5:
            ref, _ = eng.infer(batch, use_memo=False)
        else:
            mode, eng.mc.mode = eng.mc.mode, "select"
            try:
                ref, _ = eng.infer(batch, threshold=thr)
            finally:
                eng.mc.mode = mode
        ref = np.asarray(ref)
        for i, (r, c) in enumerate(rows):
            d, scale = gap(c.logits, ref[i, : r.size])
            worst = max(worst, d / (PARITY_RTOL * scale))
            if not d <= PARITY_RTOL * scale:      # NaN fails too
                chk.expect(False, f"parity {tag} bucket {b} row {i}: "
                           f"max|d| {d:.3e} > {PARITY_RTOL * scale:.3e}")
    log(f"[parity] {tag}: hit rate {server.stats.memo_rate:.3f}, worst "
        f"gap {worst:.3f} of the bound ({len(comps)} requests)")
    return server.stats.memo_rate


def bert_phase(chk, cfg, codec, *, seed):
    """bert_base in kernel mode through MemoServer for one codec."""
    import jax
    import numpy as np
    from repro.data import TemplateCorpus
    from repro.memo import MemoSpec

    tag = f"bert/{codec}"
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    corpus = TemplateCorpus(vocab=cfg.vocab, seq_len=SEQ, seed=seed)
    spec = MemoSpec.flat(mode="kernel", apm_codec=codec, admit=True,
                         device_slack=2.0)
    sess, _, levels = session(cfg, spec, corpus, rng, seed=seed,
                              calib_batches=4)
    eng = sess.engine
    store = sess.store
    log(f"[{tag}] built in {time.perf_counter() - t0:.1f}s: "
        f"{len(store.db)} entries x {store.entry_nbytes} B, "
        f"threshold {levels['aggressive']:.4f} (aggressive)")
    check_engine(chk, eng, tag)

    # the main path: admission on, maintenance off-thread
    for label, thr in (("autotuned", levels["aggressive"]),
                       ("forced-hit", -1e6)):
        t1 = time.perf_counter()
        sess.spec.threshold = thr
        server, comps = serve(sess, traffic(corpus, rng, N_REQUESTS))
        rate = server.stats.memo_rate
        finite = all(np.all(np.isfinite(c.logits)) for c in comps)
        log(f"[{tag}] {label}: {len(comps)} requests in "
            f"{time.perf_counter() - t1:.1f}s, hit rate {rate:.3f}, "
            f"admitted {server.stats.n_admitted}, live "
            f"{store.live_count}")
        check_health(chk, server, f"{tag} {label}")
        chk.expect(len(comps) == N_REQUESTS,
                   f"{tag} {label}: {len(comps)}/{N_REQUESTS} completed")
        chk.expect(finite, f"{tag} {label}: non-finite logits")
        if label == "forced-hit":
            chk.expect(rate > 0, f"{tag} forced-hit: zero hit rate")
    check_steps(chk, eng, tag)

    # parity with the store frozen: both legs under HIGHEST
    sess.spec.admit = False
    reqs = traffic(corpus, rng, 2 * BATCH)
    with jax.default_matmul_precision("highest"):
        for label, thr in (("autotuned", levels["aggressive"]),
                           ("forced-hit", -1e6), ("all-miss", 1e6)):
            rate = served_vs_reference(chk, sess, reqs, thr,
                                       f"{tag} {label}")
            if label == "forced-hit":
                chk.expect(rate > 0, f"{tag} parity forced-hit: no hits")
        ref = {b: eng.infer(pad_batch([r for r in reqs if r.size == b], b),
                            use_memo=False)[0] for b in BUCKETS}
    # default precision, all-miss: bounded by the plain model's own
    # default-vs-highest gap on the same batch
    sess.spec.threshold = 1e6
    server, comps = serve(sess, reqs, async_maintenance=False)
    for b in BUCKETS:
        rows = [(r, c) for r, c in zip(reqs, comps) if c.bucket == b]
        batch = pad_batch([r for r, _ in rows], b)
        plain = np.asarray(eng.infer(batch, use_memo=False)[0])
        hi = np.asarray(ref[b])
        floor, scale = gap(plain, hi)
        got = np.stack([c.logits for _, c in rows])
        d, _ = gap(got, hi)
        bound = PRECISION_SLACK * floor + 1e-3 * scale
        log(f"[parity] {tag} default precision, all-miss, bucket {b}: "
            f"max|d| {d:.3e} vs float32; plain model {floor:.3e}; "
            f"bound {bound:.3e}")
        chk.expect(d <= bound, f"{tag} default-precision all-miss bucket "
                   f"{b}: {d:.3e} > {bound:.3e}")
    log(f"[{tag}] phase {time.perf_counter() - t0:.1f}s")


def gpt2_phase(chk, cfg, *, seed):
    """gpt2_small memoized prefill through MemoServer, then greedy decode
    from the served caches against prefill_exact's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data import TemplateCorpus
    from repro.memo import MemoSpec

    tag = "gpt2/prefill"
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    corpus = TemplateCorpus(vocab=cfg.vocab, seq_len=SEQ, seed=seed)
    spec = MemoSpec.flat(mode="bucket", apm_codec="f16", admit=False,
                         device_slack=2.0, prefill_enabled=True)
    # calibrated under HIGHEST, so a replayed prompt finds its own entry
    # and that entry holds the float32 reference's APM and K/V
    with jax.default_matmul_precision("highest"):
        sess, calib, levels = session(cfg, spec, corpus, rng, seed=seed,
                                      calib_batches=4)
    eng = sess.engine
    model = eng.model
    log(f"[{tag}] built in {time.perf_counter() - t0:.1f}s: "
        f"{len(sess.store.db)} entries x {sess.store.entry_nbytes} B, "
        f"threshold {levels['aggressive']:.4f} (aggressive)")
    check_engine(chk, eng, tag)

    # parity on replays of calibration prompts with every layer forced
    # to hit: each hit replays the prompt's own stored APM + K/V, so the
    # prefill logits and the decode from the handed-back caches must
    # match prefill_exact's (a novel prompt's hit replays a neighbour's,
    # which no exact leg reproduces)
    replay = list(np.asarray(calib[0]["tokens"]))
    with jax.default_matmul_precision("highest"):
        sess.spec.threshold = -1e6
        server, comps = serve(sess, replay, (SEQ,), prefill=True,
                              async_maintenance=False)
        check_health(chk, server, f"{tag} parity")
        rate = server.stats.memo_rate
        chk.expect(rate > 0, f"{tag} parity: no hits")
        d_pf = d_dec = 0.0
        agree = total = 0
        bound_pf = bound_dec = 0.0
        for r, c in zip(replay, comps):
            le, ce = eng.prefill_exact({"tokens": jnp.asarray(r)[None]})
            d, scale = gap(c.logits, le[0])
            d_pf, bound_pf = max(d_pf, d), max(bound_pf,
                                               PARITY_RTOL * scale)
            chk.expect(d <= PARITY_RTOL * scale,
                       f"{tag} prefill logits: {d:.3e}")
            lm, cm = jnp.asarray(c.logits)[None], c.caches
            for step in range(DECODE_STEPS):
                te = jnp.argmax(le, -1).reshape(1, 1)
                agree += int(jnp.argmax(lm, -1)[0] == te[0, 0])
                total += 1
                pos = jnp.int32(r.size + step)
                lm, cm = model.decode_step(eng.params, te, cm, pos)
                le, ce = model.decode_step(eng.params, te, ce, pos)
                d, scale = gap(lm, le)
                d_dec, bound_dec = max(d_dec, d), max(bound_dec,
                                                      PARITY_RTOL * scale)
                chk.expect(d <= PARITY_RTOL * scale,
                           f"{tag} decode step {step}: {d:.3e}")
    log(f"[parity] {tag}: hit rate {rate:.3f}; prefill max|d| {d_pf:.3e} "
        f"(bound {bound_pf:.3e}), decode max|d| {d_dec:.3e} (bound "
        f"{bound_dec:.3e}) over {DECODE_STEPS} steps, greedy agreement "
        f"{agree}/{total}")

    # the main path at default precision: replays and novel prompts,
    # admission on, maintenance off-thread
    sess.spec.threshold = levels["aggressive"]
    sess.spec.admit = True
    reqs = replay + list(corpus.sample(BATCH, rng)[0])
    server, comps = serve(sess, reqs, (SEQ,), prefill=True)
    rate = server.stats.memo_rate
    log(f"[{tag}] {len(comps)} prefill requests, hit rate {rate:.3f}, "
        f"admitted {server.stats.n_admitted}")
    check_health(chk, server, tag)
    chk.expect(rate > 0, f"{tag}: zero hit rate")
    chk.expect(len(comps) == len(reqs) and all(
        c.caches is not None and np.all(np.isfinite(c.logits))
        for c in comps), f"{tag}: a completion lacks finite logits or "
                         f"a decode cache")
    check_steps(chk, eng, tag)
    log(f"[{tag}] phase {time.perf_counter() - t0:.1f}s")


def sharded_store_leg(chk, cfg, *, seed):
    """A ShardedMemoStore over N_SHARDS devices against a one-device
    store at equal total budget: the same admission stream (the budget
    holds 3/4 of it, so both evict), the same queries."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.shard import ShardedMemoStore
    from repro.core.store import MemoStore

    dim, n_entries, n_templates, rounds = 128, 2048, 64, 8
    apm_shape = (cfg.n_heads, SEQ, SEQ)

    rng = np.random.default_rng(seed)
    templates = (rng.normal(0, 1.0, (n_templates, dim)) * 4.0).astype(
        np.float32)
    assign = rng.integers(0, n_templates, n_entries)
    embs = (templates[assign]
            + rng.normal(0, 0.05, (n_entries, dim))).astype(np.float32)
    entry = MemoStore(apm_shape, dim, codec="int8").entry_nbytes
    budget = (3 * n_entries // 4) * entry

    def build(sharded):
        kw = dict(index_kind="exact", codec="int8", capacity=256,
                  budget_bytes=budget)
        s = (ShardedMemoStore(apm_shape, dim, n_shards=N_SHARDS, **kw)
             if sharded else
             MemoStore(apm_shape, dim, device_index_kind="flat", **kw))
        arng = np.random.default_rng(seed + 1)   # identical payloads
        for i in range(0, n_entries, 256):
            n = min(256, n_entries - i)
            apms = arng.random((n, *apm_shape), np.float32)
            s.admit(apms / apms.sum(-1, keepdims=True), embs[i:i + n])
        s.sync(force_full=True)
        return s

    def leg(s, sharded):
        di, db = s.device_index, s.device_db
        if sharded:
            fn = jax.jit(lambda args, parts, q: di.search_fetch(
                q, args=args, parts=parts))
        else:
            def fn(args, parts, q):
                d2, idx = di.search_device(q, args=args)
                i0 = idx[:, 0].astype(jnp.int32)
                return d2, idx, tuple(jnp.take(p, i0, 0) for p in parts)
            fn = jax.jit(fn)
        qrng = np.random.default_rng(seed + 2)
        hits = total = 0
        parity = True
        for _ in range(rounds):
            q = templates[qrng.integers(0, n_templates, 64)]
            q = q + qrng.normal(0, 0.05, q.shape).astype(np.float32)
            q[::4] = qrng.normal(0, 8.0, q[::4].shape)    # misses
            d2, idx, rows = fn(di.search_args, db.parts,
                               jnp.asarray(q, jnp.float32))
            dist = np.sqrt(np.maximum(np.asarray(d2)[:, 0], 0.0))
            slot = np.asarray(idx)[:, 0]
            ok = (dist < 1.0) & (slot >= 0)
            hits += int(ok.sum())
            total += ok.size
            if ok.any():        # the fetched rows are the arena's rows
                want = np.asarray(s.codec.decode_rows(tuple(
                    jnp.asarray(p) for p in s.db.parts_at(slot[ok]))),
                    np.float32)
                got = np.asarray(s.codec.decode_rows(tuple(
                    np.asarray(p)[ok] for p in rows)), np.float32)
                parity &= bool(np.array_equal(got, want))
        return hits / max(1, total), parity

    t0 = time.perf_counter()
    single_rate, single_par = leg(build(False), False)
    sh = build(True)
    sh_rate, sh_par = leg(sh, True)
    st = sh.shard_stats()
    hit_gap = abs(single_rate - sh_rate)
    log(f"[shard] store: {n_entries} entries of {apm_shape} int8, budget "
        f"{budget / 1e6:.1f} MB total; hit rate one device "
        f"{single_rate:.3f}, {N_SHARDS} shards {sh_rate:.3f}, hit gap "
        f"{hit_gap:.3f}; occupancy {st['occupancy']} (imbalance "
        f"{st['imbalance']:.2f}x); payload parity {single_par and sh_par}; "
        f"{time.perf_counter() - t0:.1f}s")
    chk.expect(hit_gap <= 0.05, f"shard store hit gap {hit_gap:.3f}")
    chk.expect(st["imbalance"] <= 2.0,
               f"shard occupancy imbalance {st['imbalance']:.2f}x")
    chk.expect(single_par and sh_par, "shard store payload parity")
    chk.expect(sh_rate > 0, "shard store: zero hit rate")


def sharded_engine_leg(chk, cfg, *, seed):
    """bert_base in kernel mode over the sharded store: served logits
    against the select reference, both under HIGHEST."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data import TemplateCorpus
    from repro.memo import MemoSpec

    tag = f"shard/bert x{N_SHARDS}"
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    corpus = TemplateCorpus(vocab=cfg.vocab, seq_len=SEQ, seed=seed)
    spec = MemoSpec.flat(mode="kernel", apm_codec="int8", shards=N_SHARDS,
                         device_slack=2.0)
    sess, _, levels = session(cfg, spec, corpus, rng, seed=seed,
                              calib_batches=2)
    eng = sess.engine
    log(f"[{tag}] built in {time.perf_counter() - t0:.1f}s: "
        f"{len(sess.store.db)} entries, occupancy "
        f"{sess.store.shard_stats()['occupancy']}")
    check_engine(chk, eng, tag)
    batch = {"tokens": jnp.asarray(corpus.sample(BATCH, rng)[0])}
    with jax.default_matmul_precision("highest"):
        for label, thr in (("autotuned", levels["aggressive"]),
                           ("forced-hit", -1e6), ("all-miss", 1e6)):
            out, st = eng.infer(batch, threshold=thr)
            mode, eng.mc.mode = eng.mc.mode, "select"
            try:
                ref, _ = eng.infer(batch, threshold=thr)
            finally:
                eng.mc.mode = mode
            log(f"[{tag}] {label}: hit rate {st.memo_rate:.3f}")
            within(chk, f"{tag} {label} vs select", out, ref)
            if label == "forced-hit":
                chk.expect(st.memo_rate > 0,
                           f"{tag} forced-hit: zero hit rate")
    log(f"[{tag}] phase {time.perf_counter() - t0:.1f}s")


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded-store path on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if args.four_chip and len(devs) < N_SHARDS:
        print(f"chip_smoke: --four-chip needs {N_SHARDS} devices, found "
              f"{len(devs)}", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"jax {jax.__version__}; compile cache {cache}")
    chk = Checks()
    t0 = time.perf_counter()
    bert = get_config("bert_base")
    if args.four_chip:
        sharded_store_leg(chk, bert, seed=args.seed)
        sharded_engine_leg(chk, bert, seed=args.seed)
    else:
        for codec in ("int8", "f16"):
            bert_phase(chk, bert, codec, seed=args.seed)
        gpt2_phase(chk, get_config("gpt2_small"), seed=args.seed)
    log(f"[device] total {time.perf_counter() - t0:.1f}s, "
        f"peak_bytes_in_use {peak_bytes()}")
    if chk.failed:
        log(f"chip_smoke: {len(chk.failed)} check(s) failed")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
