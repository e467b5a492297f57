"""Open-loop serving-runtime benchmark (ISSUE 4 / DESIGN.md §2.7).

Serves one Poisson-arrival, variable-length, mid-run-drifting request
trace through the MemoServer runtime twice — synchronous batch-boundary
maintenance vs the off-thread worker — on identically rebuilt sessions,
and records throughput + p50/p99 latency + hit rate for both. Emitted
into BENCH_serve.json as the ``serve_runtime`` section; the regression
gate tracks the async/sync p99 ratio (``--check-regress``), which is
machine-independent because both legs run on the same box back to back.

Also records the **facade A/B** (ISSUE 5): per-batch serve latency
through ``MemoSession.serve()`` vs a hand-wired ``MemoServer(engine)``
(paired wall-clock ratio, recorded), plus the session layer's own
wrapper time measured in isolation as a fraction of batch time —
``facade_overhead_frac`` (~0.2–0.35% measured), hard-gated at <1% by
``--check-regress``. The public API must stay free.

Sessions are built fresh per leg (NOT the lru-shared ``built_session``):
serving mutates the store, and the A/B is only honest if both legs start
from the identical calibration state.

The **sharded leg** (ISSUE 9) lives in ``collect_sharded`` (exposed as
the ``serve_sharded`` module/section): an 8-way CPU mesh subprocess
(device count locks at first jax init) serving a database bigger than
any single shard's position budget through ``ShardedMemoStore``, vs a
single-host store at the SAME total byte budget. Records the hit-rate
gap (the cost of centroid routing), per-shard occupancy balance, search
latency, and fetched-payload parity; ``--check-regress`` ceilings the
gap at 0.05 and the imbalance at 2x (benchmarks/run.py ABS_BOUNDS).
"""
from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import trained_encoder
from repro.data import TemplateCorpus
from repro.launch.server import probe_rate, serve_trace
from repro.memo import MemoServer, MemoSession, MemoSpec

SEQ = 32
BATCH = 8
REQUESTS = 120
BUCKETS = (16, 32)


def _build_session():
    model, params, corpus = trained_encoder("bert_base", n_layers=2,
                                            seq_len=SEQ)
    spec = MemoSpec.flat(mode="bucket", embed_steps=120, admit=True,
                         budget_mb=256.0, recal_every=2, device_slack=8.0)
    # dedicated rng: both A/B legs must build the IDENTICAL store (the
    # shared corpus rng advances between calls)
    rng = np.random.default_rng(123)
    sess = MemoSession.build(
        model, params, spec,
        batches=[{"tokens": jnp.asarray(corpus.sample(BATCH, rng)[0])}
                 for _ in range(4)],
        key=jax.random.PRNGKey(1))
    sess.autotune([{"tokens": jnp.asarray(corpus.sample(BATCH, rng)[0])}],
                  level="aggressive")
    return sess, corpus


def _workload(corpus, rate: float):
    """Poisson arrivals; two lengths per bucket (so the length-gated
    store adapts quickly and both legs reach the same steady hit rate);
    corpus drifts at the midpoint — the phase where maintenance
    (admission + delta sync + recal) is busiest."""
    rng = np.random.default_rng(7)
    drifted = TemplateCorpus(vocab=corpus.vocab, seq_len=SEQ, seed=117,
                             n_templates=corpus.n_templates,
                             slot_fraction=corpus.slot_fraction)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, REQUESTS))
    wl = []
    for i in range(REQUESTS):
        src = corpus if i < REQUESTS // 2 else drifted
        bucket = int(rng.choice(BUCKETS))
        length = bucket - int(rng.choice([0, 2]))
        wl.append((float(arrivals[i]), src.sample(1, rng)[0][0, :length]))
    return wl


def _facade_ab(sess: MemoSession, corpus, rounds: int = 16,
               reps: int = 3, wrapper_reps: int = 2000):
    """The session layer's serve-latency cost, measured two ways.

    **Wall-clock A/B** (recorded, not hard-gated): a hand-wired
    ``MemoServer(engine)`` (the pre-facade call pattern) vs
    ``session.serve()`` — same engine, same jit caches, same FROZEN
    store (admission paused), same tokens per round, paired min-of-reps
    ratios, median over rounds. On the CI-class boxes this distribution
    has per-round spread of ±10%+ (virtualized timing noise at ~15ms
    batch granularity), so the median swings a few percent run to run —
    it documents parity, but cannot *prove* a sub-1% bound.

    **Wrapper isolation** (the gated metric): ``session.serve()``
    returns the raw ``MemoServer`` — the per-batch serve path contains
    ZERO session-layer code (asserted here), so the thickest per-call
    wrapper the facade owns anywhere is ``session.infer`` (kwarg
    plumbing + cumulative stats merge). That wrapper is timed in
    isolation by stubbing the engine call out of it, and reported as a
    fraction of the median direct batch time:
    ``facade_overhead_frac`` ≈ 0.2–0.35% measured (wrapper ~30–50µs vs
    ~14ms batches). The ``--check-regress`` bound (<1%,
    benchmarks/run.py ABS_BOUNDS) keeps a several-fold margin and does
    not depend on differencing two large noisy timings — it fails only
    if someone adds real per-batch work to the facade, not from
    scheduler noise."""
    eng = sess.engine
    admit0 = eng.mc.admit
    eng.mc.admit = False
    rng = np.random.default_rng(3)
    try:
        direct = MemoServer(eng, buckets=BUCKETS, max_batch=BATCH,
                            async_maintenance=False)
        facade = sess.serve(buckets=BUCKETS, max_batch=BATCH,
                            async_maintenance=False)
        # the facade serves through the SAME runtime class, not a proxy:
        # per-batch serving never executes session-layer code
        assert type(facade) is MemoServer
        direct.warmup()
        facade.warmup()

        def one_batch(server, toks):
            t0 = time.perf_counter()
            for j in range(BATCH):
                server.submit(toks[j, : SEQ - 2 * (j % 2)])
            server.step(flush=True)
            return time.perf_counter() - t0

        def best_of(server, toks):
            return min(one_batch(server, toks) for _ in range(reps))

        ratios, td, tf = [], [], []
        for i in range(rounds):
            toks = corpus.sample(BATCH, rng)[0]
            if i % 2:
                f = best_of(facade, toks)
                d = best_of(direct, toks)
            else:
                d = best_of(direct, toks)
                f = best_of(facade, toks)
            td.append(d)
            tf.append(f)
            ratios.append(f / max(d, 1e-9))
        direct.close()
        facade.close()

        # wrapper isolation: session.infer with the engine stubbed out
        toks = jnp.asarray(corpus.sample(BATCH, rng)[0])
        out, st = sess.infer({"tokens": toks})      # canned return values
        real_infer = eng.infer
        eng.infer = lambda batch, **kw: (out, st)
        try:
            t0 = time.perf_counter()
            for _ in range(wrapper_reps):
                sess.infer({"tokens": toks})
            wrapper_s = (time.perf_counter() - t0) / wrapper_reps
        finally:
            eng.infer = real_infer
    finally:
        eng.mc.admit = admit0
    d_ms = float(np.median(td) * 1e3)
    return {"rounds": rounds, "reps": reps,
            "direct_p50_ms": d_ms,
            "facade_p50_ms": float(np.median(tf) * 1e3),
            "facade_over_direct": float(np.median(ratios)),
            "wrapper_us": float(wrapper_s * 1e6),
            "facade_overhead_frac": float(wrapper_s * 1e3 / max(d_ms,
                                                                1e-9))}


@functools.lru_cache(maxsize=1)
def collect():
    sess, corpus = _build_session()
    rate = probe_rate(sess, buckets=BUCKETS, max_batch=BATCH, seq=SEQ)
    # the probe serves (and admits) at real sync-mode cost, mutating the
    # store — rebuild so BOTH legs start from the identical fresh state
    sess, _ = _build_session()
    workload = _workload(corpus, rate)

    out = {"config": {"arch": "bert_base (reduced, 2 layers)",
                      "requests": REQUESTS, "rate_rps": float(rate),
                      "buckets": list(BUCKETS), "max_batch": BATCH,
                      "threshold": float(sess.spec.runtime.threshold),
                      "backend": jax.default_backend()}}
    kw = dict(buckets=BUCKETS, max_batch=BATCH, max_delay=4e-3)
    out["sync"] = serve_trace(sess, workload, async_maintenance=False,
                              **kw)
    sess2, _ = _build_session()      # identical fresh store for the A/B
    out["async"] = serve_trace(sess2, workload, async_maintenance=True,
                               **kw)
    out["p99_async_over_sync"] = (out["async"]["p99_ms"]
                                  / max(out["sync"]["p99_ms"], 1e-9))
    out["hit_rate_gap"] = abs(out["async"]["hit_rate"]
                              - out["sync"]["hit_rate"])
    # facade overhead A/B on a third fresh session (the open-loop legs
    # above mutated sess/sess2's stores mid-trace)
    sess3, corpus3 = _build_session()
    out["facade_ab"] = _facade_ab(sess3, corpus3)
    return out


# ------------------------------------------------------------- sharded leg

_SHARDED_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import numpy as np
import jax, jax.numpy as jnp
from repro.core.store import MemoStore
from repro.core.shard import ShardedMemoStore

APM, DIM = (2, 8, 8), 16
N, T, BATCH, ROUNDS, THR = 2048, 64, 64, 12, 1.0
rng = np.random.default_rng(0)

# clustered corpus: T well-separated templates, each entry a jittered
# template — queries near a template have an unambiguous nearest entry
templates = (rng.normal(0, 1.0, (T, DIM)) * 4.0).astype(np.float32)
assign = rng.integers(0, T, N)
embs = (templates[assign]
        + rng.normal(0, 0.05, (N, DIM))).astype(np.float32)
apms = rng.random((N, *APM)).astype(np.float16)

# equal TOTAL budget, sized so the live set exceeds one shard's
# positions several-fold (the big-memory acceptance shape, ISSUE 9)
entry = MemoStore(APM, DIM, codec="f16").entry_nbytes
budget = 1536 * entry


def build(sharded):
    kw = dict(index_kind="exact", codec="f16", capacity=256,
              budget_bytes=budget)
    s = (ShardedMemoStore(APM, DIM, n_shards=8, hot_k=32,
                          route_nprobe=4, **kw)
         if sharded else
         MemoStore(APM, DIM, device_index_kind="flat", **kw))
    for i in range(0, N, 256):     # identical admission stream -> both
        s.admit(apms[i:i + 256], embs[i:i + 256])   # stores evict the
    s.sync(force_full=True)                         # same slots
    return s


def queries(rng):
    # 3/4 near a template (should hit), 1/4 uniform noise (miss)
    t = templates[rng.integers(0, T, BATCH)]
    q = t + rng.normal(0, 0.05, (BATCH, DIM)).astype(np.float32)
    q[::4] = rng.normal(0, 8.0, (BATCH // 4 + 1, DIM))[: len(q[::4])]
    return jnp.asarray(q, jnp.float32)


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
    qrng = np.random.default_rng(42)     # same stream for both legs
    hits = total = 0
    times = []
    parity = True
    for r in range(ROUNDS):
        q = queries(qrng)
        jax.block_until_ready(fn(di.search_args, db.parts, q))
        t0 = time.perf_counter()
        d2, idx, rows = jax.block_until_ready(
            fn(di.search_args, db.parts, q))
        times.append(time.perf_counter() - t0)
        dist = np.sqrt(np.maximum(np.asarray(d2)[:, 0], 0.0))
        slot = np.asarray(idx)[:, 0]
        ok = (dist < THR) & (slot >= 0)
        hits += int(ok.sum())
        total += int(ok.size)
        if r == 0 and ok.any():          # fetched payload == arena rows
            want = s.codec.decode_rows(
                tuple(jnp.asarray(p)
                      for p in s.db.parts_at(slot[ok])))
            got = np.asarray(s.codec.decode_rows(
                tuple(np.asarray(p)[ok] for p in rows)), np.float32)
            parity = bool(np.allclose(got, np.asarray(want, np.float32),
                                      atol=1e-3))
    return {"hit_rate": hits / max(1, total),
            "search_us_per_q": float(np.median(times) * 1e6 / BATCH),
            "payload_parity": parity}

single = leg(build(False), False)
sh_store = build(True)
sharded = leg(sh_store, True)
st = sh_store.shard_stats()
live = int(sh_store.db.live_mask[: len(sh_store.db)].sum())
per_shard = sh_store.per_shard_budget_bytes
out = {
    "config": {"n_admitted": N, "dim": DIM, "batch": BATCH,
               "rounds": ROUNDS, "threshold": THR,
               "budget_mb": budget / 1e6, "n_shards": 8,
               "route_nprobe": 4,
               "backend": jax.default_backend()},
    "single": single,
    "sharded": dict(sharded, occupancy=st["occupancy"],
                    imbalance=st["imbalance"],
                    n_shard_evictions=st["n_shard_evictions"],
                    n_spills=st["n_spills"],
                    per_shard_budget_mb=per_shard / 1e6,
                    db_over_shard_budget=live * entry / per_shard),
    "hit_gap": abs(single["hit_rate"] - sharded["hit_rate"]),
    "payload_parity": bool(single["payload_parity"]
                           and sharded["payload_parity"]),
}
assert out["sharded"]["db_over_shard_budget"] > 1.0, out
print("SHARDBENCH", json.dumps(out))
"""


@functools.lru_cache(maxsize=1)
def collect_sharded():
    """8-way mesh sharded-store leg, in a subprocess (the parent jax
    already initialized with the default device count). The child is
    pinned to the CPU: its mesh is virtual, and on an accelerator host
    the parent already holds the chip."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", _SHARDED_CODE],
                         capture_output=True, text=True, env=env,
                         cwd=repo, timeout=900)
    for line in out.stdout.splitlines():
        if line.startswith("SHARDBENCH "):
            return json.loads(line[len("SHARDBENCH "):])
    raise RuntimeError(f"sharded bench subprocess failed:\n"
                       f"{out.stderr[-3000:]}")


def run_sharded():
    out = collect_sharded()
    sh, si = out["sharded"], out["single"]
    yield ("serve_sharded", sh["search_us_per_q"],
           f"hit={sh['hit_rate']:.3f};single_hit={si['hit_rate']:.3f};"
           f"hit_gap={out['hit_gap']:.3f};"
           f"imbalance={sh['imbalance']:.2f};"
           f"db_over_shard={sh['db_over_shard_budget']:.1f}x;"
           f"single_us={si['search_us_per_q']:.0f};"
           f"parity={out['payload_parity']}")


def run():
    out = collect()
    for mode in ("sync", "async"):
        r = out[mode]
        yield (f"serve_runtime_{mode}", r["p99_ms"] * 1e3,
               f"p50={r['p50_ms']:.1f}ms;p99={r['p99_ms']:.1f}ms;"
               f"rps={r['throughput_rps']:.1f};"
               f"hit={r['hit_rate']:.3f}")
    yield ("serve_runtime_overlap", 0.0,
           f"p99_ratio={out['p99_async_over_sync']:.3f};"
           f"hit_gap={out['hit_rate_gap']:.3f}")
    fa = out["facade_ab"]
    yield ("serve_runtime_facade", fa["facade_p50_ms"] * 1e3,
           f"direct_p50={fa['direct_p50_ms']:.1f}ms;"
           f"wall_ratio={fa['facade_over_direct']:.3f};"
           f"wrapper={fa['wrapper_us']:.0f}us;"
           f"overhead_frac={fa['facade_overhead_frac']:.2e}")
