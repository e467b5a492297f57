"""Serving launcher: batched request loop with optional AttMemo memoization.

    python -m repro.launch.serve --arch bert_base --reduced --requests 64
    python -m repro.launch.serve --arch gpt2_small --reduced --no-memo
    python -m repro.launch.serve --arch bert_base --reduced --online
    python -m repro.launch.serve --arch gpt2_small --reduced --prefill

``--online`` demonstrates the MemoStore lifecycle (DESIGN.md §2.5) under
drifting traffic: the request stream switches template corpus mid-run
(a new phase seed = new clause skeletons), which collapses the hit rate
of a frozen store; with online admission enabled, captured misses are
admitted under the byte budget and delta-synced to the device tier, and
the hit rate recovers. Both passes (frozen first — it does not mutate
the store — then adaptive) run the same phase schedule for an A/B.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced
from repro.data import TemplateCorpus
from repro.launch.compile_cache import enable_compile_cache
from repro.memo import LEVELS, MemoSession, MemoSpec, MemoStats
from repro.models import build_model
from repro.train.checkpoint import load_checkpoint


def _autotune_threshold(eng, corpus, args, tag):
    """Paper Table 2 levels are per-model: autotune from a FRESH sample
    of the calibration distribution (percentiles of predicted top-1
    similarity). Querying with the calibration batches themselves would
    give degenerate zero-distance percentiles, and the stock 0.97
    threshold can sit above every predicted sim (α = 0 at every layer,
    starving both serving and the selective perf model)."""
    levels = eng.suggest_levels(
        [{"tokens": jnp.asarray(corpus.sample(args.batch)[0])}])
    eng.mc.threshold = levels.get(args.level, eng.mc.threshold)
    print(f"[{tag}] autotuned threshold ({args.level}): "
          f"{eng.mc.threshold:.3f}")


def _run_phase(eng, corpus, n_batches, batch_size, st):
    """Serve one phase; returns (per-batch hit rates, ms/batch list)."""
    rates, times = [], []
    for _ in range(n_batches):
        toks = jnp.asarray(corpus.sample(batch_size)[0])
        h0, a0 = st.n_hits, st.n_layer_attempts
        t0 = time.perf_counter()
        logits, st = eng.infer({"tokens": toks}, stats=st)
        jax.block_until_ready(logits)
        times.append((time.perf_counter() - t0) * 1e3)
        rates.append((st.n_hits - h0) / max(1, st.n_layer_attempts - a0))
    return rates, times, st


def _serve_prefill(eng, model, corpus, args, calib):
    """Prefill-memoization A/B (DESIGN.md §2.13): per batch, time exact
    prefill vs memoized prefill, then decode greedily from BOTH cache
    sets and report parity — a hit must hand back a decode cache the
    backbone cannot tell apart from the one exact prefill built."""
    st = MemoStats()
    lat_memo, lat_exact = [], []
    n_batches = max(1, args.requests // args.batch)
    for _ in range(n_batches):
        batch = {"tokens": jnp.asarray(corpus.sample(args.batch)[0])}
        t0 = time.perf_counter()
        logits_e, _ = eng.prefill_exact(batch)
        jax.block_until_ready(logits_e)
        lat_exact.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        logits_m, _, st = eng.prefill(batch, stats=st)
        jax.block_until_ready(logits_m)
        lat_memo.append(time.perf_counter() - t0)

    p = np.median(lat_exact[1:] or lat_exact) * 1e3
    m = np.median(lat_memo[1:] or lat_memo) * 1e3
    print(f"[prefill] exact        {p:8.1f} ms/batch")
    print(f"[prefill] memoized     {m:8.1f} ms/batch  "
          f"({(1 - m / p) * 100:+.1f}% latency)")
    print(f"[prefill] memo rate    {st.memo_rate*100:8.1f}%  "
          f"(hits {st.n_hits}/{st.n_layer_attempts})")

    # decode parity on a REPLAY of an admitted calibration batch
    # (self-hits): on a hit the decode cache comes from the stored KV
    # entry, so the gap below is pure codec quantization — parity on the
    # novel traffic above would fold in input drift and say nothing
    # about KV fidelity. Both legs are fed the exact leg's tokens
    # (teacher forcing) so one divergent step can't snowball the logits
    # gap; agreement counts how often the memoized leg would have
    # picked the same token anyway.
    replay = calib[0]
    h0, a0 = st.n_hits, st.n_layer_attempts
    le, ce = eng.prefill_exact(replay)
    lm, cm, st = eng.prefill(replay, stats=st)
    print(f"[prefill] replay hits  {st.n_hits - h0}"
          f"/{st.n_layer_attempts - a0}")
    dmax, agree, total = 0.0, 0, 0
    t0 = time.perf_counter()
    for step in range(args.decode_steps):
        tm = jnp.argmax(lm, -1).reshape(-1)
        te = jnp.argmax(le, -1).reshape(-1)
        agree += int((tm == te).sum())
        total += int(te.shape[0])
        pos = jnp.int32(args.seq + step)
        lm, cm = model.decode_step(eng.params, te[:, None], cm, pos)
        le, ce = model.decode_step(eng.params, te[:, None], ce, pos)
        dmax = max(dmax, float(jnp.max(jnp.abs(lm - le))))
    jax.block_until_ready(lm)
    dt = time.perf_counter() - t0
    print(f"[prefill] decode       {args.decode_steps} steps x "
          f"{args.batch} rows in {dt*1e3:.1f} ms "
          f"({args.decode_steps * args.batch / dt:.0f} tok/s)")
    print(f"[prefill] parity       max|Δlogits| {dmax:.2e}, greedy "
          f"agreement {agree}/{total}")


def _serve_online(eng, corpus, args):
    """Drift-phase schedule: phase 0 = the calibration distribution, later
    phases = drifted corpora. Frozen pass first (store untouched), then
    the adaptive pass with admission + delta sync."""
    mk = lambda seed: TemplateCorpus(vocab=eng.cfg.vocab, seq_len=args.seq,
                                     seed=seed, n_templates=corpus.n_templates,
                                     slot_fraction=corpus.slot_fraction)
    phases = [corpus] + [mk(100 + 17 * i) for i in range(1, args.phases)]
    results = {}
    counts0 = eng.db.reuse_counts.copy()
    for label, admit in (("frozen", False), ("adaptive", True)):
        eng.mc.admit = admit
        # identical starting state for both passes: the frozen pass does
        # not admit/evict, but serving still warms reuse_counts (the
        # eviction clock's input) — restore them
        eng.db.reuse_counts[:] = counts0
        st = MemoStats()
        per_phase = []
        for pi, ph in enumerate(phases):
            # fresh sampling stream per pass so both passes see the same
            # requests: re-seed the phase corpus RNG
            ph._rng = np.random.default_rng(1000 + pi)
            rates, times, st = _run_phase(eng, ph, args.phase_batches,
                                          args.batch, st)
            per_phase.append((rates, times))
            tail = np.mean(rates[len(rates) // 2:])
            print(f"[online] {label:8s} phase {pi}: hit-rate "
                  f"{' '.join(f'{r:.2f}' for r in rates)}  "
                  f"(steady {tail:.2f})  {np.median(times):6.1f} ms/batch")
        results[label] = (per_phase, st)
    eng.mc.admit = False

    froz = results["frozen"][0][-1][0]
    adap = results["adaptive"][0][-1][0]
    froz_ss = float(np.mean(froz[len(froz) // 2:]))
    adap_ss = float(np.mean(adap[len(adap) // 2:]))
    s = eng.store.stats
    print(f"[online] post-drift steady-state hit rate: "
          f"adaptive {adap_ss:.2f} vs frozen {froz_ss:.2f} "
          f"({'∞' if froz_ss == 0 else f'{adap_ss / froz_ss:.1f}'}× recovery)")
    print(f"[online] store: {s.n_admitted} admitted, {s.n_evicted} evicted, "
          f"live {eng.store.live_count} "
          f"({eng.store.live_count * eng.store.entry_nbytes / 1e6:.1f} MB"
          + (f" / budget {eng.mc.budget_mb:.0f} MB" if eng.mc.budget_mb
             else "") + ")")
    print(f"[online] sync: {s.n_delta_syncs} delta ({s.bytes_delta/1e6:.2f} "
          f"MB) + {s.n_full_syncs} full ({s.bytes_full/1e6:.2f} MB) + "
          f"{s.n_noop_syncs} no-op; full-resync-per-batch would have moved "
          f"{(s.n_delta_syncs * len(eng.db) * eng.store.entry_nbytes)/1e6:.1f}"
          " MB")
    # logits parity vs the select reference on the final drifted batch
    # (admission paused so the comparison doesn't mutate the store), plus
    # prediction agreement vs the UNmemoized model — the quality check
    # that recovered hits substitute faithfully
    toks = jnp.asarray(phases[-1].sample(args.batch)[0])
    out_fast, _ = eng.infer({"tokens": toks})
    out_plain, _ = eng.infer({"tokens": toks}, use_memo=False)
    mode = eng.mc.mode
    eng.mc.mode = "select"
    out_sel, _ = eng.infer({"tokens": toks})
    eng.mc.mode = mode
    ok = np.allclose(np.asarray(out_fast), np.asarray(out_sel),
                     rtol=2e-3, atol=2e-3)
    agree = float((np.argmax(np.asarray(out_fast), -1)
                   == np.argmax(np.asarray(out_plain), -1)).mean())
    print(f"[online] logits match select: {ok}; "
          f"prediction agreement vs no-memo: {agree:.2f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bert_base")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the reduced config (--no-reduced: the "
                         "published widths)")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--level", default="moderate",
                    choices=list(LEVELS) + ["custom"])
    ap.add_argument("--threshold", type=float, default=None)
    ap.add_argument("--mode", default="bucket",
                    choices=["select", "bucket", "kernel"])
    ap.add_argument("--index", default="exact",
                    choices=["exact", "ivf", "device"])
    ap.add_argument("--codec", default="int8",
                    choices=["f16", "int8", "lowrank"],
                    help="APM storage codec for both memo tiers "
                         "(DESIGN.md §2.6)")
    ap.add_argument("--apm-rank", type=int, default=None,
                    help="lowrank codec rank (default L//8)")
    ap.add_argument("--device-index", default="auto",
                    choices=["auto", "flat", "clustered"],
                    help="device-tier search: exhaustive matmul vs "
                         "two-stage clustered (IVF); auto flips at "
                         "--cluster-crossover entries")
    ap.add_argument("--cluster-crossover", type=int, default=4096)
    ap.add_argument("--nprobe", type=int, default=16)
    ap.add_argument("--shards", type=int, default=0,
                    help="partition the device memo store over N mesh "
                         "shards (0 = single-device store); run with "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N to shard a CPU host")
    ap.add_argument("--shard-hot", type=int, default=32,
                    help="replicated hot-entry set size per shard")
    ap.add_argument("--shard-nprobe", type=int, default=None,
                    help="centroid probes per query when routing to "
                         "shards (default: the store picks)")
    ap.add_argument("--prefill", action="store_true",
                    help="memoized causal prefill (DESIGN.md §2.13): "
                         "serve prefill requests whose hits replay the "
                         "stored KV entry into a decode cache, and A/B "
                         "latency + decode parity vs exact prefill "
                         "(needs a causal arch, e.g. --arch gpt2_small)")
    ap.add_argument("--decode-steps", type=int, default=8,
                    help="--prefill: greedy decode continuation length "
                         "for the parity check")
    ap.add_argument("--kv-codec", default="auto",
                    choices=["auto", "f16", "int8", "lowrank"],
                    help="--prefill: stored-KV codec (auto follows the "
                         "APM codec: f16 base -> f16 KV, else int8)")
    ap.add_argument("--kv-rank", type=int, default=None,
                    help="--prefill: lowrank KV codec rank")
    ap.add_argument("--no-memo", action="store_true")
    ap.add_argument("--no-fast-path", action="store_true",
                    help="force the host-synchronous serving path "
                         "(per-layer lookup round-trips; A/B baseline)")
    ap.add_argument("--varlen", action="store_true",
                    help="serve variable-length padded batches (lengths "
                         "drawn per request; masks flow through memo "
                         "lookup — DESIGN.md §2.7) and check select "
                         "parity on the last batch")
    ap.add_argument("--calib-batches", type=int, default=6)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--selective", action="store_true")
    ap.add_argument("--online", action="store_true",
                    help="drift-phase schedule with online admission "
                         "(MemoStore lifecycle A/B: frozen vs adaptive)")
    ap.add_argument("--phases", type=int, default=2,
                    help="--online: number of corpus phases (first = "
                         "calibration distribution)")
    ap.add_argument("--phase-batches", type=int, default=8,
                    help="--online: batches served per phase")
    ap.add_argument("--budget-mb", type=float, default=256.0,
                    help="--online: store byte budget for admission")
    ap.add_argument("--admit-every", type=int, default=1,
                    help="--online: capture misses every Nth batch")
    ap.add_argument("--save-store", default=None, metavar="PATH",
                    help="persist the built session (store + embedder + "
                         "spec) after calibration/autotune — the "
                         "offline-database leg of warm-start serving")
    ap.add_argument("--load-store", default=None, metavar="PATH",
                    help="warm-start from a saved session instead of "
                         "calibrating (skips build + embedder training)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (get_reduced if args.reduced else get_config)(args.arch)
    if args.prefill:
        if args.online or args.varlen:
            raise SystemExit("--prefill is its own serving leg; drop "
                             "--online/--varlen")
        if not cfg.causal:
            raise SystemExit(
                f"--prefill needs a causal (decoder-only) arch; "
                f"{args.arch!r} is bidirectional — try --arch gpt2_small")
    if args.online and not cfg.n_classes:
        cfg = cfg.replace(n_classes=4)
    model = build_model(cfg, layer_loop="unroll")
    if args.ckpt:
        params, _, _ = load_checkpoint(args.ckpt)
    else:
        params = model.init(jax.random.PRNGKey(0))
    corpus = TemplateCorpus(vocab=cfg.vocab, seq_len=args.seq, seed=1)
    if args.online and not args.ckpt and cfg.n_classes:
        # a briefly-trained classifier (the paper's BERT/SST-2 analogue):
        # random-init hiddens embed poorly, which understates adaptation
        from repro.optim import adamw_init, adamw_update
        opt = adamw_init(params)

        @jax.jit
        def _step(p, o, b):
            loss, g = jax.value_and_grad(model.classify_loss)(p, b)
            p, o = adamw_update(p, g, o, lr=3e-4)
            return loss, p, o
        for b in corpus.batches(50, 32):
            b = {k: jnp.asarray(v) for k, v in b.items()}
            _, params, opt = _step(params, opt, b)
        print("[online] trained classifier head (50 steps)")

    thr = args.threshold if args.threshold is not None else LEVELS.get(
        args.level, 0.97)
    spec = MemoSpec.flat(
        threshold=thr, mode=args.mode, index_kind=args.index,
        apm_codec=args.codec, apm_rank=args.apm_rank,
        device_index=args.device_index,
        cluster_crossover=args.cluster_crossover, nprobe=args.nprobe,
        device_fast_path=False if args.no_fast_path else None,
        budget_mb=args.budget_mb if args.online else None,
        admit_every=args.admit_every,
        recal_every=2 if args.online else None,
        shards=args.shards, shard_hot=args.shard_hot,
        shard_route_nprobe=args.shard_nprobe,
        **({"prefill_enabled": True, "prefill_kv_codec": args.kv_codec,
            "prefill_kv_rank": args.kv_rank} if args.prefill else {}))
    calib = [{"tokens": jnp.asarray(corpus.sample(args.batch)[0])}
             for _ in range(args.calib_batches)]
    t0 = time.perf_counter()
    if args.load_store:
        sess = MemoSession.load(args.load_store, model, params)
        # STORAGE spec (codec/index/embed shapes) is baked into the
        # file and cannot be overridden; the saved mode supersedes
        # --mode and is re-synced into args so the branches below
        # cannot diverge from the loaded engine. SERVING-POLICY knobs
        # remain the CLI's: threshold (when given) and the online
        # admission settings are applied to the loaded spec exactly as
        # a cold build would have set them.
        print("[serve] note: storage spec (codec/index/embed) comes "
              "from the store file; --codec/--index/--device-index/"
              "--apm-rank are ignored on warm start")
        if sess.spec.runtime.mode != args.mode:
            print(f"[serve] note: saved spec mode "
                  f"{sess.spec.runtime.mode!r} supersedes --mode "
                  f"{args.mode!r}")
            args.mode = sess.spec.runtime.mode
        if args.threshold is not None:
            sess.spec.threshold = args.threshold
        if args.online:
            sess.spec.budget_mb = args.budget_mb
            sess.spec.admit_every = args.admit_every
            sess.spec.recal_every = 2
        print(f"[serve] warm start from {args.load_store} in "
              f"{time.perf_counter()-t0:.2f}s (no calibration)")
    else:
        sess = MemoSession.build(model, params, spec, batches=calib,
                                 key=jax.random.PRNGKey(1))
    eng = sess.engine
    store = sess.store
    print(f"[serve] db: {len(store.db)} entries, "
          f"{store.db.nbytes/1e6:.1f} MB ({store.codec.name}: "
          f"{store.entry_nbytes/store.logical_entry_nbytes:.2f}x f16 "
          f"bytes/entry), ready {time.perf_counter()-t0:.1f}s")
    if args.save_store and not args.online:
        if args.threshold is None:
            _autotune_threshold(eng, corpus, args, "serve")
        sess.save(args.save_store)
        print(f"[serve] session saved -> {args.save_store}")

    if args.prefill:
        if args.threshold is None:
            _autotune_threshold(eng, corpus, args, "prefill")
        _serve_prefill(eng, model, corpus, args, calib)
        return

    if args.online:
        if args.threshold is None:
            _autotune_threshold(eng, corpus, args, "online")
        if args.mode == "select":
            print("[online] note: select mode is the host reference path; "
                  "admission still works but the fast path is bucket/kernel")
        _serve_online(eng, corpus, args)
        if args.save_store:
            # the post-drift ADAPTED store is the artifact worth keeping
            sess.save(args.save_store)
            print(f"[serve] adapted session saved -> {args.save_store}")
        return

    active = None
    if args.selective:
        if args.threshold is None:
            _autotune_threshold(eng, corpus, args, "serve")
        # profiles t_overhead on the path that will serve (the fused-jit
        # lookup on the fast path); infer() below restricts memoization
        # to the layers whose predicted benefit is positive
        pm = eng.profile(calib[0])
        active = pm.active_layers()
        print(pm.summary())
        print("[serve] selective memo active layers:", active)

    if args.varlen and args.no_fast_path:
        raise SystemExit("--varlen is served by the device fast path "
                         "(or --mode select); drop --no-fast-path")
    vl_rng = np.random.default_rng(11)

    def sample_batch():
        toks = np.asarray(corpus.sample(args.batch)[0])
        if not args.varlen:
            return {"tokens": jnp.asarray(toks)}
        # a few distinct lengths per batch: pad tokens past each length
        lens = np.asarray(vl_rng.choice(
            [args.seq, args.seq - 4, args.seq // 2], args.batch), np.int32)
        for i, ln in enumerate(lens):
            toks[i, ln:] = 0
        return {"tokens": jnp.asarray(toks), "lengths": lens}

    lat_memo, lat_plain = [], []
    st = MemoStats()
    n_batches = max(1, args.requests // args.batch)
    batch = None
    for i in range(n_batches):
        batch = sample_batch()
        t0 = time.perf_counter()
        logits, _ = eng.infer(batch, use_memo=False)
        jax.block_until_ready(logits)
        lat_plain.append(time.perf_counter() - t0)
        if not args.no_memo:
            t0 = time.perf_counter()
            logits_m, st = eng.infer(batch, stats=st,
                                     active_layers=active)
            jax.block_until_ready(logits_m)
            lat_memo.append(time.perf_counter() - t0)
    if args.varlen and not args.no_memo and args.mode == "bucket":
        # padded-row parity: the fast path's mask-aware lookup + gather
        # must match the select reference on the same padded batch
        out_fast, _ = eng.infer(batch, active_layers=active)
        mode0, eng.mc.mode = eng.mc.mode, "select"
        out_sel, _ = eng.infer(batch, active_layers=active)
        eng.mc.mode = mode0
        diff = float(np.abs(np.asarray(out_fast)
                            - np.asarray(out_sel)).max())
        print(f"[serve] varlen parity vs select: max|Δlogits| = "
              f"{diff:.2e}")
    # drop warmup batch from stats
    p = np.median(lat_plain[1:] or lat_plain) * 1e3
    print(f"[serve] baseline     {p:8.1f} ms/batch")
    if not args.no_memo:
        m = np.median(lat_memo[1:] or lat_memo) * 1e3
        fast = eng._use_fast_path()
        print(f"[serve] memoized     {m:8.1f} ms/batch  "
              f"({(1 - m / p) * 100:+.1f}% latency)"
              + ("  [device fast path]" if fast else "  [host-sync path]"))
        print(f"[serve] memo rate    {st.memo_rate*100:8.1f}%  "
              f"(hits {st.n_hits}/{st.n_layer_attempts})")
        if fast:
            # fused path: no per-phase timers by design (zero per-layer
            # sync); see benchmarks/serve_fastpath.py for the breakdown
            print(f"[serve] fused serve  {st.t_total:.2f}s total "
                  f"(event-based stats, one barrier/batch)")
        else:
            print(f"[serve] overhead     embed {st.t_embed:.2f}s "
                  f"search {st.t_search:.2f}s fetch {st.t_fetch:.2f}s")
    if getattr(store, "shard_stats", None) is not None:
        ss = store.shard_stats()
        print(f"[serve] shards       {ss['n_shards']} x "
              f"{ss['positions_per_shard']} positions, occupancy "
              f"{ss['occupancy']} (imbalance {ss['imbalance']:.2f}x), "
              f"evictions {ss['n_shard_evictions']}, "
              f"spills {ss['n_spills']}")


if __name__ == "__main__":
    main()
