"""Serve latency: device-resident fast path vs host-synchronous path.

The ISSUE-1 acceptance benchmark: end-to-end ``infer`` latency for
``select``/``bucket``/``kernel`` modes with and without the device fast
path on the reduced bert_base config (CPU, interpret mode), plus a
per-phase breakdown (embed / search / fetch / attn). The host path's
phases come from its per-layer timers; the fused device path has no
per-layer timers by design (that is the point), so its phases are
microbenchmarked on the same tensors.

Emitted as machine-readable JSON by ``python -m benchmarks.run
--json BENCH_serve.json`` for the perf trajectory. ``collect_kernel``
adds the ``serve_kernel`` family (ISSUE 7): kernel-mode latency ratios
vs bucket and select plus a modeled HBM-bytes-moved account of the
fused dispatch. Standalone:

    python -m benchmarks.serve_fastpath --quick   # interpret-Pallas smoke
    python -m benchmarks.serve_fastpath --hw      # compiled TPU/GPU leg
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import built_engine, timeit_ms
from repro.core.engine import MemoStats

BATCH = 32
# kernel mode now serves through the one-matmul XLA form on CPU
# (engine._kernel_impl), so its timings are as stable as bucket's
REPS = {"select": 8, "bucket": 8, "kernel": 8}


def _median_ms(eng, toks, thr, reps):
    ts = []
    st = MemoStats()
    for _ in range(reps + 2):
        t0 = time.perf_counter()
        logits, st = eng.infer({"tokens": toks}, threshold=thr, stats=st)
        jax.block_until_ready(logits)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts[2:]) * 1e3), st, logits


def _phase_micro(eng, toks):
    """Per-phase latencies on the serving tensors (whole batch, one
    memoizable layer): embed MLP, index search (host numpy round-trip vs
    fused device search), APM fetch (host arena gather + transfer vs
    device gather), and the attention both ways."""
    import repro.models.backbone as bb
    h = bb.embed_tokens(eng.params, toks, eng.cfg)
    positions = jnp.broadcast_to(
        jnp.arange(toks.shape[1], dtype=jnp.int32), toks.shape)
    li, kind, lp = eng._iter_layers()[0]
    x = bb.norm_apply(lp["norm1"], h, eng.cfg.norm)
    emb_dev = eng._embed(x)
    emb_np = np.asarray(emb_dev)
    idx_np = eng.index.search(emb_np, 1)[1][:, 0]
    idx_dev = jnp.asarray(idx_np, jnp.int32)
    apm = jnp.asarray(eng.db.get(idx_np, count_reuse=False))
    search_dev = jax.jit(
        lambda q, a: eng.device_index.search_device(q, args=a)[1])
    codec = eng.store.codec
    gather_dev = jax.jit(lambda parts, i: codec.decode_rows(
        tuple(jnp.take(p, i, axis=0) for p in parts)))
    return {
        "embed_ms": timeit_ms(lambda: eng._embed(x)),
        "search_host_ms": timeit_ms(lambda: eng.index.search(emb_np, 1)),
        "search_device_ms": timeit_ms(
            lambda: search_dev(emb_dev, eng.device_index.search_args)),
        "fetch_host_ms": timeit_ms(
            lambda: jnp.asarray(eng.db.get(idx_np, count_reuse=False))),
        # the hot-path fetch: compressed gather + on-device dequant
        "fetch_device_ms": timeit_ms(
            lambda: gather_dev(eng.device_db.parts, idx_dev)),
        "attn_full_ms": timeit_ms(
            lambda: eng._attn_only(lp, x, kind, positions)),
        "attn_memo_ms": timeit_ms(
            lambda: eng._memo_only(lp, x, kind, apm.astype(jnp.float32))),
    }


@functools.lru_cache(maxsize=1)
def collect():
    eng, corpus = built_engine(threshold=0.8, mode="select")
    toks = jnp.asarray(corpus.sample(BATCH)[0])
    old = (eng.mc.mode, eng.mc.device_fast_path)
    levels = {"moderate": float(eng.levels["moderate"]),
              "aggressive": float(eng.levels["aggressive"])}

    by_level = {}
    try:      # the engine is lru-shared with other benchmark modules:
        for level, thr in levels.items():      # never leak a mode switch
            eng.mc.mode, eng.mc.device_fast_path = "select", None
            ref_ms, _, ref_logits = _median_ms(eng, toks, thr,
                                               REPS["select"])
            ref_logits = np.asarray(ref_logits)
            modes = {"select": {"host_ms": ref_ms}}
            for mode in ("bucket", "kernel"):
                eng.mc.mode = mode
                eng.mc.device_fast_path = False
                host_ms, host_st, _ = _median_ms(eng, toks, thr, REPS[mode])
                eng.mc.device_fast_path = True
                fast_ms, fast_st, fast_logits = _median_ms(eng, toks, thr,
                                                           REPS[mode])
                modes[mode] = {
                    "host_ms": host_ms,
                    "fast_ms": fast_ms,
                    "speedup": host_ms / fast_ms,
                    "memo_rate": fast_st.memo_rate,
                    "host_phases_s": {"embed": host_st.t_embed,
                                      "search": host_st.t_search,
                                      "fetch": host_st.t_fetch,
                                      "attn": host_st.t_attn},
                    "logits_match_select": bool(np.allclose(
                        np.asarray(fast_logits), ref_logits, rtol=2e-3,
                        atol=2e-3)),
                    "logits_max_abs_diff": float(np.max(np.abs(
                        np.asarray(fast_logits) - ref_logits))),
                }
            by_level[level] = {"threshold": thr, "modes": modes}
        eng.mc.mode, eng.mc.device_fast_path = "select", None
        phases = _phase_micro(eng, toks)
    finally:
        eng.mc.mode, eng.mc.device_fast_path = old
    return {
        "config": {"arch": "bert_base (reduced)", "batch": BATCH,
                   "seq": int(toks.shape[1]),
                   "backend": jax.default_backend(),
                   "interpret": jax.default_backend() == "cpu"},
        "levels": by_level,
        "phase_micro_ms": phases,
    }


def _hbm_bytes_model(cfg, codec_name, B, S, n_hit):
    """Modeled HBM→VMEM bytes per memoized layer for one batch, from
    tile counts × codec bytes (what the fused dispatch's index maps
    admit — boundary refetches, ≤1 per operand per hit↔miss boundary,
    are ignored):

    * ``kernel_fused`` — the hit flag drives the index maps: a miss
      program streams Q (once per q-row) + K/V; a hit program streams
      V + its APM tiles + (int8) the per-row scale slivers, and zero
      Q/K bytes. Misses move zero DB bytes.
    * ``kernel_unfused`` — the pre-aliasing design: every program
      fetched every operand (misses speculatively streamed entry 0's
      APM row; hits still paid the full K stream).
    * ``gather_path`` — the select/bucket shape: gather + dequantize
      all B full APMs out of the DB, then stream Q/K/V for attention.
    """
    H = cfg.n_heads
    Hkv = getattr(cfg, "n_kv_heads", None) or H
    dh = cfg.d_model // H
    blk = max(8, min(128, S))
    Sp = -(-S // blk) * blk
    nq = nk = Sp // blk
    t_q = blk * dh * 4                              # f32 activations
    t_kv = blk * dh * 4
    code_b = 1 if codec_name == "int8" else 2
    t_apm = blk * blk * code_b
    sliver = blk * 2 if codec_name == "int8" else 0
    n_miss = B - n_hit
    miss = nq * t_q + nq * nk * 2 * t_kv            # Q per row, K+V stream
    hit = nq * nk * (t_kv + t_apm) + nq * sliver    # V + APM (+ scales)
    fused = H * (n_hit * hit + n_miss * miss)
    every = nq * t_q + nq * nk * (2 * t_kv + t_apm) + nq * sliver
    unfused = H * B * every
    gather = B * H * (S * S * code_b + (S * 2 if code_b == 1 else 0))
    gather_path = gather + H * B * (nq * t_q + nq * nk * 2 * t_kv)
    return {"kernel_fused": int(fused), "kernel_unfused": int(unfused),
            "gather_path": int(gather_path),
            "fused_over_unfused": fused / max(1, unfused),
            "fused_over_gather": fused / max(1, gather_path)}


def _codec_parity():
    """Kernel-mode select-parity under BOTH streamed codecs (the fused
    dispatch has a distinct tile path per codec — f16 tiles vs int8
    codes + scale slivers): a small 2-layer engine per codec, one
    kernel-mode batch vs its own select reference."""
    from benchmarks.common import trained_encoder
    from repro.data import TemplateCorpus
    from repro.memo import MemoSession, MemoSpec
    model, params, _ = trained_encoder("bert_base", n_layers=2, seq_len=32)
    corpus = TemplateCorpus(vocab=model.cfg.vocab, seq_len=32,
                            n_templates=6, slot_fraction=0.2, seed=0)
    calib = [{"tokens": jnp.asarray(corpus.sample(16)[0])}
             for _ in range(3)]
    toks = jnp.asarray(corpus.sample(16)[0])
    out = {}
    for codec in ("f16", "int8"):
        sess = MemoSession.build(
            model, params,
            MemoSpec.flat(threshold=0.8, mode="select", embed_steps=60,
                          apm_codec=codec, device_slack=4.0),
            batches=calib, key=jax.random.PRNGKey(1))
        eng = sess.engine
        thr = float(eng.suggest_levels([calib[0]])["moderate"])
        ref, _ = eng.infer({"tokens": toks}, threshold=thr)
        eng.mc.mode = "kernel"
        fast, st = eng.infer({"tokens": toks}, threshold=thr)
        out[codec] = {
            "memo_rate": st.memo_rate,
            "logits_match_select": bool(np.allclose(
                np.asarray(fast), np.asarray(ref), rtol=2e-3, atol=2e-3)),
            "logits_max_abs_diff": float(np.max(np.abs(
                np.asarray(fast) - np.asarray(ref)))),
        }
    return out


@functools.lru_cache(maxsize=1)
def collect_kernel():
    """The ``serve_kernel`` family (ISSUE 7): kernel mode's standing
    relative to the bucket fast path and the select reference, the
    modeled HBM-byte account, and select-parity under both streamed
    codecs. Reuses the lru-cached ``collect()`` sweep — free when
    serve_fastpath already ran."""
    base = collect()
    eng, corpus = built_engine(threshold=0.8, mode="select")
    S = base["config"]["seq"]
    levels = {}
    for level, blk in base["levels"].items():
        kern = blk["modes"]["kernel"]
        buck = blk["modes"]["bucket"]
        sel_ms = blk["modes"]["select"]["host_ms"]
        n_hit = int(round(kern["memo_rate"] * BATCH))
        levels[level] = {
            "threshold": blk["threshold"],
            "kernel_fast_ms": kern["fast_ms"],
            "kernel_speedup": kern["speedup"],          # host/fast, >1 wins
            "kernel_over_bucket": kern["fast_ms"] / buck["fast_ms"],
            "kernel_over_select": kern["fast_ms"] / sel_ms,
            "memo_rate": kern["memo_rate"],
            "logits_match_select": kern["logits_match_select"],
            "hbm_bytes_model": _hbm_bytes_model(
                eng.cfg, eng.store.codec.name, BATCH, S, n_hit),
        }
    return {"config": base["config"], "kernel_impl": eng._kernel_impl,
            "levels": levels, "codec_parity": _codec_parity()}


def run():
    out = collect()
    for level, blk in out["levels"].items():
        for mode, row in blk["modes"].items():
            yield (f"serve_{level}_{mode}_host", row["host_ms"] * 1e3,
                   f"rate={row.get('memo_rate', '')}")
            if "fast_ms" in row:
                yield (f"serve_{level}_{mode}_fast", row["fast_ms"] * 1e3,
                       f"speedup={row['speedup']:.2f}x "
                       f"match={row['logits_match_select']}")
    for name, ms in out["phase_micro_ms"].items():
        yield (f"serve_phase_{name}", ms * 1e3, "")
    kern = collect_kernel()
    for level, row in kern["levels"].items():
        hbm = row["hbm_bytes_model"]
        yield (f"serve_kernel_{level}", row["kernel_fast_ms"] * 1e3,
               f"vs_bucket={row['kernel_over_bucket']:.2f}x "
               f"vs_select={row['kernel_over_select']:.2f}x "
               f"hbm_fused_mb={hbm['kernel_fused'] / 1e6:.1f} "
               f"hbm_ratio={hbm['fused_over_unfused']:.2f}")


def _quick_smoke():
    """CI leg (kernel-smoke): one interpret-Pallas kernel-mode batch vs
    the select reference — compiled-path semantics under the interpreter,
    small enough to finish in seconds."""
    eng, corpus = built_engine(threshold=0.8, mode="select")
    toks = jnp.asarray(corpus.sample(8)[0])
    thr = float(eng.levels["moderate"])
    old = (eng.mc.mode, eng.mc.kernel_impl, eng.mc.device_fast_path)
    try:
        eng.mc.mode, eng.mc.device_fast_path = "select", None
        ref, _ = eng.infer({"tokens": toks}, threshold=thr)
        eng.mc.mode = "kernel"
        eng.mc.kernel_impl = "pallas"     # pin the kernel: this leg exists
        eng.mc.device_fast_path = True    # to smoke the Pallas dispatch
        out, st = eng.infer({"tokens": toks}, threshold=thr)
        ok = bool(np.allclose(np.asarray(out), np.asarray(ref),
                              rtol=2e-3, atol=2e-3))
        print(f"quick kernel smoke: parity={ok} "
              f"memo_rate={st.memo_rate:.2f} backend=interpret")
        return 0 if ok else 1
    finally:
        eng.mc.mode, eng.mc.kernel_impl, eng.mc.device_fast_path = old


def _hw_leg():
    """Real-hardware leg: the compiled (interpret=False) fused kernel on
    TPU/GPU. Fails on CPU: this leg's numbers mean nothing without an
    accelerator (the interpreter is covered by --quick and the XLA form
    by the main sweep)."""
    if jax.default_backend() == "cpu":
        print("serve_fastpath --hw: backend is cpu (no accelerator) — "
              "the compiled-kernel leg needs a TPU or GPU")
        return 1
    eng, corpus = built_engine(threshold=0.8, mode="select")
    toks = jnp.asarray(corpus.sample(BATCH)[0])
    old = (eng.mc.mode, eng.mc.kernel_impl, eng.mc.device_fast_path,
           eng.mc.interpret)
    try:
        eng.mc.mode, eng.mc.device_fast_path = "select", None
        for level in ("moderate", "aggressive"):
            thr = float(eng.levels[level])
            eng.mc.mode, eng.mc.kernel_impl = "select", None
            ref_ms, _, ref = _median_ms(eng, toks, thr, REPS["select"])
            eng.mc.mode = "kernel"
            eng.mc.kernel_impl = "pallas"
            eng.mc.interpret = False      # compiled Pallas, not interpreter
            eng.mc.device_fast_path = True
            fast_ms, st, logits = _median_ms(eng, toks, thr, REPS["kernel"])
            ok = bool(np.allclose(np.asarray(logits), np.asarray(ref),
                                  rtol=2e-3, atol=2e-3))
            print(f"hw kernel {level}: {fast_ms:.2f}ms vs select "
                  f"{ref_ms:.2f}ms ({ref_ms / fast_ms:.2f}x) "
                  f"rate={st.memo_rate:.2f} parity={ok} "
                  f"backend={jax.default_backend()}")
    finally:
        (eng.mc.mode, eng.mc.kernel_impl, eng.mc.device_fast_path,
         eng.mc.interpret) = old
    return 0


if __name__ == "__main__":
    import argparse
    import sys
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="one interpret-Pallas kernel batch vs select")
    ap.add_argument("--hw", action="store_true",
                    help="compiled-kernel leg on TPU/GPU (fails on CPU)")
    a = ap.parse_args()
    if a.quick:
        sys.exit(_quick_smoke())
    if a.hw:
        sys.exit(_hw_leg())
    for name, us, derived in run():
        print(f"{name},{us:.2f},{derived}")
