"""Benchmark harness (deliverable d) — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Run:
    PYTHONPATH=src python -m benchmarks.run [--only fig10,table6]
    PYTHONPATH=src python -m benchmarks.run --only serve --json BENCH_serve.json

``--json`` additionally writes a machine-readable perf trajectory: every
CSV row plus the serve fast-path detail (per-phase latency for
select/bucket/kernel with and without the device-resident path) from
``serve_fastpath.collect()`` — the baseline future PRs regress against.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    "fig1_breakdown",      # Fig 1: attention share of inference
    "fig3_similarity",     # Fig 3 + Fig 12: similarity distributions
    "fig4_threshold",      # Fig 4 + Tables 2/5: threshold/accuracy
    "table4_breakdown",    # Table 4: memo step breakdown
    "table6_gather",       # Table 6: copy vs mapping gather
    "fig10_speedup",       # Fig 10: e2e speedup x batch x level
    "table7_selective",    # Table 7: selective memoization
    "fig11_reuse",         # Fig 11: APM reuse histogram
    "fig13_dbscale",       # Fig 13: DB-size scaling
    "fig15_large_model",   # Fig 15: larger-model potential
    "ablations",           # beyond-paper: similarity knob + index ablation
    "roofline",            # deliverable (g): from the dry-run artifacts
    "serve_fastpath",      # ISSUE 1: device fast path vs host-sync serve
    "serve_online",        # ISSUE 2: MemoStore online adaptation + delta sync
    "serve_compress",      # ISSUE 3: codec x index sweep (bytes/accuracy)
    "serve_runtime",       # ISSUE 4: open-loop runtime, sync vs async maint
    "serve_faults",        # ISSUE 6: chaos classes, degradation + recovery
    "serve_sharded",       # ISSUE 9: 8-way sharded store vs single host
    "serve_prefill",       # ISSUE 10: memoized prefill + KV decode handoff
]


def _normalized_latencies(doc):
    """Serve metrics as DIMENSIONLESS ratios, so a regression check is
    meaningful across machines: fast/host-path ms normalized by the same
    run's select-reference ms, and the clustered-search inverse speedup.
    Lower is better for every key."""
    out = {}
    for level, blk in ((doc.get("serve") or {}).get("levels") or {}).items():
        base = (blk.get("modes") or {}).get("select", {}).get("host_ms")
        if not base:
            continue
        for mode, row in blk["modes"].items():
            # kernel mode included since ISSUE 7: it serves through the
            # one-matmul XLA form on CPU (engine._kernel_impl), so its
            # timings are as stable as bucket's
            for k in ("host_ms", "fast_ms"):
                if k in row:
                    out[f"serve/{level}/{mode}/{k}"] = row[k] / base
    # the fused-kernel standing (ISSUE 7): kernel-mode latency as a
    # fraction of the bucket fast path and the select reference from the
    # SAME run — dimensionless, and additionally ceiling-gated in
    # ABS_BOUNDS (kernel mode must keep beating select outright)
    for level, row in ((doc.get("serve_kernel") or {}).get("levels")
                       or {}).items():
        for k in ("kernel_over_bucket", "kernel_over_select"):
            if row.get(k):
                out[f"serve_kernel/{level}/{k}"] = row[k]
    micro = (doc.get("serve_compress") or {}).get("search_micro") or {}
    for key, row in micro.items():
        if row.get("speedup"):
            out[f"compress/search_{key}/inv_speedup"] = 1.0 / row["speedup"]
    # runtime A/B: async p99 normalized by the same run's sync p99 —
    # both legs share the box and the trace, so the ratio is the
    # machine-independent measure of the maintenance overlap win.
    # Floored at 0.5: deep-win ratios (0.0x) swing multiplicatively with
    # scheduler noise, so the gate only tracks the regime that matters —
    # async drifting toward (or past) parity with sync.
    rt = doc.get("serve_runtime") or {}
    if rt.get("p99_async_over_sync"):
        out["runtime/p99_async_over_sync"] = max(
            0.5, rt["p99_async_over_sync"])
    # facade cost (ISSUE 5): the session layer's own per-batch wrapper
    # time as a fraction of the direct batch time, measured in isolation
    # (deterministic — see serve_runtime._facade_ab). The wall-clock
    # facade/direct p50 ratio is recorded in the JSON for the trajectory
    # but NOT gated: its run-to-run spread on virtualized boxes (±2-3%)
    # dwarfs the sub-1% bound it would be checking.
    fa = rt.get("facade_ab") or {}
    if fa.get("facade_overhead_frac") is not None:
        out["runtime/facade_overhead_frac"] = fa["facade_overhead_frac"]
    # chaos classes (ISSUE 6): both keys are absolute-ceiling gates, not
    # baseline-relative — a fault class may NEVER cost a request
    # (unavailability ≤ 0) and recovery must restore the memo path
    # (post-recovery hit rate within 0.05 of the fault-free baseline).
    # p99 under faults is recorded in the JSON but not gated: it carries
    # one-off XLA compiles for the exact-attention path.
    for cls, leg in ((doc.get("serve_faults") or {}).get("classes")
                     or {}).items():
        if leg.get("availability") is not None:
            out[f"faults/{cls}/unavailability"] = 1.0 - leg["availability"]
        if leg.get("hit_recovery_gap") is not None:
            out[f"faults/{cls}/hit_recovery_gap"] = leg["hit_recovery_gap"]
    # capacity tier (DESIGN.md §2.11): a store ~10x the host budget must
    # serve within 0.05 hit rate of all-in-RAM once promotion warms up
    cap = (doc.get("serve_faults") or {}).get("capacity") or {}
    if cap.get("hit_gap") is not None:
        out["faults/capacity/hit_gap"] = cap["hit_gap"]
    # sharded store (ISSUE 9): both absolute-ceiling gates. Centroid
    # routing may cost at most 0.05 hit rate vs the single-host store at
    # the same total budget, and the greedy balanced ownership must keep
    # the fullest shard within 2x of the mean occupancy.
    sh = doc.get("serve_sharded") or {}
    if sh.get("hit_gap") is not None:
        out["sharded/hit_gap"] = sh["hit_gap"]
    if (sh.get("sharded") or {}).get("imbalance") is not None:
        out["sharded/occupancy_imbalance"] = sh["sharded"]["imbalance"]
    # prefill memoization (ISSUE 10): both absolute-ceiling gates —
    # substituting a memoized prefill hit may cost at most 5% of greedy
    # decode tokens vs the all-exact baseline, and every codec's
    # prefill/decode |Δlogits| must stay inside the kernel-parity bounds
    # (a failure count, so the ceiling is exactly zero)
    pf = doc.get("serve_prefill") or {}
    if pf.get("hit_gap") is not None:
        out["prefill/hit_gap"] = pf["hit_gap"]
    if pf.get("decode_parity_failures") is not None:
        out["prefill/decode_parity_failures"] = float(
            pf["decode_parity_failures"])
    return out


# Absolute ceilings, enforced by --check-regress INDEPENDENTLY of the
# baseline/tolerance machinery (and excluded from the relative
# comparison — a 1e-4 fraction doubling is not a regression): the
# facade contract is "<1% serve latency over the direct runtime"
# (ISSUE 5), not "no worse than last time". The measured fraction is
# ~0.2-0.35% (several-fold margin), so this only fires when someone
# adds real per-batch work to the facade.
ABS_BOUNDS = {"runtime/facade_overhead_frac": 0.01}
# chaos acceptance (ISSUE 6): zero dropped requests under every fault
# class, and post-recovery hit rate within 0.05 of the fault-free run
for _cls in ("corrupt_row", "sync_fail", "evict_bogus", "maint_crash",
             "maint_stall", "queue_overflow",
             # disk-fault classes (DESIGN.md §2.11): losing the capacity
             # tier degrades durability, never availability or recovery
             "disk_write_io", "journal_torn", "checkpoint_crash",
             "mmap_bitflip"):
    ABS_BOUNDS[f"faults/{_cls}/unavailability"] = 0.0
    ABS_BOUNDS[f"faults/{_cls}/hit_recovery_gap"] = 0.05
# big-memory acceptance (DESIGN.md §2.11): serving a store ~10x the
# host byte budget costs at most 0.05 hit rate vs all-in-RAM
ABS_BOUNDS["faults/capacity/hit_gap"] = 0.05
# fused-kernel standing (ISSUE 7): kernel mode must keep beating the
# select reference outright (measured 0.74-0.85 + ~8% runner noise) and
# stay within bucket's ballpark (measured 1.08-1.09; the ceiling fires
# if the fused dispatch regresses to the pre-ISSUE-7 0.87x-speedup
# regime, where kernel lost ~25% to bucket)
for _lvl in ("moderate", "aggressive"):
    ABS_BOUNDS[f"serve_kernel/{_lvl}/kernel_over_select"] = 1.0
    ABS_BOUNDS[f"serve_kernel/{_lvl}/kernel_over_bucket"] = 1.35
# sharded-store acceptance (ISSUE 9): an 8-way mesh serving a database
# beyond any single shard's position budget stays within 0.05 hit rate
# of the single-host store at equal total budget, with the fullest
# shard at most 2x the mean occupancy
ABS_BOUNDS["sharded/hit_gap"] = 0.05
ABS_BOUNDS["sharded/occupancy_imbalance"] = 2.0
# prefill memoization (ISSUE 10): a memoized-prefill hit hands decode a
# cache the backbone cannot tell from exact prefill's — zero per-codec
# parity-bound violations, and at most 0.05 greedy-token gap vs the
# all-exact baseline
ABS_BOUNDS["prefill/hit_gap"] = 0.05
ABS_BOUNDS["prefill/decode_parity_failures"] = 0.0


def check_regress(new_doc, baseline_path, tol=0.10):
    """Compare this run against the last recorded BENCH_serve.json:
    any normalized serve latency worse by > tol fails the run, and any
    ``ABS_BOUNDS`` key over its ceiling fails regardless of baseline.
    Only keys present in both documents enter the relative comparison
    (a missing module is not a regression)."""
    try:
        with open(baseline_path) as f:
            old_doc = json.load(f)
    except FileNotFoundError:
        print(f"# --check-regress: no baseline at {baseline_path}, skipping",
              file=sys.stderr)
        return []
    new_n = _normalized_latencies(new_doc)
    problems = []
    for key, old_v in _normalized_latencies(old_doc).items():
        if key in ABS_BOUNDS:      # absolute-ceiling keys only, below
            continue
        new_v = new_n.get(key)
        if new_v is not None and new_v > old_v * (1.0 + tol):
            problems.append({"key": key, "baseline": old_v, "new": new_v,
                             "regression": new_v / old_v - 1.0})
    for key, bound in ABS_BOUNDS.items():
        new_v = new_n.get(key)
        if new_v is not None and new_v > bound:
            problems.append({"key": key, "baseline": bound, "new": new_v,
                             "regression": new_v / bound - 1.0})
    return problems


def parity_failures(serve_doc, tag=""):
    """Bucket/kernel fast-path logits must match the select reference;
    collect every mode whose parity boolean is False so --json can fail
    loudly with a diff report instead of silently recording it."""
    bad = []
    for level, blk in (serve_doc or {}).get("levels", {}).items():
        for mode, row in blk.get("modes", {}).items():
            if row.get("logits_match_select") is False:
                bad.append({"where": f"{tag}{level}/{mode}",
                            "max_abs_diff": row.get("logits_max_abs_diff"),
                            "threshold": blk.get("threshold")})
    return bad


def kernel_parity_failures(sk_doc):
    """Same hard gate for the serve_kernel section (ISSUE 7): the fused
    dispatch's per-level parity and the per-codec (f16/int8) parity."""
    bad = []
    for level, row in (sk_doc or {}).get("levels", {}).items():
        if row.get("logits_match_select") is False:
            bad.append({"where": f"serve_kernel/{level}",
                        "max_abs_diff": row.get("logits_max_abs_diff"),
                        "threshold": row.get("threshold")})
    for codec, row in (sk_doc or {}).get("codec_parity", {}).items():
        if row.get("logits_match_select") is False:
            bad.append({"where": f"serve_kernel/codec/{codec}",
                        "max_abs_diff": row.get("logits_max_abs_diff"),
                        "threshold": None})
    return bad


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module substrings")
    ap.add_argument("--json", default=None, metavar="BENCH_serve.json",
                    help="also write rows + serve fast-path detail as JSON")
    ap.add_argument("--check-regress", default=None, metavar="BASELINE.json",
                    help="compare this run's serve latencies (normalized "
                         "to the run's own select reference, so the check "
                         "is machine-independent) against a previous "
                         "BENCH_serve.json; exit nonzero on >10%% "
                         "regression")
    ap.add_argument("--regress-tol", type=float, default=0.10)
    args = ap.parse_args()
    enable_compile_cache()
    only = args.only.split(",") if args.only else None

    print("name,us_per_call,derived")
    failures = 0
    failed_modules = set()
    rows = []
    for name in MODULES:
        if only and not any(o in name for o in only):
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            for row_name, us, derived in mod.run():
                rows.append({"name": row_name, "us_per_call": us,
                             "derived": str(derived)})
                print(f"{row_name},{us:.2f},{derived}", flush=True)
            print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
        except Exception:  # noqa: BLE001
            failures += 1
            failed_modules.add(name)
            print(f"# {name} FAILED:\n{traceback.format_exc()}",
                  file=sys.stderr)
    if args.json or args.check_regress:
        doc = {"rows": rows}
        # lru-cached: free if serve_fastpath already ran; skip if it just
        # failed (lru_cache does not cache exceptions — a retry would
        # redo the multi-minute sweep only to fail the same way)
        def wanted(name):
            return ((only is None or any(o in name for o in only))
                    and name not in failed_modules)

        detail_sections = [("serve", "serve_fastpath", "collect"),
                           ("serve_kernel", "serve_fastpath",
                            "collect_kernel"),
                           ("serve_online", "serve_online", "collect"),
                           ("serve_compress", "serve_compress", "collect"),
                           ("serve_runtime", "serve_runtime", "collect"),
                           ("serve_faults", "serve_faults", "collect"),
                           ("serve_sharded", "serve_sharded", "collect"),
                           ("serve_prefill", "serve_prefill", "collect")]
        for doc_key, mod_name, fn_name in detail_sections:
            if not wanted(mod_name):
                continue
            try:
                mod = importlib.import_module(f"benchmarks.{mod_name}")
                doc[doc_key] = getattr(mod, fn_name)()
            except Exception:  # noqa: BLE001
                print(f"# {doc_key} detail FAILED:\n"
                      f"{traceback.format_exc()}", file=sys.stderr)
                failures += 1
        if args.check_regress:
            bad = check_regress(doc, args.check_regress,
                                tol=args.regress_tol)
            if bad:
                failures += 1
                print("# LATENCY REGRESSION vs "
                      f"{args.check_regress} (tol {args.regress_tol:.0%}):",
                      file=sys.stderr)
                for b in bad:
                    print(f"#   {b['key']}: {b['baseline']:.3f} -> "
                          f"{b['new']:.3f} (+{b['regression']:.0%})",
                          file=sys.stderr)
                doc["latency_regressions"] = bad
            else:
                print(f"# --check-regress vs {args.check_regress}: OK",
                      file=sys.stderr)
        # fast-path parity is a HARD gate: divergence from the select
        # reference exits nonzero with a diff report, not just a boolean
        # buried in the JSON
        bad = (parity_failures(doc.get("serve"))
               + kernel_parity_failures(doc.get("serve_kernel")))
        if bad:
            failures += 1
            print("# PARITY FAILURE: fast-path logits diverged from the "
                  "select reference beyond tolerance:", file=sys.stderr)
            for b in bad:
                print(f"#   {b['where']} (thr={b['threshold']}): "
                      f"max|Δlogits| = {b['max_abs_diff']}",
                      file=sys.stderr)
            doc["parity_failures"] = bad
        if args.json:
            with open(args.json, "w") as f:
                json.dump(doc, f, indent=2, sort_keys=True)
            print(f"# wrote {args.json}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
