import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture × input shape) on the production meshes; print
memory_analysis() and cost_analysis(); extract roofline terms.

The XLA_FLAGS line above MUST run before any other import — jax locks the
device count at first init. Do NOT set it globally: smoke tests and
benchmarks should see 1 device.

Scan correction (DESIGN.md §7): HLO cost analysis counts a while body once,
so per-unit costs come from python-unrolled 1-unit vs 2-unit variants of the
same config at full width; the reported totals are
    corrected = unroll(1 unit) + (reps − 1) · [unroll(2 units) − unroll(1)]
The full scanned compile still proves lowering + provides memory_analysis.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only] [--out DIR]
"""
import argparse
import json
import time
import traceback
from dataclasses import replace

import jax

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.configs.base import EncoderConfig
from repro.launch.hlo_utils import collective_bytes, cost_summary
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step

ASSIGNED = [a for a in ARCH_IDS if a not in ("bert_base", "gpt2_small")]


def unit_info(cfg):
    unit = len(cfg.layer_pattern) if cfg.layer_pattern != ("mix",) else 1
    start = cfg.dense_first_n
    reps = (cfg.n_layers - start) // unit
    tail = cfg.n_layers - start - reps * unit
    return unit, start, reps, tail


def small_variant(cfg, n_units: int):
    """Same config at full width with ``n_units`` scan repeats (leading
    dense layers and hybrid tails preserved)."""
    unit, start, reps, tail = unit_info(cfg)
    cfg2 = cfg.replace(n_layers=start + unit * n_units + tail)
    if cfg.encoder is not None:
        cfg2 = cfg2.replace(encoder=replace(cfg.encoder, n_layers=n_units))
    return cfg2


def lower_and_compile(arch, shape_name, mesh, *, cfg=None, layer_loop="scan",
                      rules_overrides=None, verbose=False, donate=False):
    built = build_step(arch, shape_name, mesh, rules_overrides=rules_overrides,
                       cfg=cfg)
    if built is None:
        return None, None
    built["model"].layer_loop = layer_loop
    # donate params/opt (train) or caches (decode) — the launchers'
    # production configuration; halves the resident footprint
    donate_argnums = ()
    if donate:
        kind = built["meta"]["kind"]
        donate_argnums = (0, 1) if kind == "train" else (
            (2,) if kind == "decode" else ())
    with jax.set_mesh(mesh):
        jit_fn = jax.jit(built["fn"], in_shardings=built["in_shardings"],
                         out_shardings=built["out_shardings"],
                         donate_argnums=donate_argnums)
        lowered = jit_fn.lower(*built["args"])
        compiled = lowered.compile()
    metrics = cost_summary(compiled)
    metrics["collectives"] = collective_bytes(compiled.as_text())
    if verbose:
        print("  memory_analysis:", compiled.memory_analysis())
        ca = compiled.cost_analysis() or {}
        print("  cost_analysis: flops=%.3e bytes=%.3e" % (
            ca.get("flops", 0), ca.get("bytes accessed", 0)))
    return built, metrics


def run_one(arch, shape_name, multi_pod, *, correct_scan=True,
            rules_overrides=None, verbose=True, tag="", cfg_override=None,
            donate=False):
    mesh_name = "pod512" if multi_pod else "pod256"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "devices": 512 if multi_pod else 256, "tag": tag}
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = cfg_override or get_config(arch)
    try:
        built, metrics = lower_and_compile(
            arch, shape_name, mesh, cfg=cfg_override,
            rules_overrides=rules_overrides, verbose=verbose,
            donate=donate)
        if built is None:
            rec["status"] = "skipped"
            rec["reason"] = ("long_500k needs a sub-quadratic variant; "
                             "this arch has none configured")
            return rec
        rec["meta"] = built["meta"]
        rec["full"] = metrics
        unit, start, reps, tail = unit_info(cfg)
        rec["scan_reps"] = reps
        if correct_scan and reps > 1:
            _, m1 = lower_and_compile(arch, shape_name, mesh,
                                      cfg=small_variant(cfg, 1),
                                      layer_loop="unroll",
                                      rules_overrides=rules_overrides)
            _, m2 = lower_and_compile(arch, shape_name, mesh,
                                      cfg=small_variant(cfg, 2),
                                      layer_loop="unroll",
                                      rules_overrides=rules_overrides)
            corr = {}
            for k in ("flops", "bytes", "transcendentals"):
                d = m2[k] - m1[k]
                corr[k] = m1[k] + (reps - 1) * d
            dcoll = (m2["collectives"]["total"]
                     - m1["collectives"]["total"])
            corr["collective_bytes"] = (m1["collectives"]["total"]
                                        + (reps - 1) * dcoll)
            rec["unit1"] = {k: m1[k] for k in ("flops", "bytes")}
            rec["unit1"]["collective_bytes"] = m1["collectives"]["total"]
            rec["unit2"] = {k: m2[k] for k in ("flops", "bytes")}
            rec["unit2"]["collective_bytes"] = m2["collectives"]["total"]
            rec["corrected"] = corr
        else:
            rec["corrected"] = {
                "flops": metrics["flops"], "bytes": metrics["bytes"],
                "collective_bytes": metrics["collectives"]["total"]}
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — report compile failures as data
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["elapsed_s"] = round(time.time() - t0, 1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--no-correct", action="store_true")
    ap.add_argument("--donate", action="store_true",
                    help="donate input buffers (production default; the "
                         "committed baselines are conservative non-donated)")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = [args.arch] if args.arch else ASSIGNED
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [False, True]
    if args.multi_pod or args.multi_pod_only:
        meshes = [True]
    elif args.single_pod_only:
        meshes = [False]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = f"{arch}_{shape}_{'pod512' if mp else 'pod256'}"
                path = os.path.join(args.out, key + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip existing] {key}")
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                # multi-pod pass proves lowering only; corrections are for
                # the single-pod roofline table
                rec = run_one(arch, shape, mp, donate=args.donate,
                              correct_scan=(not args.no_correct) and not mp)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                print(f"   -> {rec['status']} ({rec['elapsed_s']}s)"
                      + (f"  {rec.get('error', '')}"
                         if rec["status"] == "error" else ""), flush=True)
                results.append(rec)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    print(f"\n{n_ok} ok, {n_skip} skipped, "
          f"{len(results) - n_ok - n_skip} failed / {len(results)}")


if __name__ == "__main__":
    main()
