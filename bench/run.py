"""One run of one benchmark cell on the chip this process starts on.

    python3 bench/run.py --workload bert_base_rope_allmiss.templ_r80 \\
        --seed 7 --seconds 30 --trace 0

Steps, in order: weights on the device from the seed; the memo store built
by calibrating on seeded traffic (``MemoSession.build``), with the
configuration's ``threshold``; the cell's own shapes warmed up by serving
a few batches; the
open loop driven through ``MemoServer.submit/step`` for ``--seconds``;
the outputs checked against the plain reference; one JSON line printed
last on standard output. ``--trace 1`` traces the window with the JAX
profiler and reports the cell's per-layer metrics in place of its
end-to-end ones.

The run fails, printing no result, where JAX finds no TPU, fewer chips
than the cell asks for, or a device kind missing from ``peaks.json``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Optional

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """JAX found no device this cell can be measured on."""


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices(chips: int, peaks_of: Callable):
    """The chips to run on: TPUs, at least ``chips`` of them, of a kind
    the peaks table knows."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    try:
        peaks = peaks_of(devs[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None
    return devs[:chips], peaks


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), holding every
    program, so that only a checkout's first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts JAX's compile events (backend compiles and persistent-cache
    reads) while ``on`` is set: the window should see none."""

    def __init__(self):
        import jax
        self.on = False
        self.counts: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_kw):
        if self.on and ("compile" in event or "cache" in event):
            self.counts[event] = self.counts.get(event, 0) + 1


@dataclass
class Context:
    """What a per-layer metric's reader (``metrics/<name>.py``) reads."""
    config: dict
    model: dict
    peaks: dict
    window: object
    counters: dict
    store: dict
    trace: Optional[dict] = None
    speedup: Optional[tuple] = None
    log: Callable = log
    work: Callable = None
    shapes: Optional[dict] = None


def memo_spec(cfg: dict):
    from repro.memo import MemoSpec
    return MemoSpec.flat(**cfg["memo"])


def pad_batch(tokens_list, rows: int):
    """Equal-length prompts as one (rows, S) batch, filler rows repeating
    the first (as the server pads)."""
    import numpy as np
    toks = np.stack(tokens_list)
    if toks.shape[0] < rows:
        toks = np.concatenate(
            [toks, np.repeat(toks[:1], rows - toks.shape[0], 0)])
    return toks


def time_steps(fn, batches, reps: int = 1) -> float:
    """Seconds per batch of ``fn`` over ``batches``, warm, each call ended
    by block_until_ready, timed over all calls together."""
    import jax
    jax.block_until_ready(fn(batches[0]))
    t = time.perf_counter()
    for _ in range(reps):
        for b in batches:
            jax.block_until_ready(fn(b))
    return (time.perf_counter() - t) / (reps * len(batches))


def memo_speedup(sess, sess_cfg, task, reqs, rows: int):
    """(memo-off, served) seconds per padded batch, on the same batches of
    the window's own prompts: the served leg is the engine's
    prepare_batch -> run_layers -> finalize (with ``prefill`` where the
    task is prefill, so the leg returns the decode caches too), the
    memo-off leg the task's plain model."""
    import jax.numpy as jnp
    import numpy as np
    eng = sess.engine
    S = max(r.tokens.size for r in reqs)
    full = [r.tokens for r in reqs if r.tokens.size == S]
    n_b = max(1, min(32, len(full) // rows))
    batches = [jnp.asarray(pad_batch(full[i * rows:(i + 1) * rows], rows))
               for i in range(n_b)]
    lens = np.full((rows,), S, np.int32)
    prefill = is_prefill(task)

    def served(tokens):
        prep = eng.prepare_batch({"tokens": tokens, "lengths": lens,
                                  "n_valid": rows}, sync_store=False,
                                 prefill=prefill)
        eng.run_layers(prep)
        return eng.finalize(prep)[0]

    off = task.memo_off(sess.model, sess.params, sess_cfg)
    reps = max(1, 32 // n_b)
    return time_steps(off, batches, reps), time_steps(served, batches, reps)


def is_prefill(task) -> bool:
    """Whether the task's requests are served as prefill (``PREFILL``)."""
    return bool(getattr(task, "PREFILL", False))


def agreement(off, logits_of: Callable, reqs, win, rows: int, seed: int,
              cap: int = 4096) -> float:
    """Share of served requests (all, or a seeded sample of ``cap``) whose
    answer equals the program's memo-off answer on the same prompt;
    ``logits_of`` takes the answer logits from what ``off`` returns."""
    import jax.numpy as jnp
    import numpy as np
    ks = sorted(win.served)
    if len(ks) > cap:
        ks = sorted(np.random.default_rng([seed, 7]).choice(
            ks, cap, replace=False).tolist())
    by_len: dict = {}
    for k in ks:
        by_len.setdefault(reqs[k].tokens.size, []).append(k)
    same = 0
    for group in by_len.values():
        for i in range(0, len(group), rows):
            part = group[i:i + rows]
            out = np.asarray(logits_of(off(jnp.asarray(pad_batch(
                [reqs[k].tokens for k in part], rows)))))
            want = np.argmax(out[: len(part)], -1)
            same += int(sum(int(w) == win.served[k].answer
                            for w, k in zip(want, part)))
    return same / max(1, len(ks))


def output_check(cfg, task, ref, model, params, reqs, kept,
                 control: bool = False) -> dict:
    """Compares what the window served for the sampled requests with the
    plain reference, run in float32 under HIGHEST precision once over each
    prompt with its served answer. ``kept`` is emptied: the task turns
    what it kept into served tokens and logit rows first (a prefill task
    decodes through the served caches), so the program's arrays are freed
    before the reference runs.

    Per served answer: ``gap``, by how much the served token's reference
    logit lies below the reference's best; and ``err``, the largest
    distance between the served logits and the reference's, in units of
    the reference logits' RMS over the sample. ``control`` also reads both at
    the same positions for the control, the int8 (W8A8) reference put in
    the program's place (never part of a benchmark run)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    keys = sorted(kept)
    prompts = [reqs[k].tokens for k in keys]
    served = task.served([kept.pop(k) for k in keys], model, params, cfg)
    mcfg = cfg["model"]
    f32 = jax.jit(lambda p, t, s: task.reference_rows(ref, p, mcfg, t, s))
    q8 = jax.jit(lambda p, t, s: task.reference_rows(ref, p, mcfg, t, s,
                                                     "int8"))

    def judge(rows, tok, logits):
        best = rows.max(-1)
        got = np.take_along_axis(rows, tok[:, :, None], -1)[..., 0]
        return (best - got).reshape(-1), \
            np.abs(logits - rows).max(-1).reshape(-1)

    out = {"gap": [], "err": [], "control_gap": [], "control_err": [],
           "scale": []}
    by_len: dict = {}
    for i, p in enumerate(prompts):
        by_len.setdefault(p.size, []).append(i)
    blocks = [g[j:j + 8] for g in by_len.values()
              for j in range(0, len(g), 8)]
    for blk in blocks:
        t = jnp.asarray(np.stack([prompts[i] for i in blk]))
        tok = np.asarray([served[i][0] for i in blk])
        lg = np.stack([served[i][1] for i in blk]).astype(np.float64)
        with jax.default_matmul_precision("highest"):
            rows = np.asarray(f32(params, t, jnp.asarray(tok)), np.float64)
        g, e = judge(rows, tok, lg)
        out["gap"].append(g)
        out["err"].append(e)
        if control:
            c_lg = np.asarray(q8(params, t, jnp.asarray(tok)), np.float64)
            g, e = judge(rows, np.argmax(c_lg, -1), c_lg)
            out["control_gap"].append(g)
            out["control_err"].append(e)
        out["scale"].append(rows.reshape(-1))
    out = {k: np.concatenate(v) if v else np.zeros(0)
           for k, v in out.items()}
    # logit errors in units of the reference logits' RMS over the sample
    scale = float(np.sqrt(np.mean(np.square(out.pop("scale"))))) or 1.0
    for k in ("err", "control_err"):
        out[k] = out[k] / scale
    out["n_requests"] = len(keys)
    return out


def check_numbers(gap, err) -> dict:
    """The numbers a configuration's limits may name, over the sampled
    served answers: the widest gap, the largest logit error and the root
    mean square of the logit errors."""
    import numpy as np
    if not gap.size:
        return {"widest_gap": float("inf"), "logit_err": float("inf"),
                "logit_err_rms": float("inf")}
    return {"widest_gap": float(gap.max()), "logit_err": float(err.max()),
            "logit_err_rms": float(np.sqrt(np.mean(np.square(err))))}


def result_line(correct, attempted, failed, metrics, dev, extra=None,
                check=None):
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": dev}
    if extra:
        out.update(extra)
    out["check"] = check or {}
    return out


class Cell:
    """One cell set up on its device: weights, built store and a warmed
    server. ``window`` drives the open loop."""

    def __init__(self, workload: str, seed: int, *, root: Path = ROOT,
                 bench: Optional[Path] = None, require_tpu: bool = True):
        t0 = time.perf_counter()
        sys.path.insert(0, str(root / "src"))
        from bench import driver, generator, registry, weights
        self.workload, self.seed, self.root = workload, seed, root
        self.bench = bench = Path(bench or registry.BENCH)
        self.spec = spec = registry.benchmark(root)
        self.cell = cell = registry.cell(workload, spec)
        self.cfg = cfg = registry.config(cell["config"], spec, root)
        self.traffic = traffic = registry.traffic(cell["traffic"], bench)
        self.task = task = registry.module("tasks", cfg["task"], bench)
        self.prefill = is_prefill(task)
        self.ref = registry.module("references", cfg["reference"], bench)

        import jax
        import jax.numpy as jnp
        if require_tpu:
            self.devs, self.peaks = devices(
                int(cell["chips"]), lambda k: registry.peaks(k, bench))
        else:
            # tests on the CPU: any known chip's peaks, so the readers run
            self.devs = jax.devices()[: int(cell["chips"])]
            self.peaks = next(iter(registry.load_json(
                bench / "peaks.json")["devices"].values()))
        dev = self.devs[0]
        cache_dir = enable_compile_cache(root) if require_tpu else "off"
        self.compiles = CompileCounter()
        log(f"[device] {dev.platform} {dev.device_kind} x{len(self.devs)}; "
            f"jax {jax.__version__}; compile cache {cache_dir}")

        from repro.memo import MemoSession

        mcfg = cfg["model"]
        self.model = registry.program(mcfg)
        self.shapes = weights.shapes_of(self.model)
        self.params = jax.block_until_ready(
            weights.make(self.model, seed, self.shapes))
        t_w = time.perf_counter()
        self.templates = generator.corpus(traffic, mcfg["vocab"], seed)
        cal = cfg["calibration"]
        self.calib = generator.calibration(self.templates,
                                           cal["sequences"], seed)
        bs = cal["batch"]
        self.sess = MemoSession.build(
            self.model, self.params, memo_spec(cfg),
            batches=[{"tokens": jnp.asarray(self.calib[i:i + bs])}
                     for i in range(0, len(self.calib), bs)],
            key=jax.random.PRNGKey(seed + 1))
        t_b = time.perf_counter()
        sv = cfg["serve"]
        self.rows = int(sv["max_batch"])
        self.server = self.sess.serve(
            buckets=tuple(sv["buckets"]), max_batch=self.rows,
            batch_quantum=int(sv["batch_quantum"]),
            max_delay=float(sv["max_delay_ms"]) * 1e-3)
        warm = self.requests(1.0, dict(traffic, arrivals="backlog",
                                       count=2 * self.rows + 1), stream=1)
        driver.run(self.server, warm, 0.0, task.answer,
                   prefill=self.prefill)
        self.server.drain_maintenance(timeout=120)
        gc.collect()
        t_wu = time.perf_counter()
        self.setup_s = t_wu - t0
        store = self.sess.store
        self.store = {"S": max(sv["buckets"]),
                      "codec": cfg["memo"]["apm_codec"],
                      "n_entries": int(store.live_count),
                      "embed_dim": int(self.sess.spec.embed_dim)}
        log(f"[setup] weights {t_w - t0:.3f}s, build {t_b - t_w:.3f}s "
            f"({len(store.db)} entries x {store.entry_nbytes} B, threshold "
            f"{self.sess.spec.runtime.threshold:.5f}), warm-up "
            f"{t_wu - t_b:.3f}s; setup_s {self.setup_s:.3f}")

    def requests(self, seconds: float, traffic: Optional[dict] = None,
                 stream: int = 0):
        from bench import generator
        return generator.requests(traffic or self.traffic, seconds,
                                  self.seed, self.templates, stream)

    def window(self, reqs, seconds: float, sample=(), trace: bool = False):
        """Drive ``reqs`` open-loop, with the garbage collector held off.
        Returns (window, counters, kept, trace or None): ``kept`` holds
        what the output check needs of the sampled requests. A traced
        window's trace also holds ``spanfold.fold``'s keys. Sets
        ``peaks_seen``: the device's peak bytes at the window's open and just
        before the first sampled completion is kept, so the peak of
        serving alone is known apart from what the check holds."""
        import jax
        from bench import driver, spanfold, tracefold
        server, task = self.server, self.task
        st = server.stats
        kept: dict = {}
        sample = set(sample)
        self.peaks_seen = {"open": self.peak_bytes()}

        def on_complete(k, comp):
            if k in sample:
                if not kept:
                    self.peaks_seen["before_sample"] = self.peak_bytes()
                kept[k] = task.keep(comp)

        before = (st.n_hits, st.n_layer_attempts, server.n_batches,
                  server.n_filler_rows)
        tdir = tr = None
        if trace:
            tdir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # the driver's spans suffice
            jax.profiler.start_trace(tdir, profiler_options=opts)
        self.compiles.counts = {}
        self.compiles.on = True
        gc.collect()
        gc.freeze()
        gc.disable()
        try:
            win = driver.run(server, reqs, seconds, task.answer,
                             on_complete=on_complete, annotate=trace,
                             prefill=self.prefill)
        finally:
            gc.enable()
            gc.unfreeze()
            self.compiles.on = False
        if trace:
            jax.profiler.stop_trace()
            xplane = tracefold.find_xplane(tdir)
            tr = tracefold.load(xplane)
            tr.update(spanfold.fold(xplane))
            shutil.rmtree(tdir, ignore_errors=True)
        server.drain_maintenance(timeout=120)
        n_b = server.n_batches - before[2]
        counters = {"n_hits": st.n_hits - before[0],
                    "n_layer_attempts": st.n_layer_attempts - before[1],
                    "n_batches": n_b,
                    "n_filler_rows": server.n_filler_rows - before[3],
                    "rows_per_batch": self.rows,
                    "n_memo_layers": len(self.sess.engine.layers)}
        log(f"[window] {len(win.served)}/{win.attempted} served, "
            f"{win.unfinished} unfinished, {n_b} batches, hit share "
            f"{counters['n_hits'] / max(1, counters['n_layer_attempts']):.4f}"
            f", generator lateness p50 "
            f"{driver.percentile(win.lateness or [0], 50) * 1e3:.3f} ms max "
            f"{max(win.lateness or [0]) * 1e3:.3f} ms; compile events in "
            f"window {self.compiles.counts or 0}; host phases over "
            f"{driver.SLOW_S * 1e3:.0f} ms: "
            + (", ".join(f"{p} at {t:.3f}s {d * 1e3:.1f} ms"
                         for p, t, d in win.slow) or "none"))
        return win, counters, kept, tr

    def peak_bytes(self) -> int:
        mem = self.devs[0].memory_stats() or {}
        return int(mem.get("peak_bytes_in_use", 0))

    def release(self):
        """Close the server and free the store, so the reference that
        follows runs on a device the program no longer holds."""
        self.server.close()
        self.server = self.sess = None
        gc.collect()


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, bench: Optional[Path] = None,
             require_tpu: bool = True) -> dict:
    """Everything after argument parsing; returns the result line.
    ``require_tpu=False`` (tests only) skips the look for a chip."""
    import numpy as np
    from bench import driver, registry, tracefold
    c = Cell(workload, seed, root=root, bench=bench,
             require_tpu=require_tpu)
    cfg, task, rows = c.cfg, c.task, c.rows
    reqs = c.requests(seconds)
    n_sample = min(int(cfg["check"]["sample"]), len(reqs))
    sample = np.random.default_rng([seed, 3]).choice(
        len(reqs), n_sample, replace=False).tolist()
    win, counters, kept, tr = c.window(reqs, seconds, sample, trace)
    peak = c.peak_bytes()
    log(f"[memory] peak_bytes_in_use {peak}; at the window's open "
        f"{c.peaks_seen['open']}, before the first kept sample "
        f"{c.peaks_seen.get('before_sample', peak)}")
    speed = None
    if trace:
        speed = memo_speedup(c.sess, cfg, task, reqs, rows)
        log(f"[speedup] memo-off {speed[0] * 1e3:.3f} ms, served "
            f"{speed[1] * 1e3:.3f} ms per batch of {rows}")
    c.release()

    t_c = time.perf_counter()
    agree = agreement(task.memo_off(c.model, c.params, cfg),
                      task.logits_of, reqs, win, rows, seed)
    out = output_check(cfg, task, c.ref, c.model, c.params, reqs, kept)
    gaps = out["gap"]
    limits = cfg["check"]["limits"]
    numbers = check_numbers(gaps, out["err"])
    check = {k: {"value": numbers[k], "limit": v} for k, v in limits.items()}
    failed = win.unfinished
    correct = (failed == 0 and len(win.served) == win.attempted
               and bool(check)
               and all(c["value"] <= c["limit"] for c in check.values()))
    log(f"[check] {out['n_requests']} sampled requests, {gaps.size} served "
        f"tokens, {int((gaps > 0).sum())} not the reference's best; "
        f"readings {numbers}; agreement with memo-off {agree:.5f}; "
        f"{time.perf_counter() - t_c:.3f}s")

    dev = c.devs[0]
    devinfo = {"platform": dev.platform, "kind": dev.device_kind,
               "count": len(c.devs), "memory_peak_bytes": peak}
    extra = {}
    if not trace:
        lat = [s.latency for s in win.served.values()]
        done = sum(s.tokens for s in win.served.values()
                   if s.done <= seconds)
        values = {
            "latency_p50_ms": driver.percentile(lat, 50) * 1e3,
            "latency_p95_ms": driver.percentile(lat, 95) * 1e3,
            "tokens_per_s": done / seconds,
            "agreement": 100.0 * agree,
            "setup_s": c.setup_s,
        }
        log("[end_to_end] " + ", ".join(f"{k} {v:.6g}"
                                        for k, v in values.items()))
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in registry.metrics_of(workload, c.spec, False)}
    else:
        steps = tracefold.spans_of(tr, "bench.step")
        lo = min((s for s, _ in steps), default=0)
        hi = max((e for _, e in steps), default=0)
        devinfo.update(busy_s=tracefold.busy_s(tr, lo, hi),
                       window_s=(hi - lo) * 1e-9)
        ctx = Context(config=cfg, model=cfg["model"],
                      peaks=c.peaks, window=win, counters=counters,
                      store=c.store, trace=tr, speedup=speed,
                      work=lambda n: registry.module("work", n, c.bench),
                      shapes=c.shapes)
        metrics = {}
        for m in registry.metrics_of(workload, c.spec, True):
            v = registry.module("metrics", m["name"], c.bench).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        extra["breakdown"] = {"device_ops": tracefold.top_ops(tr),
                              "idle_gaps": tracefold.idle_gaps(tr, lo, hi)}
    for k, v in check.items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    return result_line(correct, win.attempted, failed, metrics, devinfo,
                       extra, check)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(ROOT))
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
