"""The program's own spans in a profiler trace, and what they measure.

The program marks its serving path with ``memo.*`` host spans
(``repro.core.spans``): per batch a ``memo.step`` on the serving thread
holding ``memo.assemble``, ``memo.prepare``, ``memo.run_layers`` (one
``memo.layer`` per layer), ``memo.barrier``, ``memo.drain``,
``memo.handoff`` and ``memo.complete``; and a ``memo.maintain`` per
maintenance payload. ``tracefold.load`` folds none of them, nor the
device's "XLA Modules" line that names the program each operation ran in.
``fold`` reads both from the same ``.xplane.pb`` into two more keys of
the trace form, which ``run.Cell.window`` merges into every traced
window's trace:

- ``program_spans``: ``[name, start_ns, end_ns, line, args]``, where
  ``line`` numbers the host thread that recorded the span and ``args``
  holds its int arguments;
- ``modules``: per device plane, ``[name, start_ns, end_ns]`` of each
  program that ran.

The functions after ``fold`` work on a trace that holds those keys beside
``tracefold``'s, and read nothing where the program recorded no span.
"""
from __future__ import annotations

import bisect
import re
import statistics
from typing import Dict, Iterable, List, Optional, Tuple

from bench import tracefold

PROGRAM_PREFIX = "memo."
MODULE_LINE = "XLA Modules"


def fold(path: str) -> dict:
    """``{"program_spans": [...], "modules": {plane: [...]}}`` of the
    ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules: Dict[str, list] = {}
    program: list = []
    n_lines = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            mods = [[e.name, e.start_ns, e.end_ns]
                    for line in plane.lines if line.name == MODULE_LINE
                    for e in line.events]
            if mods:
                modules[plane.name] = sorted(mods, key=lambda m: m[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                n_lines += 1
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIX):
                        # a span's args are the event's int stats
                        program.append([e.name, e.start_ns, e.end_ns,
                                        n_lines, {k: v for k, v in e.stats
                                                  if isinstance(v, int)}])
    # a parent before the spans it holds, where both start together
    return {"program_spans": sorted(program, key=lambda s: (s[1], -s[2])),
            "modules": modules}


# ---------------------------------------------------------- intervals
def gaps(cov: tracefold.Covered, lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] that no interval of ``cov`` covers."""
    out, cur = [], lo
    i = max(0, bisect.bisect_right(cov.starts, lo) - 1)
    while i < len(cov.starts) and cov.starts[i] < hi:
        if cov.ends[i] > cur:
            if cov.starts[i] > cur:
                out.append((cur, cov.starts[i]))
            cur = cov.ends[i]
        i += 1
    if cur < hi:
        out.append((cur, hi))
    return out


# ------------------------------------------------------------ programs
def program_of(module: str) -> str:
    """A program's name as the "XLA Modules" line gives it, without the
    id the profiler appends (``jit_memo_layer(42)`` -> ``jit_memo_layer``)."""
    return re.sub(r"\(\d+\)$", "", module)


def top_ops(trace: dict, n: int = 10) -> list:
    """``tracefold.top_ops``, with an operation that runs inside a program
    of its plane's "XLA Modules" line named ``<program>/<operation>``
    (``jit_memo_layer/%copy-done.2``): [[name, seconds]], summed over
    events of one name and averaged over planes."""
    acc: Dict[str, float] = {}
    planes = max(1, len(trace["devices"]))
    for plane, evs in trace["devices"].items():
        mods = trace.get("modules", {}).get(plane, [])
        m_starts = [m[1] for m in mods]
        for label, s, e in evs:
            key = label.split(" ", 1)[0]
            i = bisect.bisect_right(m_starts, s) - 1
            if i >= 0 and s < mods[i][2]:
                key = f"{program_of(mods[i][0])}/{key}"
            acc[key] = acc.get(key, 0.0) + (e - s) * 1e-9 / planes
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


# ------------------------------------------------------- program spans
def serving_lines(trace: dict) -> set:
    """The host lines (threads) that recorded a ``memo.step`` span."""
    return {sp[3] for sp in trace.get("program_spans", ())
            if sp[0] == "memo.step"}


def serving_spans(trace: dict) -> list:
    """The program spans recorded on the serving thread."""
    lines = serving_lines(trace)
    return [sp for sp in trace.get("program_spans", ()) if sp[3] in lines]


def program_steps(trace: dict) -> list:
    """Each ``memo.step`` span of the serving thread with the spans nested
    inside it on that thread, in order: ``[(step, [child, ...])]``, spans
    as ``program_spans`` holds them."""
    by_line: Dict[int, list] = {}
    for sp in serving_spans(trace):
        by_line.setdefault(sp[3], []).append(sp)
    out = []
    for sps in by_line.values():
        starts = [sp[1] for sp in sps]
        for i, st in enumerate(sps):
            if st[0] != "memo.step":
                continue
            j = bisect.bisect_right(starts, st[2])
            out.append((st, [c for c in sps[i + 1:j]
                             if c[2] <= st[2] and c[0] != "memo.step"]))
    return sorted(out, key=lambda t: t[0][1])


def innermost(spans: Iterable, lo: float, hi: float) -> Tuple[str, float]:
    """(name, overlap) of the span that overlaps [lo, hi] most; among
    equal overlaps the shortest, which nesting makes the innermost.
    ``("none", 0.0)`` where none overlaps."""
    best, best_t, best_len = "none", 0.0, float("inf")
    for sp in spans:
        t = min(sp[2], hi) - max(sp[1], lo)
        if t > best_t or (t == best_t > 0 and sp[2] - sp[1] < best_len):
            best, best_t, best_len = sp[0], t, sp[2] - sp[1]
    return best, best_t


def idle_gaps(trace: dict, lo: float, hi: float, n: int = 10) -> list:
    """``tracefold.idle_gaps``, with each gap named by the serving
    thread's innermost program span that covers most of it, and by the
    ``bench.*`` span that overlaps it most only where no program span
    overlaps it: [[span name, seconds]], the same gaps and seconds."""
    planes = sorted(trace["devices"])
    if not planes:
        return []
    found = gaps(tracefold.Covered(tracefold.union(
        tracefold.device_intervals(trace, planes[0]))), lo, hi)
    found.sort(key=lambda g: g[0] - g[1])
    serving = serving_spans(trace)
    out = []
    for gs, ge in found[:n]:
        name, t = innermost(serving, gs, ge)
        if t <= 0:
            name, _ = innermost(trace["spans"], gs, ge)
        out.append([name, (ge - gs) * 1e-9])
    return out


def _phases(step, kids) -> list:
    """The step's time cut where its child spans begin and end, each piece
    named by the innermost child covering it (``none`` where no child
    does): ``[(start, end, name)]`` in order."""
    cuts = sorted({step[1], step[2]} | {t for c in kids for t in c[1:3]
                                        if step[1] <= t <= step[2]})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        m = (a + b) / 2
        cover = [c for c in kids if c[1] <= m < c[2]]
        name = (max(cover, key=lambda c: (c[1], -c[2]))[0] if cover
                else "none")
        out.append((a, b, name))
    return out


def idle_by_phase(trace: dict) -> dict:
    """Device-idle seconds inside the serving thread's ``memo.step``
    spans, by the innermost span the serving thread was in (``none``: in
    the step but in none of its phases), averaged over device planes:
    ``{"phases": {name: s}, "idle_s", "step_s", "maintain_s"}``.
    ``maintain_s`` is the part of that idle time during which another
    thread was inside a ``memo.maintain`` span: maintenance that can hold
    the interpreter lock while the serving thread waits for it."""
    steps = program_steps(trace)
    lines = serving_lines(trace)
    worker = tracefold.Covered(tracefold.union(
        (sp[1], sp[2]) for sp in trace.get("program_spans", ())
        if sp[0] == "memo.maintain" and sp[3] not in lines))
    planes = list(trace["devices"])
    pieces = [(st, _phases(st, kids)) for st, kids in steps]
    phases: Dict[str, float] = {}
    idle = maint = 0.0
    for p in planes:
        busy = tracefold.Covered(tracefold.union(
            tracefold.device_intervals(trace, p)))
        for st, cut in pieces:
            c_starts = [a for a, _, _ in cut]
            for g0, g1 in gaps(busy, st[1], st[2]):
                idle += g1 - g0
                maint += worker.within(g0, g1)
                j = max(0, bisect.bisect_right(c_starts, g0) - 1)
                while j < len(cut) and cut[j][0] < g1:
                    a, b, name = cut[j]
                    t = min(b, g1) - max(a, g0)
                    if t > 0:
                        phases[name] = phases.get(name, 0.0) + t
                    j += 1
    k = 1e-9 / max(1, len(planes))
    return {"phases": {n: v * k for n, v in
                       sorted(phases.items(), key=lambda kv: -kv[1])},
            "idle_s": idle * k, "maintain_s": maint * k,
            "step_s": sum(st[2] - st[1] for st, _ in steps) * 1e-9}


# ------------------------------------------------------------ readings
def _child_ns(kids, name: str) -> float:
    return sum(c[2] - c[1] for c in kids if c[0] == name)


def _median_ms(values) -> Optional[float]:
    return 1e-6 * statistics.median(values) if values else None


def step_host_ms(trace: dict) -> Optional[float]:
    """Median over ``memo.step`` spans of (the step's length - its
    ``memo.barrier``'s): what a step would cost with an infinitely fast
    device."""
    return _median_ms([(st[2] - st[1]) - _child_ns(kids, "memo.barrier")
                       for st, kids in program_steps(trace)])


def dispatch_ms(trace: dict) -> Optional[float]:
    """Median over ``memo.step`` spans of its ``memo.run_layers`` length:
    the host's time issuing the layer programs."""
    return _median_ms([_child_ns(kids, "memo.run_layers")
                       for _, kids in program_steps(trace)
                       if any(c[0] == "memo.run_layers" for c in kids)])


def post_barrier_ms(trace: dict) -> Optional[float]:
    """Median over ``memo.step`` spans of (end of the step - end of its
    ``memo.barrier``): the stats drain, the maintenance hand-off and the
    split into completions, all with the device idle."""
    return _median_ms([st[2] - max(c[2] for c in kids
                                   if c[0] == "memo.barrier")
                       for st, kids in program_steps(trace)
                       if any(c[0] == "memo.barrier" for c in kids)])


def step_idle_host(trace: dict, by: Optional[dict] = None
                   ) -> Optional[float]:
    """100 x device-idle time inside ``memo.step`` spans but outside their
    ``memo.barrier`` / the steps' summed length: the idle time the host,
    not the device, is responsible for. ``step_device_idle`` less this
    is the idle time the device has while the host already waits."""
    by = by or idle_by_phase(trace)
    if by["step_s"] <= 0 or not trace["devices"]:
        return None
    host = by["idle_s"] - by["phases"].get("memo.barrier", 0.0)
    return 100.0 * host / by["step_s"]


READINGS = ("step_host_ms", "dispatch_ms", "post_barrier_ms",
            "step_idle_host")


def readings(trace: dict) -> dict:
    """The readings of ``READINGS`` that are not None, and
    ``idle_by_phase`` where the trace holds a device."""
    by = idle_by_phase(trace)
    out = {"step_host_ms": step_host_ms(trace),
           "dispatch_ms": dispatch_ms(trace),
           "post_barrier_ms": post_barrier_ms(trace),
           "step_idle_host": step_idle_host(trace, by)}
    out = {k: v for k, v in out.items() if v is not None}
    if "step_idle_host" in out:
        out["idle_by_phase"] = by
    return out
