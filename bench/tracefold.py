"""The reduction from a profiler trace to numbers.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
small JSON-able form: per device plane, the operations that ran
(``[label, start_ns, end_ns]``), and the driver's host spans (``bench.*``
``TraceAnnotation`` events). A label is the event's name followed by its
string-valued stats, so a kernel is found by its function name wherever
the trace records it. Everything after ``load`` works on that form, which
is what the tests feed it.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

SPAN_PREFIX = "bench."
# device lines that hold one event per executed operation; "XLA Modules"
# and "Steps" lines nest whole programs around them and would count twice
OP_LINES = ("XLA Ops",)


def find_xplane(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def _label(ev) -> str:
    parts = [ev.name]
    for k, v in ev.stats:
        if isinstance(v, str) and v and len(v) < 512:
            parts.append(f"{k}={v}")
    return " ".join(parts)


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            evs = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    evs.extend([_label(e), e.start_ns, e.end_ns]
                               for e in line.events)
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.end_ns]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


# ----------------------------------------------------------- intervals
def union(intervals: Iterable[Sequence[float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted((float(a), float(b)) for a, b in intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def overlap(merged: Sequence[Tuple[float, float]], lo: float,
            hi: float) -> float:
    """Length of the merged intervals that lies inside [lo, hi]."""
    return Covered(merged).within(lo, hi)


class Covered:
    """Disjoint sorted intervals with prefix sums, so the covered length
    inside any [lo, hi] costs two bisections."""

    def __init__(self, merged: Sequence[Tuple[float, float]]):
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.cum = [0.0]
        for s, e in merged:
            self.cum.append(self.cum[-1] + (e - s))

    def _upto(self, x: float) -> float:
        """Covered length in (-inf, x]."""
        i = bisect.bisect_right(self.starts, x)     # intervals starting <= x
        if i == 0:
            return 0.0
        return self.cum[i - 1] + min(self.ends[i - 1], x) - self.starts[i - 1]

    def within(self, lo: float, hi: float) -> float:
        return max(0.0, self._upto(hi) - self._upto(lo)) if hi > lo else 0.0


def device_intervals(trace: dict, plane: str) -> list:
    return [(s, e) for _, s, e in trace["devices"][plane]]


def busy_s(trace: dict, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] (ns) during which an operation ran, averaged
    over the device planes that ran any."""
    planes = list(trace["devices"])
    if not planes:
        return 0.0
    return sum(overlap(union(device_intervals(trace, p)), lo, hi)
               for p in planes) / len(planes) * 1e-9


def spans_of(trace: dict, name: str) -> list:
    return [(s, e) for n, s, e in trace["spans"] if n == name]


def busy_in_spans(trace: dict, spans: Sequence[Tuple[float, float]]
                  ) -> Tuple[float, float]:
    """(device-busy seconds inside the spans, seconds the spans cover),
    busy averaged over device planes."""
    cover = union(spans)
    total = sum(e - s for s, e in cover) * 1e-9
    planes = list(trace["devices"])
    if not planes:
        return 0.0, total
    busy = 0.0
    for p in planes:
        cov = Covered(union(device_intervals(trace, p)))
        busy += sum(cov.within(s, e) for s, e in cover)
    return busy / len(planes) * 1e-9, total


def kernel_s(trace: dict, names: Sequence[str]) -> Tuple[float, int]:
    """(summed device seconds, event count) of the operations whose label
    holds one of ``names`` (a kernel's function name)."""
    t, n = 0.0, 0
    for evs in trace["devices"].values():
        for label, s, e in evs:
            if any(k in label for k in names):
                t += (e - s) * 1e-9
                n += 1
    return t, n


def top_ops(trace: dict, n: int = 10) -> list:
    """The device operations that took the most time: [[name, seconds]],
    summed over events of one name and averaged over planes."""
    acc: Dict[str, float] = {}
    planes = max(1, len(trace["devices"]))
    for evs in trace["devices"].values():
        for label, s, e in evs:
            key = label.split(" ", 1)[0]
            acc[key] = acc.get(key, 0.0) + (e - s) * 1e-9 / planes
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, lo: float, hi: float, n: int = 10) -> list:
    """The longest stretches of [lo, hi] with no operation on the first
    device plane, each named by the host span that overlaps it most:
    [[span name, seconds]]."""
    planes = sorted(trace["devices"])
    if not planes:
        return []
    merged = union(device_intervals(trace, planes[0]))
    gaps, cur = [], lo
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for gs, ge in gaps[:n]:
        best, best_t = "none", 0.0
        for name, s, e in trace["spans"]:
            t = min(e, ge) - max(s, gs)
            if t > best_t:
                best, best_t = name, t
        out.append([best, (ge - gs) * 1e-9])
    return out
