"""The open loop: submits each request at its scheduled arrival and
steps ``MemoServer`` until every request of the window has completed.

Latency is charged from the scheduled arrival (``submit(arrival=...)``),
so a stall delays every later request. A classification request's latency
ends where the server stamps it (``Completion.latency``); a prefill
request's ends when the ``step()`` that served it returns, since the
server stamps it before ``memo.complete`` cuts each request's decode
caches out of the batch, and those caches are the prefill's answer.

The driver keeps its own host spans, and under a trace also writes them
into the profiler's trace as ``TraceAnnotation`` events (``bench.submit``,
``bench.step``, ``bench.wait``), so device idle time can be attributed to
what the host was doing.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

SLOW_S = 0.1


@dataclass
class Served:
    rid: int
    arrival: float          # scheduled, seconds after the window opened
    done: float             # completion, same clock
    latency: float          # completion - scheduled arrival
    step_start: float       # start of the step() span that served it
    tokens: int
    answer: int


@dataclass
class Window:
    seconds: float
    served: Dict[int, Served] = field(default_factory=dict)
    # per batch served: (start, end, real rows, bucket, memo hits, memoized
    # layer attempts), the last two differenced from the server's stats
    steps: List[tuple] = field(default_factory=list)
    lateness: List[float] = field(default_factory=list)
    # host phases of the loop that took over ``SLOW_S``: (phase, start,
    # seconds), phase one of submit, step, answer (reading completions)
    # and wait (time asleep past what was asked)
    slow: List[tuple] = field(default_factory=list)
    attempted: int = 0
    unfinished: int = 0


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def run(server, reqs, seconds: float, answer: Callable, *,
        on_complete: Optional[Callable] = None, annotate: bool = False,
        drain_s: float = 60.0, prefill: bool = False) -> Window:
    """Serve ``reqs`` (sorted by arrival) open-loop, each submitted with
    ``prefill``. ``answer(comp)`` is read from each completion as it
    arrives; ``on_complete(rid, comp)`` may keep what the output check
    needs and must drop the rest."""
    win = Window(seconds=seconds, attempted=len(reqs))
    base = server._now()
    sleep_cap = server.max_delay
    i, n = 0, len(reqs)
    rid_of = {}
    while True:
        now = server._now() - base
        if i < n and reqs[i].arrival <= now:
            with _span("bench.submit", annotate):
                while i < n and reqs[i].arrival <= now:
                    r = reqs[i]
                    rid = server.submit(r.tokens, arrival=base + r.arrival,
                                        prefill=prefill)
                    rid_of[rid] = i
                    win.lateness.append(now - r.arrival)
                    i += 1
        st = server.stats
        h0, a0 = st.n_hits, st.n_layer_attempts
        t_s = server._now() - base
        _note(win, "submit", now, t_s - now)
        with _span("bench.step", annotate):
            got = server.step(flush=i >= n)
        t_e = server._now() - base
        _note(win, "step", t_s, t_e - t_s)
        if got:
            win.steps.append((t_s, t_e, len(got), got[0].bucket,
                              st.n_hits - h0, st.n_layer_attempts - a0))
            for c in got:
                k = rid_of.pop(c.rid)
                lat = t_e - reqs[k].arrival if prefill else c.latency
                win.served[k] = Served(
                    rid=k, arrival=reqs[k].arrival,
                    done=lat + reqs[k].arrival, latency=lat,
                    step_start=t_s, tokens=int(c.length),
                    answer=answer(c))
                if on_complete is not None:
                    on_complete(k, c)
            _note(win, "answer", t_e, server._now() - base - t_e)
            continue
        if i >= n and not server.queued:
            break
        if now > seconds + drain_s:
            win.unfinished = n - len(win.served)
            break
        if i < n:
            nap = min(max(reqs[i].arrival - now, 0.0), sleep_cap)
            t_w = server._now() - base
            with _span("bench.wait", annotate):
                time.sleep(nap)
            _note(win, "wait", t_w, server._now() - base - t_w - nap)
    return win


def _note(win: Window, phase: str, start: float, seconds: float) -> None:
    if seconds > SLOW_S:
        win.slow.append((phase, start, seconds))


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))
