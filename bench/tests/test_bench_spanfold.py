"""The fold of the program's own spans: ``fold`` on a synthetic profile
and on a CPU trace, gaps named by the innermost program span, operations
keyed by their program, idle time by phase and the four readings, and
the seven per-layer readers unmoved by the two keys ``fold`` adds."""
import json
from pathlib import Path

import pytest

from bench import registry, spanfold, tracefold
from bench.tests import test_bench_trace as tt

MS = 1_000_000     # ns
READERS = ("queue_wait_ms", "step_ms", "hit_rate", "memo_speedup",
           "memo_attention_roofline", "step_mfu", "step_device_idle")

# one device plane with two programs, one host thread serving and one
# maintaining
XSPACE = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_memo_layer(42)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_memo_head(7)" } }
  event_metadata { key: 3 value { id: 3 name: "%copy-done.2" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 9000000
      stats { metadata_id: 1 int64_value: 3 }
      stats { metadata_id: 2 int64_value: 512 } }
    events { metadata_id: 3 offset_ps: 700000 duration_ps: 100000 } }
  lines { id: 8 name: "python3" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 600000 duration_ps: 100000
      stats { metadata_id: 3 int64_value: 2 } } }
  event_metadata { key: 1 value { id: 1 name: "memo.step" } }
  event_metadata { key: 2 value { id: 2 name: "memo.maintain" } }
  event_metadata { key: 3 value { id: 3 name: "other.span" } }
  stat_metadata { key: 1 value { id: 1 name: "batch" } }
  stat_metadata { key: 2 value { id: 2 name: "bucket" } }
  stat_metadata { key: 3 value { id: 3 name: "depth" } } }
'''


def test_fold_reads_spans_and_modules(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    got = spanfold.fold(str(path))
    assert got["modules"] == {"/device:TPU:0": [
        ["jit_memo_layer(42)", 1000.0, 6000.0],
        ["jit_memo_head(7)", 7000.0, 8000.0]]}
    step, maint = got["program_spans"]
    assert step == ["memo.step", 500.0, 9500.0, 1,
                    {"batch": 3, "bucket": 512}]
    assert maint == ["memo.maintain", 600.0, 700.0, 2, {"depth": 2}]
    # tracefold's fold of the same file is what it was
    assert set(tracefold.load(str(path))) == {"devices", "spans"}


def test_fold_of_a_cpu_trace(tmp_path):
    """Spans opened by the program's helper on two threads land in the
    trace with their int args, one line per thread, nested in order."""
    import threading

    import jax
    from repro.core.spans import span
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        worker = threading.Thread(target=_nested, args=("maintain",))
        with span("step", batch=4, rows=8):
            with span("layer", layer=0):
                pass
            with span("layer", layer=1):
                pass
        worker.start()
        worker.join(timeout=30)
    finally:
        jax.profiler.stop_trace()
    assert not worker.is_alive()
    tr = spanfold.fold(tracefold.find_xplane(str(tmp_path)))
    names = [(s[0], s[4]) for s in tr["program_spans"]]
    assert names[:3] == [("memo.step", {"batch": 4, "rows": 8}),
                         ("memo.layer", {"layer": 0}),
                         ("memo.layer", {"layer": 1})]
    maint = [s for s in tr["program_spans"] if s[0] == "memo.maintain"]
    assert len(maint) == 1
    assert maint[0][3] not in spanfold.serving_lines(tr)
    (step, kids), = spanfold.program_steps(tr)
    assert [k[0] for k in kids] == ["memo.layer", "memo.layer"]


def _nested(name):
    from repro.core.spans import span
    with span(name):
        pass


def _program_trace():
    """``test_bench_trace._trace()`` (ops 0-3, 5-7, 9-10 ms;
    ``bench.step`` spans 0-8 and 9-12 ms) with the program's spans and
    modules: two steps, each with its phases, and a worker's
    maintenance."""
    tr = tt._trace()
    line, worker = 3, 4
    sp = [["memo.step", 0, 8 * MS, line, {"batch": 0}],
          ["memo.prepare", 0, 1 * MS, line, {}],
          ["memo.run_layers", 1 * MS, 4 * MS, line, {}],
          ["memo.layer", 1 * MS, 2 * MS, line, {"layer": 0}],
          ["memo.layer", 2 * MS, 4 * MS, line, {"layer": 1}],
          ["memo.barrier", 4 * MS, 7 * MS, line, {}],
          ["memo.drain", 7 * MS, 8 * MS, line, {}],
          ["memo.step", 9 * MS, 12 * MS, line, {"batch": 1}],
          ["memo.barrier", 9 * MS, 10 * MS, line, {}],
          ["memo.complete", 10 * MS, 11 * MS, line, {}],
          ["memo.maintain", 3 * MS, 6 * MS, worker, {}]]
    tr["program_spans"] = sorted(sp, key=lambda s: (s[1], -s[2]))
    tr["modules"] = {"/device:TPU:0": [["jit_memo_layer(1)", 0, 4 * MS],
                                       ["jit_memo_head(2)", 5 * MS, 7 * MS]]}
    return tr


def test_steps_hold_their_phases():
    steps = spanfold.program_steps(_program_trace())
    assert [s[0][4]["batch"] for s in steps] == [0, 1]
    assert [k[0] for k in steps[0][1]] == [
        "memo.prepare", "memo.run_layers", "memo.layer", "memo.layer",
        "memo.barrier", "memo.drain"]
    assert [k[0] for k in steps[1][1]] == ["memo.barrier", "memo.complete"]


def test_idle_gaps_named_by_innermost_program_span():
    tr = _program_trace()
    old = tracefold.idle_gaps(tt._trace(), 0, 12 * MS)
    new = spanfold.idle_gaps(tr, 0, 12 * MS)
    assert [g for _, g in new] == [g for _, g in old]
    # 3..5 ms: memo.layer 1 (3-4) and memo.barrier (4-5) hold 1 ms each,
    # memo.step all 2 ms; 7..9 ms: memo.step and its memo.drain hold 1 ms
    # each, the innermost wins; 10..12 ms: memo.step holds all of it
    assert new == [["memo.step", pytest.approx(2e-3)],
                   ["memo.drain", pytest.approx(2e-3)],
                   ["memo.step", pytest.approx(2e-3)]]
    # where no program span overlaps, the bench.* span names the gap
    tr["program_spans"] = [s for s in tr["program_spans"]
                           if s[1] < 8 * MS]
    assert spanfold.idle_gaps(tr, 0, 12 * MS)[2] == [
        "bench.step", pytest.approx(2e-3)]


def test_innermost_prefers_the_nested_span():
    spans = [["memo.step", 0, 10, 1, {}], ["memo.barrier", 2, 8, 1, {}]]
    assert spanfold.innermost(spans, 3, 7) == ("memo.barrier", 4)
    assert spanfold.innermost(spans, 0, 10) == ("memo.step", 10)
    assert spanfold.innermost(spans, 20, 30) == ("none", 0.0)


def test_top_ops_keyed_by_program():
    tr = _program_trace()
    top = dict(spanfold.top_ops(tr))
    assert top == pytest.approx({
        "jit_memo_layer/fusion.1": 2e-3, "jit_memo_layer/fusion.2": 2e-3,
        "jit_memo_head/custom-call.7": 2e-3, "fusion.1": 1e-3})
    # without modules, tracefold's top_ops exactly
    assert spanfold.top_ops(tt._trace()) == tracefold.top_ops(tt._trace())


def test_idle_by_phase():
    by = spanfold.idle_by_phase(_program_trace())
    # idle inside the steps: 3-5 and 7-8 ms in step 0, 10-12 ms in step 1
    assert by["idle_s"] == pytest.approx(5e-3)
    assert by["step_s"] == pytest.approx(11e-3)
    assert by["phases"] == pytest.approx({
        "memo.layer": 1e-3, "memo.barrier": 1e-3, "memo.drain": 1e-3,
        "memo.complete": 1e-3, "none": 1e-3})
    # the worker's memo.maintain (3-6 ms) overlaps the idle 3-5 ms
    assert by["maintain_s"] == pytest.approx(2e-3)


def test_readings_arithmetic():
    tr = _program_trace()
    # step 0: 8 ms, barrier 3 ms; step 1: 3 ms, barrier 1 ms
    assert spanfold.step_host_ms(tr) == pytest.approx((5 + 2) / 2)
    assert spanfold.dispatch_ms(tr) == pytest.approx(3.0)
    assert spanfold.post_barrier_ms(tr) == pytest.approx((1 + 2) / 2)
    # idle outside the barriers: 5 ms idle less 1 ms inside a barrier
    assert spanfold.step_idle_host(tr) == pytest.approx(100 * 4 / 11)
    got = spanfold.readings(tr)
    assert set(got) == set(spanfold.READINGS) | {"idle_by_phase"}


def test_readings_silent_without_program_spans():
    assert spanfold.readings(tt._trace()) == {}
    tr = _program_trace()
    tr["devices"] = {}
    assert "step_idle_host" not in spanfold.readings(tr)
    assert "step_host_ms" in spanfold.readings(tr)


DATA = Path(__file__).parent / "data" / "trace_small.json"


def _recorded():
    tr = json.loads(DATA.read_text())
    evs = tr["devices"]["/device:TPU:0"]
    lo, hi = min(e[1] for e in evs), max(e[2] for e in evs)
    tr["spans"] = [["bench.step", lo, hi]]
    return tr, lo, hi


def _with_program(tr, lo, hi):
    tr = json.loads(json.dumps(tr))
    mid = (lo + hi) / 2
    tr["program_spans"] = [["memo.step", lo, hi, 1, {"batch": 0}],
                           ["memo.barrier", mid, hi, 1, {}],
                           ["memo.maintain", lo, mid, 2, {}]]
    tr["modules"] = {p: [["jit_memo_layer(9)", lo, mid]]
                     for p in tr["devices"]}
    return tr


@pytest.mark.parametrize("which", ["synthetic", "recorded"])
def test_readers_unmoved_by_program_keys(which):
    """The seven per-layer readers read the same values from a trace that
    also holds ``program_spans`` and ``modules``."""
    if which == "synthetic":
        tr, lo, hi = tt._trace(), 0, 12 * MS
    else:
        tr, lo, hi = _recorded()
    def read(t):
        return {m: registry.module("metrics", m).read(
            tt._ctx(t, speedup=(1e-3, 4e-3))) for m in READERS}
    before = read(tr)
    assert read(_with_program(tr, lo, hi)) == before
    assert all(v is not None for v in before.values())
    # and the gaps keep their seconds
    assert [g for _, g in spanfold.idle_gaps(_with_program(tr, lo, hi),
                                             lo, hi)] == \
        [g for _, g in tracefold.idle_gaps(tr, lo, hi)]

