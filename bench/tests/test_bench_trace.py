"""The trace reduction: busy time as a union, idle share inside spans,
kernel time by name, and the roofline and MFU arithmetic of the readers,
on synthetic events and on a small trace recorded on a TPU v5e."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import registry, tracefold

MS = 1_000_000     # ns


def _trace():
    # one device: ops overlap (union, not sum), a kernel by function name
    ops = [["fusion.1", 0, 2 * MS], ["fusion.2", 1 * MS, 3 * MS],
           ["custom-call.7 kernel=_memo_kernel", 5 * MS, 7 * MS],
           ["fusion.1", 9 * MS, 10 * MS]]
    spans = [["bench.step", 0, 8 * MS], ["bench.wait", 8 * MS, 9 * MS],
             ["bench.step", 9 * MS, 12 * MS]]
    return {"devices": {"/device:TPU:0": ops}, "spans": spans}


def test_busy_is_the_union():
    tr = _trace()
    assert tracefold.busy_s(tr, 0, 12 * MS) == pytest.approx(6e-3)
    assert tracefold.busy_s(tr, 1 * MS, 6 * MS) == pytest.approx(3e-3)


def test_busy_averages_over_devices():
    tr = _trace()
    tr["devices"]["/device:TPU:1"] = [["fusion.9", 0, 12 * MS]]
    assert tracefold.busy_s(tr, 0, 12 * MS) == pytest.approx(9e-3)


def test_idle_inside_step_spans():
    tr = _trace()
    busy, total = tracefold.busy_in_spans(
        tr, tracefold.spans_of(tr, "bench.step"))
    assert total == pytest.approx(11e-3)
    assert busy == pytest.approx(6e-3)


def test_kernel_time_by_name():
    t, n = tracefold.kernel_s(_trace(), ("_memo_kernel",))
    assert (t, n) == (pytest.approx(2e-3), 1)
    assert tracefold.kernel_s(_trace(), ("_nn_kernel",)) == (0.0, 0)


def test_top_ops_and_idle_gaps():
    tr = _trace()
    top = tracefold.top_ops(tr)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(3e-3)
    gaps = tracefold.idle_gaps(tr, 0, 12 * MS)
    assert gaps[0] == ["bench.step", pytest.approx(2e-3)]   # 3..5 and 10..12
    assert ["bench.wait", pytest.approx(1e-3)] not in gaps[:1]
    assert sum(g for _, g in gaps) == pytest.approx(6e-3)


def _ctx(tr, **kw):
    model = {"name": "bert", "arch_type": "dense", "n_layers": 12,
             "d_model": 768, "n_heads": 12, "n_kv_heads": 12, "d_ff": 3072,
             "vocab": 30522, "causal": False, "n_classes": 2, "glu": False,
             "norm": "layernorm", "act": "gelu"}
    served = {i: SimpleNamespace(tokens=512, step_start=0.0, arrival=0.0)
              for i in range(8)}
    c = dict(trace=tr, model=model, config={"head": "classify"},
             peaks=registry.peaks("TPU v5 lite"),
             window=SimpleNamespace(served=served, steps=[
                 (0.0, 0.011, 8, 512, 48, 96)]),
             counters={"n_hits": 48, "n_layer_attempts": 96,
                       "n_batches": 1, "rows_per_batch": 8,
                       "n_memo_layers": 12},
             store={"S": 512, "codec": "int8", "n_entries": 768,
                    "embed_dim": 128},
             log=lambda m: None,
             work=lambda n: registry.module("work", n), shapes=None)
    c.update(kw)
    return SimpleNamespace(**c)


def test_roofline_and_mfu_arithmetic():
    tr = _trace()
    ctx = _ctx(tr)
    work = registry.module("work", "memo_attention")
    ops, nbytes = work.work(ctx.model, 512, 4, 4, "int8")
    ops, nbytes = 12 * ops, 12 * nbytes
    least = max(ops / 197e12, nbytes / 819e9)
    got = registry.module("metrics", "memo_attention_roofline").read(ctx)
    assert got == pytest.approx(100 * least / 2e-3)
    mfu = registry.module("metrics", "step_mfu").read(ctx)
    flops = 8 * registry.module("work", "model_step").flops(
        ctx.model, 512, "classify")
    assert mfu == pytest.approx(100 * flops / (11e-3 * 197e12))
    idle = registry.module("metrics", "step_device_idle").read(ctx)
    assert idle == pytest.approx(100 * (1 - 6 / 11))


def test_silent_without_the_kernel():
    tr = _trace()
    tr["devices"]["/device:TPU:0"] = [
        e for e in tr["devices"]["/device:TPU:0"] if "memo" not in e[0]]
    ctx = _ctx(tr)
    assert registry.module("metrics", "memo_attention_roofline").read(
        ctx) is None
    assert registry.module("metrics", "step_mfu").read(
        _ctx(None)) is None


DATA = Path(__file__).parent / "data" / "trace_small.json"


def test_recorded_trace():
    """12 ms of the XLA Ops line of bert_base served on one v5e chip: the
    memo kernel is found by the name the trace gives it, busy time is
    a union inside the slice, and the gaps and top operations read."""
    tr = json.loads(DATA.read_text())
    evs = tr["devices"]["/device:TPU:0"]
    lo, hi = min(e[1] for e in evs), max(e[2] for e in evs)
    busy = tracefold.busy_s(tr, lo, hi)
    summed = sum(e[2] - e[1] for e in evs) * 1e-9
    assert 0 < busy <= min(summed, (hi - lo) * 1e-9)
    t, n = tracefold.kernel_s(tr, ("_memo_kernel", "_memo_attention_pallas"))
    assert n == 5 and 0 < t < busy
    assert tracefold.top_ops(tr)[0][0] == "%_memo_attention_pallas.1"
    gaps = tracefold.idle_gaps(tr, lo, hi)
    assert gaps and all(name == "none" for name, _ in gaps)
    assert sum(g for _, g in gaps) <= (hi - lo) * 1e-9 - busy + 1e-9


READERS = ("queue_wait_ms", "step_ms", "hit_rate", "memo_speedup",
           "memo_attention_roofline", "step_mfu", "step_device_idle")


def _spans(lo, hi):
    """Two driver steps over the recorded slice, with the program's spans
    inside them (``spanfold.fold``'s form: serving thread 1) and a
    maintenance span on thread 2 across the gap between them."""
    mid = (lo + hi) / 2
    bench = [["bench.step", lo, mid], ["bench.wait", mid, mid + MS],
             ["bench.step", mid + MS, hi]]
    memo = []
    for s, e in ((lo, mid), (mid + MS, hi)):
        memo += [["memo.step", s + 1000, e - 1000, 1, {}],
                 ["memo.layer", s + 2000, s + 3000, 1, {}],
                 ["memo.complete", e - 0.4 * MS, e - 1000, 1, {}]]
    memo.append(["memo.maintain", mid - MS, mid + 2 * MS, 2, {}])
    return bench, memo


def test_program_spans_change_no_reader():
    """With the program's memo.* spans folded in beside the driver's, the
    seven readers and the breakdown read what they read on the driver's
    spans alone; complete_ms reads the memo.complete spans, and nothing
    without them."""
    tr = json.loads(DATA.read_text())
    evs = tr["devices"]["/device:TPU:0"]
    lo, hi = min(e[1] for e in evs), max(e[2] for e in evs)
    bench, memo = _spans(lo, hi)
    before = dict(tr, spans=sorted(bench, key=lambda s: s[1]))
    after = dict(before, modules={},
                 program_spans=sorted(memo, key=lambda s: (s[1], -s[2])))
    got = {}
    for name, t in (("before", before), ("after", after)):
        ctx = _ctx(t, speedup=(1.0, 4.0))
        got[name] = {r: registry.module("metrics", r).read(ctx)
                     for r in READERS}
        got[name]["breakdown"] = (tracefold.top_ops(t),
                                  tracefold.idle_gaps(t, lo, hi))
        got[name]["complete_ms"] = registry.module(
            "metrics", "complete_ms").read(ctx)
    assert all(got["before"][r] is not None for r in READERS)
    assert got["before"]["complete_ms"] is None
    assert got["after"].pop("complete_ms") == pytest.approx(0.4 - 1e-3)
    got["before"].pop("complete_ms")
    assert got["after"] == got["before"]
