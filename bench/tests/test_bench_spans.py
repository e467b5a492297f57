"""The program's spans in a traced window of the CPU-size cell: one
``memo.step`` per batch served, each holding the serving path's phases in
order, maintenance on its own thread, and the readings of ``spanfold``
and ``bench/phases.py`` taken from them."""
import pytest

from bench import phases, run, spanfold, tracefold
from bench.tests import tiny

SEED = 2**31 + 11
PHASES = ["memo.assemble", "memo.prepare", "memo.run_layers", "memo.layer",
          "memo.barrier", "memo.drain", "memo.handoff", "memo.complete"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(scope="module")
def traced(root):
    c = run.Cell(tiny.ENCODER, SEED, root=root, bench=root / "bench",
                 require_tpu=False)
    with phases.folding() as kept:
        win, counters, _, tr = c.window(c.requests(1.0), 1.0, trace=True)
    c.release()
    assert kept["trace"] is tr
    return win, counters, tr


def test_one_step_span_per_batch(traced):
    win, counters, tr = traced
    steps = spanfold.program_steps(tr)
    assert len(steps) == counters["n_batches"] == len(win.steps) > 0
    batch = [st[4]["batch"] for st, _ in steps]
    assert batch == list(range(batch[0], batch[0] + len(batch)))
    rows = [(st[4]["n_valid"], st[4]["bucket"]) for st, _ in steps]
    assert rows == [(w[2], w[3]) for w in win.steps]
    assert all(st[4]["rows"] >= st[4]["n_valid"] for st, _ in steps)


def test_window_trace_holds_program_spans(traced):
    """A traced window's trace holds the program's memo.* spans under
    ``program_spans``, one memo.complete per batch served, and the
    driver's bench.* spans alone under ``spans``."""
    _, counters, tr = traced
    assert {sp[0] for sp in tr["spans"]} <= {
        "bench.submit", "bench.step", "bench.wait"}
    assert tracefold.spans_of(tr, "bench.step")
    done = [sp for sp in tr["program_spans"] if sp[0] == "memo.complete"]
    assert len(done) == counters["n_batches"]


def test_each_step_holds_its_phases_in_order(traced):
    _, counters, tr = traced
    layers = counters["n_memo_layers"]
    want = PHASES[:3] + ["memo.layer"] * layers + PHASES[4:]
    for st, kids in spanfold.program_steps(tr):
        assert [k[0] for k in kids] == want
        assert [k[4]["layer"] for k in kids if k[0] == "memo.layer"] == \
            list(range(layers))
        run_layers = next(k for k in kids if k[0] == "memo.run_layers")
        assert all(run_layers[1] <= k[1] and k[2] <= run_layers[2]
                   for k in kids if k[0] == "memo.layer")
        ends = [k[2] for k in kids if k[0] != "memo.layer"]
        assert ends == sorted(ends)


def test_maintenance_on_its_own_thread(traced):
    _, _, tr = traced
    serving = spanfold.serving_lines(tr)
    maint = [s for s in tr["program_spans"] if s[0] == "memo.maintain"]
    assert len(serving) == 1
    assert maint and all(s[3] not in serving for s in maint)


def test_host_readings_present(traced):
    """The three host readings read; ``step_idle_host`` needs a device
    plane, which a CPU trace has not."""
    got = spanfold.readings(traced[2])
    assert set(got) == {"step_host_ms", "dispatch_ms", "post_barrier_ms"}
    assert 0 < got["dispatch_ms"] < got["step_host_ms"]
    assert 0 < got["post_barrier_ms"] < got["step_host_ms"]


def test_phases_run_adds_program_readings(root):
    res = phases.traced_run(tiny.ENCODER, SEED + 1, 1.0, root=root,
                            bench=root / "bench", require_tpu=False)
    assert res["correct"], res["check"]
    assert {"step_ms", "queue_wait_ms", "hit_rate"} <= set(res["metrics"])
    assert {"step_host_ms", "dispatch_ms",
            "post_barrier_ms"} <= set(res["program"])
    assert list(res)[-1] == "program"
