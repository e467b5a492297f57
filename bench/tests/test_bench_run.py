"""A whole run at a size the CPU holds: the harness's look for a chip is
skipped, everything else runs as on the chip. Under the cell's own limits
a sound run is correct; a run with the timed path broken underneath, or
with the int8 control in the program's place, is not."""
import json

import numpy as np
import pytest

from bench import control, run
from bench.tests import tiny

LIMITS = json.loads((tiny.BENCH / "configs" / f"{tiny.CELL['config']}.json")
                    .read_text())["check"]["limits"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("tiny"))


def _run(root, workload, trace=False, seed=2**31 + 5):
    return run.run_cell(workload, seed, 1.0, trace, root=root,
                        bench=root / "bench", require_tpu=False)


@pytest.mark.parametrize("trace", [False, True])
def test_sound_run_is_correct(root, trace):
    res = _run(root, tiny.ENCODER, trace)
    assert res["correct"], res["check"]
    assert res["attempted"] == 40 and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert set(res["check"]) == set(LIMITS)
    names = set(res["metrics"])
    if trace:
        assert {"queue_wait_ms", "step_ms", "hit_rate",
                "memo_speedup"} <= names
        assert "busy_s" in res["device"] and "window_s" in res["device"]
        assert "breakdown" in res
    else:
        assert names == {"latency_p50_ms", "latency_p95_ms",
                         "tokens_per_s", "agreement", "setup_s"}
        assert all(m["value"] > 0 for m in res["metrics"].values())


def _skip_layers(self, prep):
    """Fault: the step hands its state back unchanged (the hidden states
    as embedded)."""
    return prep


def _alter_answer(orig):
    """Fault: the answer is altered where it is produced."""
    def finalize(self, prep, stats=None):
        out, st, payload = orig(self, prep, stats)
        return np.asarray(out)[..., ::-1], st, payload
    return finalize


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_broken_path_is_not_correct(root, fault, monkeypatch):
    from repro.core.engine import MemoEngine
    if fault == "state_unchanged":
        monkeypatch.setattr(MemoEngine, "run_layers", _skip_layers)
    else:
        monkeypatch.setattr(MemoEngine, "finalize",
                            _alter_answer(MemoEngine.finalize))
    res = _run(root, tiny.ENCODER)
    assert not res["correct"], res["check"]


def test_control_fails(root):
    """The int8 (W8A8) reference in the program's place fails the cell's
    limits on the same prompts; the program passes them."""
    c = run.Cell(tiny.ENCODER, 2**31 + 9, root=root, bench=root / "bench",
                 require_tpu=False)
    reqs = c.requests(1.0)
    win, _, kept, _ = c.window(reqs, 1.0, range(16))
    c.release()
    out = run.output_check(c.cfg, c.task, c.ref, c.model, c.params, reqs,
                           kept, control=True)
    prog = control.judged(run.check_numbers(out["gap"], out["err"]), LIMITS)
    ctrl = control.judged(run.check_numbers(out["control_gap"],
                                            out["control_err"]), LIMITS)
    assert prog["correct"], prog
    assert not ctrl["correct"], ctrl
