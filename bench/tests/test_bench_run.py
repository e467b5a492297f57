"""A whole run at a size the CPU holds: the harness's look for a chip is
skipped, everything else runs as on the chip. Under the cell's own limits
a sound run is correct; a run with the timed path broken underneath, or
with the int8 control in the program's place, is not. The encoder cell
classifies; the decoder cell serves prefill and is checked through its
returned decode caches."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, driver, registry, run
from bench.tests import tiny

BERT = "bert_base_rope_allmiss.templ_r80"
GPT2 = "gpt2_small_rope_allmiss.prefill_templ_r80"
LIMITS = tiny.config(BERT)["check"]["limits"]
DECODER_LIMITS = tiny.config(GPT2)["check"]["limits"]


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


def _control(root, workload, limits, seed):
    """(program, control) numbers of one short window, each judged by
    ``limits``."""
    c = run.Cell(workload, seed, root=root, bench=root / "bench",
                 require_tpu=False)
    reqs = c.requests(1.0)
    win, _, kept, _ = c.window(reqs, 1.0, range(16))
    c.release()
    out = run.output_check(c.cfg, c.task, c.ref, c.model, c.params, reqs,
                           kept, control=True)
    assert not kept
    prog = control.judged(run.check_numbers(out["gap"], out["err"]), limits)
    ctrl = control.judged(run.check_numbers(out["control_gap"],
                                            out["control_err"]), limits)
    return prog, ctrl


def test_control_fails(root):
    """The int8 (W8A8) reference in the program's place fails the cell's
    limits on the same prompts; the program passes them."""
    prog, ctrl = _control(root, tiny.ENCODER, LIMITS, 2**31 + 9)
    assert prog["correct"], prog
    assert not ctrl["correct"], ctrl


# ------------------------------------------------------------ prefill
def test_prefill_control_fails(root):
    """The decoder cell: the W8A8 reference, read at the last prompt
    position and at each decoded one, fails the cell's limits; the
    program's prefill and its decode through the served caches pass."""
    prog, ctrl = _control(root, tiny.DECODER, DECODER_LIMITS, 2**31 + 9)
    assert prog["correct"], prog
    assert not ctrl["correct"], ctrl


@pytest.mark.parametrize("trace", [False, True])
def test_prefill_sound_run_is_correct(root, trace):
    res = _run(root, tiny.DECODER, trace)
    assert res["correct"], res["check"]
    assert res["attempted"] == 40 and res["failed"] == 0
    assert list(res)[-1] == "check"
    assert set(res["check"]) == set(DECODER_LIMITS)
    names = set(res["metrics"])
    spec = registry.benchmark(root)
    want = {m["name"] for m in registry.metrics_of(tiny.DECODER, spec,
                                                   trace)}
    if trace:
        # every per-layer metric reads, but the device's idle share: a
        # CPU trace holds no device plane
        assert names == want - {"step_device_idle"}
        assert "complete_ms" in names
        assert "memo_attention_roofline" not in want
        assert res["metrics"]["hit_rate"]["value"] == 0
    else:
        assert names == want
    assert all(m["value"] > 0 for k, m in res["metrics"].items()
               if k != "hit_rate")


def _caches_by_layer(fn):
    """Fault helper: finalize with ``fn(engine, {layer: cache})`` applied
    to a prefill batch's decode caches where they are produced."""
    from repro.core.engine import MemoEngine
    orig = MemoEngine.finalize

    def finalize(self, prep, stats=None):
        out, st, payload = orig(self, prep, stats)
        if prep.prefill:
            logits, caches = out
            out = (logits, self._merge_caches(
                fn(self, self._split_caches(caches))))
        return out, st, payload
    return finalize


def _zero_k(eng, by_li):
    """Fault: the last layer's cached K is zeros."""
    li = max(by_li)
    by_li[li] = dict(by_li[li], k=jnp.zeros_like(by_li[li]["k"]))
    return by_li


def _neighbour_kv(eng, by_li):
    """Fault: each row of the batch gets its neighbour's K/V."""
    return {li: jax.tree.map(lambda a: jnp.roll(a, 1, 0), c)
            for li, c in by_li.items()}


def _skip_prefill_layers(self, prep, li, kind, lp, h, thr_dev):
    """Fault: every layer hands its input back unchanged, with the empty
    cache template as its decode cache."""
    if prep.prefill:
        prep.caches_by_li[li] = prep.cache_tpls[li]
    return h


def _alter_prefill_answer(orig):
    """Fault: the last-token logits are altered where they are
    produced."""
    def finalize(self, prep, stats=None):
        (logits, caches), st, payload = orig(self, prep, stats)
        return (logits[..., ::-1], caches), st, payload
    return finalize


@pytest.mark.parametrize("fault", ["k_zeroed", "neighbour_kv",
                                   "state_unchanged", "answer_altered"])
def test_prefill_broken_path_is_not_correct(root, fault, monkeypatch):
    from repro.core.engine import MemoEngine
    if fault == "k_zeroed":
        monkeypatch.setattr(MemoEngine, "finalize", _caches_by_layer(_zero_k))
    elif fault == "neighbour_kv":
        monkeypatch.setattr(MemoEngine, "finalize",
                            _caches_by_layer(_neighbour_kv))
    elif fault == "state_unchanged":
        monkeypatch.setattr(MemoEngine, "_dispatch_layer",
                            _skip_prefill_layers)
    else:
        monkeypatch.setattr(MemoEngine, "finalize",
                            _alter_prefill_answer(MemoEngine.finalize))
    res = _run(root, tiny.DECODER)
    assert not res["correct"], res["check"]


# ------------------------------------------------------------- driver
class _Server:
    """Just enough of MemoServer for the open loop: a batch takes 10 ms,
    its completions are stamped, then 5 ms more go to the split into
    completions before ``step()`` returns."""
    max_delay = 1e-3

    def __init__(self):
        self.t, self.q, self.flags = 0.0, [], []
        self.stats = SimpleNamespace(n_hits=0, n_layer_attempts=0)

    def _now(self):
        return self.t

    @property
    def queued(self):
        return len(self.q)

    def submit(self, tokens, arrival=None, prefill=False):
        self.flags.append(prefill)
        self.q.append((len(self.flags) - 1, arrival, tokens))
        return len(self.flags) - 1

    def step(self, flush=False):
        if not self.q:
            return []
        self.t += 0.010
        done = self.t
        self.t += 0.005
        out = [SimpleNamespace(rid=rid, latency=done - arr, length=t.size,
                               bucket=t.size, logits=np.zeros(2))
               for rid, arr, t in self.q]
        self.q = []
        return out


@pytest.mark.parametrize("prefill", [False, True])
def test_driver_submits_the_task_kind(prefill):
    """The driver submits every request with the task's ``prefill`` flag
    (the encoder cell: False); a classification request's latency is the
    server's stamp, a prefill request's ends when its step returns."""
    reqs = [SimpleNamespace(arrival=0.0, tokens=np.zeros(8, np.int32))
            for _ in range(3)]
    srv = _Server()
    win = driver.run(srv, reqs, 0.0, lambda c: 0, prefill=prefill)
    assert srv.flags == [prefill] * 3
    want = 0.015 if prefill else 0.010
    assert [s.latency for s in win.served.values()] == \
        [pytest.approx(want)] * 3


def test_encoder_cell_submits_classification(root, monkeypatch):
    """The tiny bert cell's driver submits prefill=False, in the warm-up
    and in the window."""
    from repro.core.runtime import MemoServer
    flags = []
    orig = MemoServer.submit

    def submit(self, tokens, arrival=None, prefill=False):
        flags.append(prefill)
        return orig(self, tokens, arrival=arrival, prefill=prefill)
    monkeypatch.setattr(MemoServer, "submit", submit)
    c = run.Cell(tiny.ENCODER, 2**31 + 21, root=root, bench=root / "bench",
                 require_tpu=False)
    assert not c.prefill
    c.window(c.requests(0.25), 0.25)
    c.release()
    assert flags and not any(flags)


def test_window_reads_the_peak_before_the_sample(root):
    """The window reads the device's peak at its open and just before the
    first sampled completion is kept, so the peak of serving alone is
    known apart from the caches the check holds; neither above the peak
    read after the window."""
    c = run.Cell(tiny.DECODER, 2**31 + 21, root=root, bench=root / "bench",
                 require_tpu=False)
    reqs = c.requests(1.0)
    _, _, kept, _ = c.window(reqs, 1.0, sample=[len(reqs) - 1])
    c.release()
    assert set(c.peaks_seen) == {"open", "before_sample"} and kept
    assert max(c.peaks_seen.values()) <= c.peak_bytes()
