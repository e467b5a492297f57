"""Host spans of the serving path (``repro.core.spans``) and the names of
its jitted programs.

Under a profiler session the engine step records ``memo.prepare``,
``memo.run_layers`` (one ``memo.layer`` per layer), ``memo.barrier`` and
``memo.drain``; ``MemoServer`` wraps each batch in ``memo.step`` with
``memo.assemble``, ``memo.handoff`` (async maintenance) or
``memo.maintain`` (sync), ``memo.exact`` (MEMO_DISABLED) and
``memo.complete``; the maintenance worker records ``memo.maintain`` on
its own thread. Every program the server runs is named ``memo_*``, so a
trace names it ``jit_memo_*``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.runtime import Health, MemoServer
from repro.core.spans import span

SEQ = 16
# jit-cache keys of the programs MemoServer and its memo-off legs run
SERVING_KEYS = {"prolog", "fused", "fusedpf", "plain", "plainpf", "head",
                "headpf", "pfexact", "split"}


def _recorded(tmp_path, fn):
    """Run ``fn`` under a profiler session; returns the ``memo.*`` host
    events as (name, start_ns, end_ns, line, args), ordered by start,
    parents first, ``line`` numbering the host thread."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    out, n = [], 0
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            n += 1
            out.extend((e.name, e.start_ns, e.end_ns, n, dict(e.stats))
                       for e in line.events if e.name.startswith("memo."))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _serving(events):
    """Names on the thread that recorded ``memo.step``, in order."""
    line, = {e[3] for e in events if e[0] == "memo.step"}
    return [e[0] for e in events if e[3] == line]


def _program_names(eng) -> set:
    names = set()
    for key, fn in eng._jit_cache.items():
        kind = key if isinstance(key, str) else key[0]
        if kind in SERVING_KEYS:
            names.add(fn.__name__)
    return names


@pytest.fixture(scope="module")
def engine():
    from repro.configs import get_reduced
    from repro.core.engine import MemoEngine
    from repro.data import TemplateCorpus
    from repro.memo import MemoSpec
    from repro.models import build_model
    cfg = get_reduced("bert_base").replace(n_classes=4, n_layers=2,
                                           d_model=64, d_ff=128, n_heads=4)
    m = build_model(cfg, layer_loop="unroll")
    params = m.init(jax.random.PRNGKey(0))
    corpus = TemplateCorpus(vocab=cfg.vocab, seq_len=SEQ, n_templates=4,
                            slot_fraction=0.2)
    eng = MemoEngine(m, params, MemoSpec.flat(threshold=0.6, embed_steps=20,
                                              mode="bucket"))
    eng.build(jax.random.PRNGKey(1),
              [{"tokens": jnp.asarray(corpus.sample(8)[0])}
               for _ in range(2)])
    return eng, corpus


def test_span_name_and_args(tmp_path):
    def nested():
        with span("step", batch=3, rows=8):
            with span("layer", layer=1):
                pass
    ev = _recorded(tmp_path, nested)
    assert [(e[0], e[4]) for e in ev] == [
        ("memo.step", {"batch": 3, "rows": 8}), ("memo.layer", {"layer": 1})]
    assert ev[0][1] <= ev[1][1] and ev[1][2] <= ev[0][2]


def test_engine_step_spans(engine, tmp_path):
    eng, corpus = engine
    toks = jnp.asarray(corpus.sample(4)[0])
    eng.infer({"tokens": toks})                  # compile outside
    ev = _recorded(tmp_path, lambda: eng.infer({"tokens": toks}))
    assert [e[0] for e in ev] == [
        "memo.prepare", "memo.run_layers", "memo.layer", "memo.layer",
        "memo.barrier", "memo.drain"]
    assert [e[4] for e in ev if e[0] == "memo.layer"] == [
        {"layer": 0}, {"layer": 1}]


@pytest.mark.parametrize("async_maint", [False, True])
def test_server_step_spans(engine, tmp_path, async_maint):
    eng, corpus = engine
    srv = MemoServer(eng, buckets=(SEQ,), max_batch=4,
                     async_maintenance=async_maint)
    try:
        toks = np.asarray(corpus.sample(3)[0])
        for t in toks:
            srv.submit(t)
        srv.step(flush=True)                     # compile outside
        srv.drain_maintenance(timeout=60)
        for t in toks:
            srv.submit(t)

        def serve():
            assert len(srv.step(flush=True)) == 3
            srv.drain_maintenance(timeout=60)
        ev = _recorded(tmp_path, serve)
    finally:
        srv.close()
    hand = "memo.handoff" if async_maint else "memo.maintain"
    assert _serving(ev) == [
        "memo.step", "memo.assemble", "memo.prepare", "memo.run_layers",
        "memo.layer", "memo.layer", "memo.barrier", "memo.drain", hand,
        "memo.complete"]
    step = next(e for e in ev if e[0] == "memo.step")
    assert step[4] == {"batch": 1, "bucket": SEQ, "rows": 4, "n_valid": 3,
                       "queued": 0}
    maint = [e for e in ev if e[0] == "memo.maintain"]
    assert len(maint) == 1
    assert (maint[0][3] == step[3]) is not async_maint
    if async_maint:
        assert next(e for e in ev if e[0] == hand)[4] == {"depth": 0}


def test_memo_disabled_spans(engine, tmp_path):
    eng, corpus = engine
    srv = MemoServer(eng, buckets=(SEQ,), max_batch=4)
    try:
        srv.health = Health.MEMO_DISABLED
        toks = np.asarray(corpus.sample(2)[0])
        for t in toks:
            srv.submit(t)
        ev = _recorded(tmp_path, lambda: srv.step(flush=True))
    finally:
        srv.close()
    assert _serving(ev) == ["memo.step", "memo.assemble", "memo.exact",
                            "memo.complete"]


def test_serving_programs_are_named(engine):
    """After serving through MemoServer and its exact leg, every serving
    program in the engine's jit cache has a memo_* name."""
    eng, corpus = engine
    srv = MemoServer(eng, buckets=(SEQ,), max_batch=4)
    try:
        for health in (Health.HEALTHY, Health.MEMO_DISABLED):
            srv.health = health
            srv.submit(np.asarray(corpus.sample(1)[0])[0])
            srv.step(flush=True)
    finally:
        srv.close()
    names = _program_names(eng)
    assert {"memo_prolog", "memo_layer", "memo_head",
            "memo_layer_plain"} <= names
    assert all(n.startswith("memo_") for n in names)


def test_prefill_programs_and_spans(tmp_path):
    from repro.configs import get_reduced
    from repro.data import TemplateCorpus
    from repro.memo import MemoSession, MemoSpec
    from repro.models import build_model
    cfg = get_reduced("gpt2_small").replace(n_layers=2)
    model = build_model(cfg, layer_loop="unroll")
    params = model.init(jax.random.PRNGKey(0))
    corpus = TemplateCorpus(vocab=cfg.vocab, seq_len=SEQ, n_templates=4,
                            slot_fraction=0.25, seed=3)
    spec = MemoSpec.flat(threshold=0.6, mode="bucket", embed_steps=20,
                         prefill_enabled=True, max_layers=1)
    sess = MemoSession.build(
        model, params, spec, key=jax.random.PRNGKey(1),
        batches=[{"tokens": jnp.asarray(corpus.sample(4)[0])}])
    eng = sess.engine
    srv = MemoServer(eng, buckets=(SEQ,), max_batch=4,
                     async_maintenance=False)
    try:
        toks = np.asarray(corpus.sample(2)[0])
        for t in toks:
            srv.submit(t, prefill=True)
        srv.step(flush=True)                     # compile outside
        for t in toks:
            srv.submit(t, prefill=True)
        ev = _recorded(tmp_path, lambda: srv.step(flush=True))
        srv.health = Health.MEMO_DISABLED
        srv.submit(toks[0], prefill=True)
        srv.step(flush=True)
    finally:
        srv.close()
    assert _serving(ev) == [
        "memo.step", "memo.assemble", "memo.prepare", "memo.run_layers",
        "memo.layer", "memo.layer", "memo.barrier", "memo.drain",
        "memo.maintain", "memo.complete"]
    assert next(e for e in ev if e[0] == "memo.complete")[4] == {
        "requests": 2, "rows": 4}
    assert {"memo_prolog", "memo_layer_prefill", "memo_layer_plain_prefill",
            "memo_head_prefill", "memo_prefill_exact",
            "memo_split_caches"} <= _program_names(eng)
