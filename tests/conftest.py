"""Test scaffolding.

The container may lack ``hypothesis``; property tests only use a tiny
slice of its API (``given`` / ``settings`` / three strategies), so when
the real package is missing we register a deterministic shim in
``sys.modules`` before collection. Seeded sampling keeps the property
tests meaningful (many examples per test) and reproducible.

``check_drain_parity`` holds the engine's stats drain to the eager
formula it replaced (``tests/test_runtime.py``, ``tests/test_prefill.py``).
"""
from __future__ import annotations

import sys
import types

import pytest


def _install_hypothesis_shim():
    import numpy as np

    class _Strategy:
        def __init__(self, draw):
            self.draw = draw

    def integers(lo, hi):
        return _Strategy(lambda rng: int(rng.integers(lo, hi + 1)))

    def floats(lo, hi, **_kw):
        return _Strategy(lambda rng: float(rng.uniform(lo, hi)))

    def sampled_from(xs):
        xs = list(xs)
        return _Strategy(lambda rng: xs[int(rng.integers(0, len(xs)))])

    def settings(max_examples=10, deadline=None, **_kw):
        def deco(fn):
            fn._shim_max_examples = max_examples
            return fn
        return deco

    def given(**strats):
        def deco(fn):
            import inspect

            # parameters NOT drawn from strategies (pytest.mark.parametrize
            # / fixtures) pass straight through; pytest must see exactly
            # those in the signature — not the strategy names, hence the
            # exec-built wrapper instead of functools.wraps
            passthrough = [p for p in inspect.signature(fn).parameters
                           if p not in strats]

            def body(*args):
                # read max_examples lazily: @settings usually sits ABOVE
                # @given, so it decorates (and tags) this wrapper
                n = getattr(wrapper, "_shim_max_examples", 10)
                rng = np.random.default_rng(0)
                kw = dict(zip(passthrough, args))
                for _ in range(n):
                    fn(**kw, **{k: s.draw(rng) for k, s in strats.items()})

            if passthrough:
                ns = {"body": body}
                argstr = ", ".join(passthrough)
                exec(f"def wrapper({argstr}):\n    return body({argstr})", ns)
                wrapper = ns["wrapper"]
            else:
                def wrapper():
                    return body()
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            wrapper.__module__ = fn.__module__
            return wrapper
        return deco

    hyp = types.ModuleType("hypothesis")
    st_mod = types.ModuleType("hypothesis.strategies")
    st_mod.integers = integers
    st_mod.floats = floats
    st_mod.sampled_from = sampled_from
    hyp.given = given
    hyp.settings = settings
    hyp.strategies = st_mod
    sys.modules["hypothesis"] = hyp
    sys.modules["hypothesis.strategies"] = st_mod


try:  # pragma: no cover - exercised implicitly at collection time
    import hypothesis  # noqa: F401
except ImportError:
    _install_hypothesis_shim()


def _eager_drain(eng, prep):
    """The eager stats drain that ``memo_drain`` replaced, kept as the
    parity reference: a per-layer ``astype`` and ``jnp.stack``, a stack
    across layers and one across the slots, blocking reads, then the host
    fold. Returns the fields ``finalize`` must reproduce."""
    import jax.numpy as jnp
    import numpy as np
    pend, nv = prep.pend, prep.n_valid
    payload = np.asarray(jnp.stack(
        [jnp.stack([p[1], p[2].astype(jnp.float32)]) for p in pend]))
    slots = np.asarray(jnp.stack([p[3] for p in pend]))[:, :nv]
    hits = payload[:, 1, :nv] > 0.5
    sims = payload[:, 0, :nv]
    admissions = []
    if prep.capture and len(pend[0]) > 4:
        embs = np.asarray(jnp.stack([p[4] for p in pend]))[:, :nv]
        apms = np.asarray(jnp.stack([p[5] for p in pend]))[:, :nv]
        kvs = (np.asarray(jnp.stack([p[6] for p in pend]))[:, :nv]
               if len(pend[0]) > 6 else None)
        lens = None if prep.lengths is None else prep.lengths[:nv]
        for l in range(embs.shape[0]):
            miss = ~hits[l]
            if miss.any():
                admissions.append(eng._stage_capture(
                    apms[l][miss], embs[l][miss],
                    None if lens is None else lens[miss],
                    None if kvs is None else kvs[l][miss]))
    return {"n_hits": int(hits.sum()), "n_layer_attempts": int(hits.size),
            "per_layer_hits": {p[0]: int(h.sum())
                               for p, h in zip(pend, hits)},
            "sims": sims.reshape(-1),
            "reuse_slots": slots[hits] if hits.any() else None,
            "admissions": admissions}


def _check_drain_parity(eng, prep):
    """``eng.finalize(prep)`` against the eager drain on the same staged
    device stats: counters equal, sims bit for bit, reuse slots and
    admissions equal in value and dtype. Returns finalize's
    ``(outputs, stats, payload)``."""
    import numpy as np
    from repro.core.engine import MemoStats
    ref = _eager_drain(eng, prep)
    out, st, payload = eng.finalize(prep, stats=MemoStats())
    assert st.n_hits == ref["n_hits"]
    assert st.n_layer_attempts == ref["n_layer_attempts"]
    assert st.per_layer_hits == ref["per_layer_hits"]
    got = np.asarray(list(st.sims), np.float32)
    np.testing.assert_array_equal(got.view(np.int32),
                                  ref["sims"].view(np.int32))
    if ref["reuse_slots"] is None:
        assert payload.reuse_slots is None
    else:
        assert payload.reuse_slots.dtype == ref["reuse_slots"].dtype
        np.testing.assert_array_equal(payload.reuse_slots,
                                      ref["reuse_slots"])
    assert len(payload.admissions) == len(ref["admissions"])
    for got_adm, ref_adm in zip(payload.admissions, ref["admissions"]):
        for g, r in zip(got_adm, ref_adm):
            if r is None:
                assert g is None
            else:
                assert g.dtype == r.dtype
                np.testing.assert_array_equal(g, r)
    return out, st, payload


@pytest.fixture
def check_drain_parity():
    """``check_drain_parity(eng, prep)`` finalizes a prepared, run batch
    and asserts its drained stats equal the eager reference drain's."""
    return _check_drain_parity
