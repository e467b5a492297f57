"""The plain reference (``references/prenorm.py``) against the program at a
small size on the CPU, on the benchmark's seeded weights, in float32 at
HIGHEST precision: the encoder's class logits against ``Model.classify``,
and the causal LM head against the engine's exact prefill followed by
greedy decode steps through its caches."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import registry, weights
from bench.tests import tiny

SEED = 2**31 + 31
REF = registry.module("references", "prenorm")


def _program(workload):
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    cfg = tiny.config(workload)
    cfg["model"].update(tiny.TINY_MODEL)
    model = build_model(ModelConfig(**cfg["model"]), layer_loop="unroll")
    return cfg, model, weights.make(model, SEED)


def _close(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.sqrt(np.mean(np.square(want)))
    assert np.abs(got - want).max() <= 1e-4 * scale, \
        (np.abs(got - want).max(), scale)


def test_encoder_reference_matches_classify():
    cfg, model, params = _program("bert_base_rope_allmiss.templ_r80")
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg["model"]["vocab"], (3, 24)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = model.classify(params, {"tokens": toks})
        got = REF.classify(params, toks, cfg["model"])
    _close(got, want)


def test_causal_reference_matches_prefill_and_decode():
    """Prefill (``MemoEngine.prefill_exact``) then 4 greedy decode steps
    through the returned caches read the reference's logits at the last
    prompt position and at each decoded one; without the causal mask the
    reference reads otherwise."""
    from repro.core.engine import MemoEngine
    from repro.memo import MemoSpec
    cfg, model, params = _program(
        "gpt2_small_rope_allmiss.prefill_templ_r80")
    mcfg = cfg["model"]
    eng = MemoEngine(model, params, MemoSpec.flat(**cfg["memo"]))
    S, steps = 24, 4
    prompts = jnp.asarray(np.random.default_rng(2).integers(
        0, mcfg["vocab"], (3, S)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        lg, caches = eng.prefill_exact({"tokens": prompts})
        rows, toks = [lg], []
        for s in range(steps):
            toks.append(jnp.argmax(rows[-1], -1)[:, None].astype(jnp.int32))
            lg, caches = model.decode_step(params, toks[-1], caches,
                                           jnp.int32(S + s))
            rows.append(lg)
        full = jnp.concatenate([prompts] + toks, 1)
        at = tuple(range(S - 1, S + steps))
        want = REF.lm_logits(params, full, mcfg, at)
        bidir = REF.lm_logits(params, full, dict(mcfg, causal=False), at)
    got = jnp.stack(rows, 1)
    assert want.shape == got.shape == (3, steps + 1, mcfg["vocab"])
    _close(got, want)
    with pytest.raises(AssertionError):
        _close(got, bidir)
