"""Encoder classification: a request is a passage, its answer the argmax
of the class logits that ``MemoServer`` returns (``submit()``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

def memo_off(model, params, cfg: dict):
    """The program's memo-off forward over a padded batch: one jit of the
    plain model (``Model.classify``), the baseline a memoized step must
    beat."""
    fn = jax.jit(lambda p, t: model.classify(p, {"tokens": t}))
    return lambda tokens: fn(params, tokens)


def answer(comp) -> int:
    return int(np.argmax(comp.logits))


def keep(comp):
    """What the output check needs of a sampled completion."""
    return np.asarray(comp.logits, np.float32)


def served(kept) -> list:
    """Per request, (served tokens, served logit rows (1, n_classes))."""
    return [([int(np.argmax(k))], k[None]) for k in kept]


def reference_rows(ref, params, cfg, prompts, served_tokens, quant=None):
    """Reference logits (B, 1, n_classes) for each prompt's answer."""
    return ref.classify(params, jnp.asarray(prompts), cfg, quant)[:, None]
