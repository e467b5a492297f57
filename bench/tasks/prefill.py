"""Causal prefill: a request is a prompt served through
``MemoServer.submit(prefill=True)``; its answer is the argmax of the
last-token logits, and the decode caches it gets back are part of what is
served.

The output check extends each sampled request by the configuration's
``check.decode_steps`` greedy steps through its served caches
(``Model.decode_step``), after the window closes, and compares the logits
of the last prompt position and of every decoded position with the
reference's full causal forward over the prompt and the decoded tokens. A
cache that holds the wrong K/V shows in the decoded positions, where the
last-token logits cannot see it."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PREFILL = True


def memo_off(model, params, cfg: dict):
    """The program's memo-off prefill over a padded batch: one jit of the
    plain model's ``Model.prefill`` at the configuration's cache length,
    the program the engine's ``prefill_exact`` runs. Returns
    (last-token logits, caches)."""
    cache_len = int(cfg["memo"]["prefill_cache_len"])
    fn = jax.jit(lambda p, t: model.prefill(p, {"tokens": t},
                                            cache_len=cache_len))
    return lambda tokens: fn(params, tokens)


def logits_of(out):
    """The answer logits (B, vocab) of what ``memo_off`` returns."""
    return out[0]


def answer(comp) -> int:
    return int(np.argmax(comp.logits))


def keep(comp):
    """What the output check needs of a sampled completion: its
    last-token logits, its decode caches (left on the device) and its
    prompt length."""
    return np.asarray(comp.logits, np.float32), comp.caches, int(comp.length)


def served(kept, model, params, cfg: dict) -> list:
    """Per request, (served tokens, served logit rows (steps + 1, vocab)):
    the last-token row as served, then ``steps`` greedy decode steps
    through the served caches, in blocks of up to 8 requests of one
    length stacked on the batch axis. Each entry of ``kept`` is dropped
    once decoded, so the caches are freed before the reference runs."""
    steps = int(cfg["check"]["decode_steps"])
    dec = jax.jit(model.decode_step)
    out = [None] * len(kept)
    by_len: dict = {}
    for i, k in enumerate(kept):
        by_len.setdefault(k[2], []).append(i)
    blocks = [g[j:j + 8] for g in by_len.values()
              for j in range(0, len(g), 8)]
    for blk in blocks:
        pos = kept[blk[0]][2]
        rows = [np.stack([kept[i][0] for i in blk])]
        # a K or V leaf is (batch, slots, heads, head_dim), behind the
        # leading layer axis of a scanned segment
        caches = jax.tree.map(
            lambda *a: jnp.concatenate(a, a[0].ndim - 4),
            *[kept[i][1] for i in blk])
        for i in blk:
            kept[i] = None
        for s in range(steps):
            tok = jnp.asarray(np.argmax(rows[-1], -1)[:, None], jnp.int32)
            lg, caches = dec(params, tok, caches, jnp.int32(pos + s))
            rows.append(np.asarray(lg, np.float32))
        del caches
        for i, r in zip(blk, np.stack(rows, 1)):     # (steps + 1, V)
            out[i] = (np.argmax(r, -1).tolist(), r)
    return out


def reference_rows(ref, params, cfg, prompts, served_tokens, quant=None):
    """Reference logits (B, steps + 1, vocab) at the last prompt position
    and at each decoded one, from one causal forward over each prompt
    followed by its served tokens (all but the last, which no position
    after it reads)."""
    S = prompts.shape[1]
    n = served_tokens.shape[1]
    tokens = jnp.concatenate([prompts, served_tokens[:, :-1]], 1)
    return ref.lm_logits(params, tokens, cfg, tuple(range(S - 1, S - 1 + n)),
                         quant)
