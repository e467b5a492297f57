"""Operations of the memo-off model for one request at its own length,
counted from the parameter shapes the program declares (``jax.eval_shape``
of its ``init``):

- two per weight per token of every matrix under ``layers``
  (``leaves.py``); a matrix beside ``w_router`` (a routed expert) counts
  at top_k over the router's width, so a shared expert, a sub-dict of its
  own, counts in full, and so does the router;
- attention's QK^T over the query projection's heads × head width (GQA's
  head_dim, MLA's nope + rope) and PV over ``wo``'s heads × head width
  (GQA's head_dim, MLA's v_head), for each query and each key it sees: S
  keys, or ``sliding_window``, and under the causal mask S·K − K²/2
  pairs, half of S² without a window;
- the head the task runs: the pooled classifier once, or with ``lm_last``
  the language-model head once, at the last position only, as prefill
  returns the last position's logits.

Embedding lookups, norms, biases and other elementwise work are not
counted."""
from __future__ import annotations

import math

import jax

from bench import leaves, registry, weights


def flops(model: dict, S: int, head: str, shapes=None) -> float:
    """``shapes``, where given, is ``weights.shapes_of`` of the program
    built from ``model``; without it the shapes are built here."""
    if head not in ("classify", "lm_last"):
        raise ValueError(f"unknown head {head!r}")
    cfg = registry.model_config(model)
    if shapes is None:
        shapes = weights.shapes_of(registry.program(model))
    flat = {tuple(leaves.name(k) for k in path): s.shape for path, s in
            jax.tree_util.tree_flatten_with_path(shapes)[0]
            if leaves.name(path[0]) == "layers"}
    K = min(S, cfg.sliding_window or S)
    pairs = S * K - K * K / 2 if cfg.causal else S * K
    out = 0
    for path, shape in flat.items():
        if leaves.kind(path).kind != "matrix":
            continue
        n = math.prod(shape)
        router = flat.get(path[:-1] + ("w_router",))
        if router is not None and path[-1] != "w_router":
            n = n * cfg.moe.top_k / router[-1]
        out += 2 * S * n
        if path[-1] == "wo":
            q = flat.get(path[:-1] + ("wq",)) or flat[path[:-1] + ("w_uq",)]
            layers = math.prod(shape[:-3])
            out += 2 * pairs * layers * (q[-2] * q[-1]
                                         + shape[-3] * shape[-2])
    out += 2 * cfg.d_model * (cfg.n_classes if head == "classify"
                              else cfg.vocab)
    return float(out)
