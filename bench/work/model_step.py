"""Operations of the memo-off model for one request at its own length:
two per parameter per token in the layers' matrices, plus attention's
QK^T and PV (halved under a causal mask), plus the head the task runs
(the pooled classifier once, or with ``lm_last`` the tied language-model
head once, at the last position only, as prefill returns the last
position's logits). Embedding lookups and elementwise work are not
counted."""
from __future__ import annotations


def flops(model: dict, S: int, head: str) -> float:
    d, L = model["d_model"], model["n_layers"]
    H, Hkv = model["n_heads"], model["n_kv_heads"]
    dh = model.get("d_head") or d // H
    mats = d * H * dh + 2 * d * Hkv * dh + H * dh * d + 2 * d * model["d_ff"]
    attn = 4 * S * S * H * dh * (0.5 if model["causal"] else 1.0)
    out = L * (2 * mats * S + attn)
    if head == "classify":
        out += 2 * d * model["n_classes"]
    elif head == "lm_last":
        out += 2 * d * model["vocab"]
    else:
        raise ValueError(f"unknown head {head!r}")
    return float(out)
