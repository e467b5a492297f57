"""Least work of one ``memo_attention`` call, from shapes.

Per sequence row of length ``S`` (padded to the bucket), over ``H`` query
heads of width ``dh`` with ``Hkv`` key/value heads:

* a hit row reads its stored APM (``H*S*S`` codes, plus one scale per APM
  row under int8) and V, and computes APM.V: ``2*H*S*S*dh`` operations;
* a miss row reads Q, K and V and computes QK^T and PV: ``4*H*S*S*dh``
  operations, half of that under a causal mask;
* both write the output.

Activations move as float32 (4 bytes), APM codes at the codec's width.
"""
from __future__ import annotations

CODE_BYTES = {"int8": 1, "f16": 2}


def work(model: dict, S: int, hit_rows: float, miss_rows: float,
         codec: str):
    """(operations, bytes) of ``hit_rows`` hit and ``miss_rows`` miss
    rows."""
    H, Hkv = model["n_heads"], model["n_kv_heads"]
    dh = model.get("d_head") or model["d_model"] // H
    act = 4
    causal = 0.5 if model["causal"] else 1.0
    out_b = H * S * dh * act
    hit_ops = 2 * H * S * S * dh
    hit_b = (H * S * S * CODE_BYTES[codec]
             + (H * S * 2 if codec == "int8" else 0)
             + Hkv * S * dh * act + out_b)
    miss_ops = 4 * H * S * S * dh * causal
    miss_b = (H + 2 * Hkv) * S * dh * act + out_b
    return (hit_rows * hit_ops + miss_rows * miss_ops,
            hit_rows * hit_b + miss_rows * miss_b)
