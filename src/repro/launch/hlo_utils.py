"""HLO inspection: collective-byte accounting for the roofline.

``cost_analysis`` has no collective term, so we parse the compiled HLO and
sum the RESULT-shape bytes of every collective op (all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute). XLA's HLO cost analysis
visits a ``while`` body once — the scan correction (DESIGN.md §7) is applied
one level up by diffing L and L+unit lowerings of the same config.
"""
from __future__ import annotations

import re
from typing import Dict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_LINE_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"\(")


def shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes per collective kind (static occurrences —
    while bodies counted once, corrected by the caller's L-diff)."""
    out: Dict[str, int] = {k: 0 for k in COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in COLLECTIVES}
    for m in _LINE_RE.finditer(hlo_text):
        shape_str, op = m.group(1), m.group(2)
        kind = op.replace("-start", "")
        out[kind] += shape_bytes(shape_str)
        counts[kind] += 1
    out["total"] = sum(out[k] for k in COLLECTIVES)
    out["counts"] = counts  # type: ignore
    return out


def cost_summary(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    out = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }
    if ma is not None:
        out.update(
            arg_bytes=int(ma.argument_size_in_bytes),
            out_bytes=int(ma.output_size_in_bytes),
            temp_bytes=int(ma.temp_size_in_bytes),
            alias_bytes=int(ma.alias_size_in_bytes),
            peak_bytes=int(getattr(ma, "peak_memory_in_bytes", 0)),
        )
    return out
