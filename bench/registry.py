"""Finds a cell's parts by name.

A configuration is ``configs/<name>.json``, a traffic mix
``traffic/<name>.json``, a serving task ``tasks/<name>.py``, a plain
reference ``references/<name>.py``, a per-layer metric's reader
``metrics/<name>.py`` and a kernel's work function ``work/<name>.py``. A
later cell adds such files and an entry in ``BENCHMARK.json``; it edits
none that exist.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import typing
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def cell(name: str, spec: dict) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in spec['workloads']]}")


def config(name: str, spec: dict, root: Path = ROOT) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return load_json(Path(root) / c["file"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def model_config(model: dict):
    """The program's ``ModelConfig`` from a configuration's ``model`` dict,
    built by the dataclass's declared field types: a nested dict becomes
    its dataclass (``MLAConfig``, ``MoEConfig``, ``EncoderConfig``, any
    other such field), a list a tuple. A key the dataclass does not
    declare is an error that names it."""
    from repro.configs.base import ModelConfig
    return _build(ModelConfig, model, "model")


def _build(cls, fields: dict, where: str):
    hints = typing.get_type_hints(cls)
    unknown = sorted(set(fields) - {f.name for f in dataclasses.fields(cls)})
    if unknown:
        raise KeyError(f"{where}: {cls.__name__} has no field "
                       f"{', '.join(unknown)}")
    return cls(**{k: _value(hints[k], v, f"{where}.{k}")
                  for k, v in fields.items()})


def _value(tp, v, where: str):
    if typing.get_origin(tp) is typing.Union:           # Optional[...]
        tp = next(a for a in typing.get_args(tp) if a is not type(None))
    if isinstance(v, dict) and dataclasses.is_dataclass(tp):
        return _build(tp, v, where)
    if isinstance(v, list):
        return tuple(v)
    return v


def program(model: dict):
    """The program's model for a configuration's ``model`` dict, with its
    layers unrolled, as the benchmark serves it."""
    from repro.models import build_model
    return build_model(model_config(model), layer_loop="unroll")


def traffic(name: str, bench: Path = BENCH) -> dict:
    return load_json(Path(bench) / "traffic" / f"{name}.json")


def module(kind: str, name: str, bench: Path = BENCH):
    """Import ``<bench>/<kind>/<name>.py`` by path (names may hold dots)."""
    path = Path(bench) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(cell_name: str, spec: dict, trace: bool) -> list:
    """The metric entries a run of this cell reports: the end-to-end ones
    with ``--trace 0``, the per-layer ones with ``--trace 1``."""
    rows = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in rows
            if "workloads" not in m or cell_name in m["workloads"]]


def peaks(device_kind: str, bench: Path = BENCH) -> dict:
    """The published peaks of one chip of this kind. A kind missing from
    the table is an error, never a default."""
    table = load_json(Path(bench) / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(known: {sorted(table['devices'])})")
    return table["devices"][device_kind]
