"""The harness finds a cell's parts by name: a configuration, a traffic
mix and a metric reader dropped into a copy of bench/ are picked up with
no existing file edited. Without a chip the command prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import registry, run

ROOT = Path(__file__).resolve().parents[2]


def _snapshot(root):
    return {p: p.read_bytes() for p in Path(root).rglob("*") if p.is_file()}


def test_new_parts_found_by_name(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _snapshot(tmp_path)
    b = tmp_path / "bench"
    cell = json.loads((tmp_path / "BENCHMARK.json").read_text())[
        "workloads"][0]
    cfg = json.loads((b / "configs" / f"{cell['config']}.json").read_text())
    (b / "configs" / "bert_other.json").write_text(
        json.dumps(dict(cfg, name="bert_other")))
    (b / "traffic" / "novel_r10.json").write_text(json.dumps(
        {"tokens": "template", "n_templates": 64, "slot_fraction": 0.9,
         "length": 128, "arrivals": "poisson", "rate_per_s": 10}))
    (b / "metrics" / "batch_rows.py").write_text(
        "def read(ctx):\n    return ctx.counters['rows_per_batch']\n")
    # the one edit a later cell makes: entries in BENCHMARK.json
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "bert_other", "source": "x",
                            "file": "bench/configs/bert_other.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "bert_other.novel_r10",
                              "config": "bert_other",
                              "traffic": "novel_r10", "chips": 1,
                              "why": "x"})
    spec["per_layer"].append({"name": "batch_rows", "unit": "rows",
                              "better": "higher", "source": "host_clock",
                              "layer": "x", "moves": "latency_p50_ms"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _snapshot(tmp_path)
    changed = [p for p in before if before[p] != after[p]]
    assert changed == [tmp_path / "BENCHMARK.json"]

    spec = registry.benchmark(tmp_path)
    cell = registry.cell("bert_other.novel_r10", spec)
    assert registry.config(cell["config"], spec, tmp_path)["name"] == \
        "bert_other"
    assert registry.traffic(cell["traffic"], b)["n_templates"] == 64
    names = [m["name"] for m in registry.metrics_of(cell["name"], spec,
                                                    True)]
    assert "batch_rows" in names and "memo_attention_roofline" not in names
    reader = registry.module("metrics", "batch_rows", b)
    assert reader.read(SimpleNamespace(counters={"rows_per_batch": 8})) == 8


def test_every_named_part_exists():
    spec = registry.benchmark()
    for w in spec["workloads"]:
        cfg = registry.config(w["config"], spec)
        registry.traffic(w["traffic"])
        for kind in ("tasks", "references"):
            registry.module(kind, cfg["task" if kind == "tasks"
                                      else "reference"])
    for m in spec["per_layer"]:
        assert hasattr(registry.module("metrics", m["name"]), "read")


def _bench_cmd(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    cell = registry.benchmark()["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell,
         "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_no_tpu_no_result():
    p = _bench_cmd(ROOT, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_bare_checkout_no_result(tmp_path):
    """Only BENCHMARK.json and bench/: no program to run, no result."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _bench_cmd(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_unknown_device_kind(monkeypatch):
    import jax
    fake = SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(run.NoChip, match="peaks.json"):
        run.devices(1, registry.peaks)
    fake.device_kind = "TPU v5 lite"
    with pytest.raises(run.NoChip, match="needs 4 chips"):
        run.devices(4, registry.peaks)
    devs, peaks = run.devices(1, registry.peaks)
    assert devs == [fake] and peaks["hbm_bytes"] == 16e9
