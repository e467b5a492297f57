"""A copy of the benchmark at a size the CPU runs in seconds: the real
harness files, the cell's configuration cut to two narrow layers, and its
traffic mix at 32 tokens, under a temporary root with its own
BENCHMARK.json. The check keeps the cell's own limits."""
from __future__ import annotations

import copy
import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
              "d_ff": 128, "vocab": 512}
SEQ = 32
CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
ENCODER = f"tiny_{CELL['config']}.tiny_{CELL['traffic']}"


def make(tmp: Path) -> Path:
    """Lay the tiny benchmark out under ``tmp``; returns its root."""
    root = Path(tmp)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = copy.deepcopy(json.loads((ROOT / "BENCHMARK.json").read_text()))
    cfg = json.loads((BENCH / "configs" / f"{CELL['config']}.json")
                     .read_text())
    cfg["name"] = f"tiny_{CELL['config']}"
    cfg["model"].update(TINY_MODEL)
    cfg["calibration"] = {"sequences": 16, "batch": 8}
    cfg["serve"]["buckets"] = [SEQ]
    cfg["check"]["sample"] = 8
    cfg["memo"]["embed_steps"] = 20
    (root / "bench" / "configs" / f"{cfg['name']}.json").write_text(
        json.dumps(cfg))
    tr = json.loads((BENCH / "traffic" / f"{CELL['traffic']}.json")
                    .read_text())
    tr.update(length=SEQ, rate_per_s=40)
    (root / "bench" / "traffic" / f"tiny_{CELL['traffic']}.json").write_text(
        json.dumps(tr))
    spec["configs"] = [{"name": cfg["name"], "source": "test",
                        "file": f"bench/configs/{cfg['name']}.json",
                        "reduced": [], "why": "test"}]
    spec["workloads"] = [{"name": ENCODER, "config": cfg["name"],
                          "traffic": f"tiny_{CELL['traffic']}", "chips": 1,
                          "why": "test"}]
    for m in spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [ENCODER]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
