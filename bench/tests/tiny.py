"""A copy of the benchmark at a size the CPU runs in seconds: the real
harness files, each cell's configuration cut to narrow layers (``cut``),
and its traffic mix at 32 tokens, under a temporary root with its own
BENCHMARK.json. The check keeps each cell's own limits. A cell is named
as in BENCHMARK.json; ``name`` gives its tiny copy's name."""
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
RANK, HEAD = 64, 32          # latent ranks; nope, rope, v and head widths
EXPERTS, TOP_K, EXPERT_FF = 4, 2, 128


def cut(model: dict) -> dict:
    """A configuration's ``model`` dict at a CPU size that keeps every kind
    of layer: ``TINY_MODEL``'s widths, fewer KV heads than query heads
    where the model groups them, one leading dense layer where there are
    any, a whole period of the layer pattern, and nested groups cut too
    (latent ranks to ``RANK``, head widths to ``HEAD``; experts to
    ``EXPERTS``, top-k to ``TOP_K``, their width to ``EXPERT_FF``; a
    window under ``SEQ``)."""
    out = dict(model, **TINY_MODEL)
    out["n_kv_heads"] = max(1, TINY_MODEL["n_heads"] * model["n_kv_heads"]
                            // model["n_heads"])
    if model.get("d_head"):
        out["d_head"] = min(model["d_head"], HEAD)
    pattern = model.get("layer_pattern", ["mix"])
    first = min(model.get("dense_first_n", 0), 1)
    if "dense_first_n" in model:
        out["dense_first_n"] = first
    out["n_layers"] = max(TINY_MODEL["n_layers"],
                          first + (1 if list(pattern) == ["mix"]
                                   else len(pattern)))
    if model.get("dense_d_ff"):
        out["dense_d_ff"] = min(model["dense_d_ff"], EXPERT_FF)
    if model.get("sliding_window"):
        out["sliding_window"] = min(model["sliding_window"], SEQ // 2)
    if model.get("mla"):
        out["mla"] = {k: v and min(v, RANK if k.endswith("_rank") else HEAD)
                      for k, v in model["mla"].items()}
    if model.get("moe"):
        m = dict(model["moe"])
        m["n_experts"] = min(m["n_experts"], EXPERTS)
        m["top_k"] = min(m["top_k"], TOP_K, m["n_experts"])
        m["d_ff"] = min(m["d_ff"], EXPERT_FF)
        out["moe"] = m
    return out


def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(workload: str) -> dict:
    for w in spec()["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def config(workload: str) -> dict:
    """The cell's configuration file, as the chip runs it."""
    return json.loads((BENCH / "configs" / f"{cell(workload)['config']}"
                       ".json").read_text())


def name(workload: str) -> str:
    """The tiny copy's name of a cell of BENCHMARK.json."""
    w = cell(workload)
    return f"tiny_{w['config']}.tiny_{w['traffic']}"


ENCODER = name("bert_base_rope_allmiss.templ_r80")
DECODER = name("gpt2_small_rope_allmiss.prefill_templ_r80")


def make(tmp: Path) -> Path:
    """Lay the tiny copy of every cell out under ``tmp``; returns its
    root."""
    root = Path(tmp)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    out = copy.deepcopy(spec())
    configs, workloads = {}, []
    for w in out["workloads"]:
        cfg = config(w["name"])
        cfg["name"] = f"tiny_{w['config']}"
        cfg["model"] = cut(cfg["model"])
        cfg["calibration"] = {"sequences": 16, "batch": 8}
        cfg["serve"]["buckets"] = [SEQ]
        cfg["check"]["sample"] = 8
        cfg["memo"]["embed_steps"] = 20
        (root / "bench" / "configs" / f"{cfg['name']}.json").write_text(
            json.dumps(cfg))
        configs[cfg["name"]] = {"name": cfg["name"], "source": "test",
                                "file": f"bench/configs/{cfg['name']}.json",
                                "reduced": [], "why": "test"}
        tr = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                        .read_text())
        tr.update(length=SEQ, rate_per_s=40)
        (root / "bench" / "traffic" / f"tiny_{w['traffic']}.json"
         ).write_text(json.dumps(tr))
        workloads.append({"name": name(w["name"]), "config": cfg["name"],
                          "traffic": f"tiny_{w['traffic']}", "chips": 1,
                          "why": "test"})
    out["configs"] = list(configs.values())
    out["workloads"] = workloads
    for m in out["end_to_end"] + out["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [name(w) for w in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(out))
    return root
