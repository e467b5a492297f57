import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Performance hillclimbing (§Perf) — the three selected pairs.

Each iteration is hypothesis → change → re-lower → re-analyse, recorded as
a tagged dry-run JSON next to the baselines:

1. minicpm3-4b × train_4k      (worst useful ratio, 0.09; peak > HBM)
   - it1 vocab padding to a 256 multiple (shardable lm_head/embedding)
   - it2 MLA latent-dim sharding (q_lora/kv_lora → model)
   - it3 activation sharding constraint in the layer scan (peak memory)
2. rwkv6-3b × train_4k         (most collective-bound)
   - it1 replicate time-mix square projections (kill mid-head resharding)
   - it2 + FSDP embeddings over data (vocab 65536 divides cleanly)
3. deepseek-7b × prefill (paper-representative, attention-heavy)
   - it1 memo-bucketed prefill at paper-scale S=2048: the AttMemo
     technique itself, expressed at pod scale — hit sub-batch runs
     APM·V only (device-sharded DB gather), miss sub-batch full attention
   - it2 hit-rate sweep (roofline vs memo rate)

Run:  python -m repro.launch.hillclimb [--pair 1|2|3]
"""
import argparse
import json
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import InputShape
from repro.launch.dryrun import run_one
from repro.launch.hlo_utils import collective_bytes, cost_summary
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import abstract_params
from repro.models import attention as attn_mod
from repro.models import backbone as bb
from repro.models import build_model
from repro.sharding.rules import (batch_shardings, logical_to_shardings,
                                  make_rules)

OUT = "experiments/hillclimb"


def _round_up(x, m):
    return (x + m - 1) // m * m


def _save(rec, name):
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, name + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    c = rec.get("corrected", {})
    print(f"  {name}: status={rec['status']} "
          f"flops={c.get('flops', 0):.3e} bytes={c.get('bytes', 0):.3e} "
          f"coll={c.get('collective_bytes', 0):.3e} "
          f"peak={rec.get('full', {}).get('peak_bytes', 0)/1e9:.2f}GB")
    return rec


# ---------------------------------------------------------------- pair 1

def pair1():
    print("[pair1] minicpm3-4b x train_4k")
    cfg = get_config("minicpm3_4b")

    # it1: pad vocab so lm_head/embedding shard over model
    cfg_pad = cfg.replace(vocab=_round_up(cfg.vocab, 256))
    _save(run_one("minicpm3_4b", "train_4k", False, tag="it1_pad_vocab",
                  cfg_override=cfg_pad), "minicpm3_train_it1_pad_vocab")

    # it2: + shard the MLA latent dims over model (heads 40 can't shard
    # over 16; the latent contraction dims can: 768/16, 256/16)
    _save(run_one("minicpm3_4b", "train_4k", False,
                  tag="it2_latent_shard", cfg_override=cfg_pad,
                  rules_overrides={"q_lora": "model", "kv_lora": "model"}),
          "minicpm3_train_it2_latent_shard")

    # it3: + FSDP (embed over data) — pulls saved-activation + opt memory
    _save(run_one("minicpm3_4b", "train_4k", False,
                  tag="it3_fsdp", cfg_override=cfg_pad,
                  rules_overrides={"q_lora": "model", "kv_lora": "model",
                                   "embed": "data"}),
          "minicpm3_train_it3_fsdp")


# ---------------------------------------------------------------- pair 2

def pair2():
    print("[pair2] rwkv6-3b x train_4k")
    # it1: replicate time-mix square projections — their model-axis shards
    # (2560/16 = 160) split the 64-wide wkv heads mid-state, forcing
    # resharding collectives around every scan step
    _save(run_one("rwkv6_3b", "train_4k", False, tag="it1_replicate_timemix",
                  rules_overrides={"heads_embed": None}),
          "rwkv6_train_it1_replicate_timemix")

    # it2: + FSDP embeddings (vocab 65536 divides 16 cleanly); grads for
    # the now-replicated time-mix weights all-reduce over data only
    _save(run_one("rwkv6_3b", "train_4k", False, tag="it2_fsdp",
                  rules_overrides={"heads_embed": None, "embed": "data"}),
          "rwkv6_train_it2_fsdp")

    # it3: shard time-mix output dim over data instead (weight-gathered
    # FSDP-style) — tests whether collectives stay gone with less
    # replicated weight memory
    _save(run_one("rwkv6_3b", "train_4k", False, tag="it3_timemix_data",
                  rules_overrides={"heads_embed": "data", "embed": "data"}),
          "rwkv6_train_it3_timemix_data")

    # it4: it1 (replicated time-mix, collective-free recurrence) + shard
    # the scan batch/state over BOTH axes — the 21.5 GB of saved wkv
    # states (4096 steps x (B,40,64,64) bf16) was it1's peak-memory cost;
    # batch 256 divides 256 chips exactly
    cfg4 = get_config("rwkv6_3b").replace(
        act_shard_batch=("data", "model"))
    _save(run_one("rwkv6_3b", "train_4k", False, tag="it4_state_batch_shard",
                  cfg_override=cfg4,
                  rules_overrides={"heads_embed": None}),
          "rwkv6_train_it4_state_batch_shard")


# ---------------------------------------------------------------- pair 3

def _prefill_memo_step(mesh, seq, batch, hit_frac, n_db=64):
    """AttMemo at pod scale: the batch is pre-bucketed (engine-level
    bucketing, DESIGN.md §2) into ``B_hit`` sequences whose APMs come from
    the device-sharded DB (APM·V only — no QKᵀ, no softmax) and ``B_miss``
    running full attention."""
    cfg = get_config("deepseek_7b")
    dp = ("data",)
    model = build_model(cfg, mesh=mesh, dp_axes=dp, layer_loop="unroll")
    rules = make_rules(cfg, mesh)
    params_abs = abstract_params(model)
    params_sh = logical_to_shardings(model.specs(), rules, mesh, params_abs)
    B_hit = _round_up(int(batch * hit_frac), 16) if hit_frac else 0
    B_hit = min(B_hit, batch - 16) if hit_frac < 1.0 else batch
    B_miss = batch - B_hit
    L = cfg.n_layers

    def memo_forward(params, toks_hit, apm_idx, db, toks_miss):
        outs = []
        if toks_hit.shape[0]:
            h = bb.embed_tokens(params, toks_hit, cfg)
            for li, kind, lp in bb.iter_layers(params, cfg):
                x = bb.norm_apply(lp["norm1"], h, cfg.norm)
                apm = jnp.take(db, apm_idx[:, li], axis=0)
                h = h + attn_mod.gqa_apply_memo(lp["mix"], x, cfg, apm)
                x = bb.norm_apply(lp["norm2"], h, cfg.norm)
                from repro.models.layers import mlp_apply
                h = h + mlp_apply(lp["chan"], x, cfg.act, cfg.glu)
            outs.append(bb.logits_from_hidden(params, h[:, -1:], cfg)[:, 0])
        if toks_miss.shape[0]:
            logits, _, _ = model.forward(params, {"tokens": toks_miss})
            outs.append(logits[:, -1])
        return jnp.concatenate(outs, 0)

    db_abs = jax.ShapeDtypeStruct((n_db, cfg.n_heads, seq, seq),
                                  jnp.bfloat16)
    args = (params_abs,
            jax.ShapeDtypeStruct((B_hit, seq), jnp.int32),
            jax.ShapeDtypeStruct((B_hit, L), jnp.int32),
            db_abs,
            jax.ShapeDtypeStruct((B_miss, seq), jnp.int32))
    tok_sh = lambda b: NamedSharding(
        mesh, P("data", None) if b % 16 == 0 and b else P())
    in_sh = (params_sh, tok_sh(B_hit),
             NamedSharding(mesh, P()),
             NamedSharding(mesh, P("data")),       # DB sharded over entries
             tok_sh(B_miss))
    return memo_forward, args, in_sh, {"B_hit": B_hit, "B_miss": B_miss,
                                       "n_db": n_db, "seq": seq}


def pair3():
    print("[pair3] deepseek-7b x prefill (paper-representative)")
    mesh = make_production_mesh()
    seq, batch = 2048, 256          # paper-scale sequence; APM DB feasible
    for tag, hit in (("it0_baseline", 0.0), ("it1_hit50", 0.5),
                     ("it2_hit94", 0.94)):
        fn, args, in_sh, meta = _prefill_memo_step(mesh, seq, batch, hit)
        rec = {"arch": "deepseek_7b", "shape": f"prefill_{seq}",
               "mesh": "pod256", "devices": 256, "tag": tag, "meta": meta,
               "status": "ok"}
        try:
            with jax.set_mesh(mesh):
                compiled = jax.jit(fn, in_shardings=in_sh).lower(
                    *args).compile()
            m = cost_summary(compiled)
            m["collectives"] = collective_bytes(compiled.as_text())
            rec["full"] = m
            rec["corrected"] = {"flops": m["flops"], "bytes": m["bytes"],
                                "collective_bytes": m["collectives"]["total"]}
        except Exception as e:  # noqa: BLE001
            rec["status"] = "error"
            rec["error"] = f"{type(e).__name__}: {e}"
        _save(rec, f"deepseek_prefill2k_{tag}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", type=int, default=0)
    args = ap.parse_args()
    if args.pair in (0, 1):
        pair1()
    if args.pair in (0, 2):
        pair2()
    if args.pair in (0, 3):
        pair3()


if __name__ == "__main__":
    main()
