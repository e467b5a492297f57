"""The harness takes any configuration the program's attention, latent
attention, dense and expert layers build: each loads from JSON to its
``ModelConfig``, gets weights drawn by the kind of each leaf
(``leaves.py``), and has its operations counted from the shapes the
program declares, against counts written here from the widths. The two
accepted configurations load, weigh and count as before.

Every ``ARCH_IDS`` entry is covered but three, whose layers the table does
not name: ``rwkv6_3b`` (RWKV-6 time and channel mixing), and
``recurrentgemma_2b`` (RG-LRU recurrent layers) and ``whisper_medium``
(an encoder-decoder with cross-attention and learned positions), which no
task of the benchmark serves."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import leaves, registry, weights
from bench.tests import tiny

SEED = 2**31 + 77
LEFT_OUT = ("rwkv6_3b", "recurrentgemma_2b", "whisper_medium")
NORMS = ("scale", "q_norm", "k_norm", "kv_norm")
BIASES = ("bias", "bq", "bk", "bv", "b_up", "b_down")


def _archs():
    from repro.configs import ARCH_IDS
    return [a for a in ARCH_IDS if a not in LEFT_OUT]


ARCHS = _archs()


def _config(arch, which):
    from repro.configs import get_config, get_reduced
    return (get_reduced if which == "reduced" else get_config)(arch)


def _as_json(cfg) -> dict:
    return json.loads(json.dumps(dataclasses.asdict(cfg)))


def _flat(tree):
    return [(tuple(leaves.name(k) for k in p), v)
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]]


def test_left_out_are_the_ones_without_attention_alone():
    from repro.configs import ARCH_IDS, get_reduced
    other = [a for a in ARCH_IDS
             if get_reduced(a).encoder is not None
             or set(get_reduced(a).layer_kinds()) - {"attn", "mla"}]
    assert set(other) == set(LEFT_OUT)
    assert len(ARCHS) == 9


# -- loader -----------------------------------------------------------------

@pytest.mark.parametrize("which", ["reduced", "published"])
@pytest.mark.parametrize("arch", ARCHS)
def test_json_round_trip(arch, which):
    cfg = _config(arch, which)
    assert registry.model_config(_as_json(cfg)) == cfg


def test_nested_groups_and_lists_become_their_types():
    from repro.configs.base import MLAConfig, MoEConfig
    d = _as_json(_config("minicpm3_4b", "reduced"))
    d.update(layer_pattern=["mla"], act_shard_batch=["data"],
             moe={"n_experts": 8, "top_k": 2, "d_ff": 64})
    cfg = registry.model_config(d)
    assert isinstance(cfg.mla, MLAConfig) and isinstance(cfg.moe, MoEConfig)
    assert cfg.layer_pattern == ("mla",) and cfg.act_shard_batch == ("data",)
    assert cfg.layer_kinds() == ("mla", "mla")


@pytest.mark.parametrize("where", ["top", "mla", "moe"])
def test_unknown_key_is_named(where):
    d = _as_json(_config("minicpm3_4b", "reduced"))
    d["moe"] = {"n_experts": 4, "top_k": 2, "d_ff": 64}
    (d if where == "top" else d[where])["n_routed"] = 3
    with pytest.raises(KeyError, match="n_routed"):
        registry.model_config(d)


# -- weights ----------------------------------------------------------------

def _fan_in(cfg, path, paths) -> int:
    """Each matrix's fan-in, from the configuration's widths."""
    d, name = cfg.d_model, path[-1]
    mla = cfg.mla if "mla" in cfg.layer_kinds() else None
    if name in ("wq", "wk", "wv", "w_dq", "w_dkv", "w_kr", "w_router",
                "w_gate", "w_up", "lm_head", "cls"):
        return d
    if name == "wo":
        return cfg.n_heads * (mla.v_head_dim if mla else cfg.head_dim)
    if name == "w_uq":
        return mla.q_lora_rank
    if name in ("w_uk", "w_uv"):
        return mla.kv_lora_rank
    assert name == "w_down", path
    if path[:-1] + ("w_router",) in paths:
        return cfg.moe.d_ff
    return cfg.dense_d_ff if cfg.moe and cfg.dense_d_ff else cfg.d_ff


def _check_weights(model_dict):
    cfg = registry.model_config(model_dict)
    params = weights.make(registry.program(model_dict), SEED)
    flat = _flat(params)
    paths = {p for p, _ in flat}
    seen = set()
    for path, v in flat:
        v = np.asarray(v, np.float64)
        name = path[-1]
        seen.add(name)
        # the issue's tolerances, or four standard errors of a small leaf
        if name in NORMS:
            tol = max(0.05, 4 * 0.1 / np.sqrt(v.size))
            assert abs(v.mean() - 1) <= tol, (path, v.mean())
        elif name not in BIASES and name != "embed":
            want = _fan_in(cfg, path, paths) ** -0.5
            tol = max(0.1, 4 / np.sqrt(2 * v.size))
            assert abs(v.std() / want - 1) <= tol, (path, v.std(), want)
    return seen


@pytest.mark.parametrize("arch", ARCHS)
def test_weights_by_kind(arch):
    seen = _check_weights(_as_json(_config(arch, "reduced")))
    assert "scale" in seen and "embed" in seen


def test_latent_attention_weights_at_their_rank():
    """MLA's up-projections draw at 1/sqrt(rank) and its norms at 1, not
    at the head count and the layer count the old rule took."""
    seen = _check_weights(_as_json(_config("minicpm3_4b", "reduced")))
    assert {"w_uq", "w_uk", "w_uv", "q_norm", "kv_norm"} <= seen


def test_unknown_leaf_names_its_path():
    f32 = jnp.float32
    tree = {"embed": jax.ShapeDtypeStruct((8, 4), f32),
            "layers": {"seg0": {"l0": {"chan": {
                "w_mystery": jax.ShapeDtypeStruct((4, 4), f32)}}}}}
    with pytest.raises(KeyError, match="layers/seg0/l0/chan/w_mystery"):
        weights.make(None, 0, tree)
    model = _as_json(_config("deepseek_7b", "reduced"))
    with pytest.raises(KeyError, match="layers/seg0/l0/chan/w_mystery"):
        registry.module("work", "model_step").flops(model, 8, "lm_last",
                                                    tree)


# -- operations -------------------------------------------------------------

def _count(cfg, S: int, head: str) -> float:
    """Operations of one request of S tokens, from the widths."""
    d, H = cfg.d_model, cfg.n_heads
    mult = 3 if cfg.glu else 2
    K = min(S, cfg.sliding_window) if cfg.sliding_window else S
    pairs = S * K - K * K / 2 if cfg.causal else S * K
    total = 0
    for i, kind in enumerate(cfg.layer_kinds()):
        if kind == "attn":
            dh = cfg.head_dim
            mix = d * H * dh + 2 * d * cfg.n_kv_heads * dh + H * dh * d
            qk = pv = H * dh
        else:
            m = cfg.mla
            qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
            mix = (d * m.q_lora_rank + m.q_lora_rank * H * qk_head
                   + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                   + m.kv_lora_rank * H * (m.qk_nope_head_dim + m.v_head_dim)
                   + H * m.v_head_dim * d)
            qk, pv = H * qk_head, H * m.v_head_dim
        if cfg.moe and i >= cfg.dense_first_n:
            chan = d * cfg.moe.n_experts + cfg.moe.top_k * mult * d \
                * cfg.moe.d_ff
        elif cfg.moe and cfg.dense_d_ff:
            chan = mult * d * cfg.dense_d_ff
        else:
            chan = mult * d * cfg.d_ff
        total += 2 * S * (mix + chan) + 2 * pairs * (qk + pv)
    return total + 2 * d * (cfg.n_classes if head == "classify"
                            else cfg.vocab)


def _flops(model_dict, S, head="lm_last", shapes=None):
    return registry.module("work", "model_step").flops(model_dict, S, head,
                                                       shapes)


@pytest.mark.parametrize("which", ["reduced", "published"])
@pytest.mark.parametrize("arch", ARCHS)
def test_flops_against_the_widths(arch, which):
    cfg = _config(arch, which)
    for S in (48, 512):
        assert _flops(_as_json(cfg), S) == _count(cfg, S, "lm_last")


def test_flops_kinds():
    """The count takes a gated MLP's three matrices, MLA's matrices and
    score and value widths, and top-k of the routed experts, the router
    and a leading dense layer."""
    qwen = _config("qwen3_8b", "published")
    assert qwen.glu and qwen.moe is None
    per_layer = 2 * (3 * qwen.d_model * qwen.d_ff)
    two = _flops(_as_json(qwen), 1) - _flops(
        _as_json(qwen.replace(glu=False)), 1)
    assert two == qwen.n_layers * per_layer / 3
    mla = _config("minicpm3_4b", "published")
    m, S = mla.mla, 512
    assert _flops(_as_json(mla.replace(causal=False)), S) - _flops(
        _as_json(mla), S) == 2 * (S * S / 2) * mla.n_layers * mla.n_heads * (
            m.qk_nope_head_dim + m.qk_rope_head_dim + m.v_head_dim)
    kimi = _config("kimi_k2_1t_a32b", "published")
    assert kimi.dense_first_n == 1 and kimi.dense_d_ff
    top1 = _as_json(kimi.replace(moe=dataclasses.replace(kimi.moe,
                                                         top_k=1)))
    routed = 2 * 3 * kimi.d_model * kimi.moe.d_ff
    assert _flops(_as_json(kimi), 1) - _flops(top1, 1) == \
        (kimi.n_layers - 1) * routed * (kimi.moe.top_k - 1)


@pytest.mark.parametrize("reps", [0, 3])
def test_shared_expert_counted_in_full(reps):
    """A hand-made layer (stacked ``reps`` deep, or single) with routed
    experts and a shared-expert sub-dict: the shared expert and the router
    count in full, the routed experts at top_k over the router's width,
    the query and value widths from ``wq`` and ``wo``."""
    d, E, k, f, fs, H, dh, S = 32, 8, 2, 16, 24, 4, 8, 64
    lead = (reps,) if reps else ()
    sd = lambda *s: jax.ShapeDtypeStruct(lead + s, jnp.float32)  # noqa: E731
    layer = {"norm1": {"scale": sd(d)},
             "mix": {"wq": sd(d, H, dh), "wk": sd(d, 2, dh),
                     "wv": sd(d, 2, dh), "wo": sd(H, dh, d)},
             "chan": {"w_router": sd(d, E), "w_gate": sd(E, d, f),
                      "w_up": sd(E, d, f), "w_down": sd(E, f, d),
                      "shared": {"w_gate": sd(d, fs), "w_up": sd(d, fs),
                                 "w_down": sd(fs, d)}}}
    shapes = {"embed": jax.ShapeDtypeStruct((100, d), jnp.float32),
              "layers": {"seg0": {"l0": layer}}}
    model = _as_json(_config("dbrx_132b", "reduced"))
    model.update(d_model=d, vocab=100, causal=True,
                 moe=dict(model["moe"], top_k=k))
    n = max(reps, 1)
    mats = (d * H * dh + 2 * d * 2 * dh + H * dh * d
            + d * E + k * 3 * d * f + 3 * d * fs)
    attn = 2 * (S * S / 2) * (H * dh + H * dh)
    assert _flops(model, S, shapes=shapes) == \
        n * (2 * S * mats + attn) + 2 * d * 100


# -- a latent-attention, routed-expert model from JSON ----------------------

DEEPSEEK_LIKE = """{
  "name": "mla-moe", "arch_type": "moe", "n_layers": 5, "d_model": 2048,
  "n_heads": 16, "n_kv_heads": 16, "d_ff": 10944, "vocab": 102400,
  "mixer": "mla", "layer_pattern": ["mla"],
  "mla": {"q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "v_head_dim": 128},
  "moe": {"n_experts": 64, "top_k": 6, "d_ff": 1408},
  "dense_first_n": 1, "dense_d_ff": 10944, "norm": "rmsnorm",
  "act": "silu", "glu": true, "tie_embeddings": false
}"""


def test_latent_expert_model_from_json():
    """Nested ``mla`` and ``moe`` and a list ``layer_pattern``: the widths
    count as the count from the widths says, and the tiny copy builds and
    weighs on the CPU with every kind of layer. (The program's MLA needs a
    low-rank query, so ``q_lora_rank`` is set here.)"""
    model = json.loads(DEEPSEEK_LIKE)
    cfg = registry.model_config(model)
    assert _flops(model, 512) == _count(cfg, 512, "lm_last")
    small = tiny.cut(model)
    scfg = registry.model_config(small)
    assert scfg.layer_kinds() == ("mla", "mla")
    assert scfg.dense_first_n == 1 and scfg.moe.n_experts == 4
    seen = _check_weights(small)
    assert {"w_router", "w_uk", "kv_norm"} <= seen
    assert _flops(small, tiny.SEQ) == _count(scfg, tiny.SEQ, "lm_last")


@pytest.mark.parametrize("arch", ARCHS)
def test_tiny_cut_keeps_every_kind(arch):
    cfg = _config(arch, "published")
    small = registry.model_config(tiny.cut(_as_json(cfg)))
    assert set(small.layer_kinds()) == set(cfg.layer_kinds())
    assert small.d_model == tiny.TINY_MODEL["d_model"]
    assert (small.n_kv_heads < small.n_heads) == \
        (cfg.n_kv_heads < cfg.n_heads)
    if cfg.mla:
        m = small.mla
        assert max(m.q_lora_rank, m.kv_lora_rank) <= tiny.RANK
        assert max(m.qk_nope_head_dim, m.qk_rope_head_dim,
                   m.v_head_dim) <= tiny.HEAD
    if cfg.moe:
        assert small.moe.n_experts == min(cfg.moe.n_experts, tiny.EXPERTS)
        assert small.moe.top_k <= tiny.TOP_K
        assert small.moe.d_ff <= tiny.EXPERT_FF
        assert small.dense_first_n == min(cfg.dense_first_n, 1)
        assert small.n_layers > small.dense_first_n
    if cfg.dense_d_ff:
        assert small.dense_d_ff <= tiny.EXPERT_FF
    _check_weights(tiny.cut(_as_json(cfg)))


def test_tiny_window_under_the_tiny_length():
    model = dict(_as_json(_config("qwen2_1_5b", "reduced")),
                 sliding_window=4096)
    small = tiny.cut(model)
    assert small["sliding_window"] < tiny.SEQ
    cfg = registry.model_config(small)
    assert _flops(small, tiny.SEQ) == _count(cfg, tiny.SEQ, "lm_last")


# -- the accepted configurations, as the parent had them --------------------

ACCEPTED = ["bert_base_rope_allmiss.templ_r80",
            "gpt2_small_rope_allmiss.prefill_templ_r80"]


def _parent_fan_in(name: str, shape) -> int:
    if name in ("wq", "wk", "wv"):
        return shape[-3]
    if name == "wo":
        return shape[-3] * shape[-2]
    return shape[-2]


def _parent_leaf(key, name: str, s):
    shape, dt = s.shape, s.dtype
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "scale":
        v = 1.0 + 0.1 * z
    elif len(shape) == 0 or name.startswith("b"):
        v = 0.02 * z
    elif name == "embed":
        v = 0.02 * z
    else:
        v = z * _parent_fan_in(name, shape) ** -0.5
    return v.astype(dt)


def _parent_make(model, seed: int):
    """``weights.make`` as the parent commit had it, frozen."""
    key = jax.random.PRNGKey(seed)
    shapes = jax.eval_shape(model.init, key)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def init(key):
        return treedef.unflatten([
            _parent_leaf(jax.random.fold_in(key, i), leaves.name(path[-1]),
                         s)
            for i, (path, s) in enumerate(flat)])

    return init(key)


@pytest.mark.parametrize("workload", ACCEPTED)
def test_accepted_config_loads_as_before(workload):
    from repro.configs.base import ModelConfig
    model = tiny.config(workload)["model"]
    assert registry.model_config(model) == ModelConfig(**model)
    # the tiny copy is the parent's: TINY_MODEL over the model dict
    assert tiny.cut(model) == dict(model, **tiny.TINY_MODEL)


@pytest.mark.parametrize("workload", ACCEPTED)
def test_accepted_tiny_weights_bitwise(workload):
    model = registry.program(tiny.cut(tiny.config(workload)["model"]))
    got, want = _flat(weights.make(model, SEED)), _flat(
        _parent_make(model, SEED))
    assert [p for p, _ in got] == [p for p, _ in want]
    for (path, g), (_, w) in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w)), path


@pytest.mark.parametrize("workload", ACCEPTED)
def test_accepted_fan_ins(workload):
    """At the published widths, from the declared shapes alone."""
    model = registry.program(tiny.config(workload)["model"])
    n = 0
    for path, s in _flat(weights.shapes_of(model)):
        leaf = leaves.kind(path)
        if leaf.kind == "matrix":
            assert leaves.fan_in(leaf, s.shape) == \
                _parent_fan_in(path[-1], s.shape), path
            n += 1
        else:
            assert (leaf.kind == "norm") == (path[-1] == "scale"), path
            assert (leaf.kind == "bias") == path[-1].startswith("b"), path
    assert n >= 6


@pytest.mark.parametrize("workload,head,want", [
    (ACCEPTED[0], "classify", 96_636_767_232),
    (ACCEPTED[1], "lm_last", 91_882_120_704)])
def test_accepted_flops(workload, head, want):
    cfg = tiny.config(workload)
    assert cfg["head"] == head
    assert _flops(cfg["model"], 512, head) == want
