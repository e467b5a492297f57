"""MoE: expert-parallel shard_map path vs dense reference + properties."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_reduced
from repro.configs.base import MoEConfig
from repro.models import moe as moe_mod


def _cfg(E=4, k=2, d_ff=64, cf=8.0, chunks=2):
    return get_reduced("dbrx_132b").replace(
        moe=MoEConfig(n_experts=E, top_k=k, d_ff=d_ff,
                      capacity_factor=cf, dispatch_chunks=chunks))


def test_ref_shapes_and_aux():
    cfg = _cfg()
    params = moe_mod.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    y, aux = moe_mod.moe_ref(params, x, cfg)
    assert y.shape == x.shape
    assert np.isfinite(float(aux)) and float(aux) >= 1.0 - 1e-3


def test_router_topk_weights_normalized():
    cfg = _cfg()
    params = moe_mod.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model))
    _, w, ids, _ = moe_mod._router(x, params["w_router"], cfg.moe.top_k)
    np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)), 1.0, rtol=1e-5)
    assert int(jnp.max(ids)) < cfg.moe.n_experts


@given(t=st.integers(2, 17), buckets=st.integers(1, 5),
       cap=st.integers(1, 8), seed=st.integers(0, 2 ** 16))
@settings(max_examples=30, deadline=None)
def test_bucketize_property(t, buckets, cap, seed):
    """_bucketize: every kept row lands in a unique (bucket, slot<cap);
    per-bucket keeps == min(count, cap)."""
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(rng.integers(0, buckets, t), jnp.int32)
    order, ks, pos, keep = moe_mod._bucketize(keys, buckets, cap)
    order, ks, pos, keep = map(np.asarray, (order, ks, pos, keep))
    assert (np.sort(order) == np.arange(t)).all()
    assert (ks == keys[order]).all()
    seen = set()
    for b, p, k in zip(ks, pos, keep):
        if k:
            assert p < cap
            assert (b, p) not in seen
            seen.add((b, p))
    for b in range(buckets):
        cnt = int((keys == b).sum())
        assert int(keep[ks == b].sum()) == min(cnt, cap)


def test_ep_equivalence_multidevice():
    """Run the EP path on a 4x2 fake-device mesh in a subprocess (device
    count is locked at first jax init, so this must be isolated)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_reduced
from repro.configs.base import MoEConfig
from repro.launch.mesh import make_host_mesh
from repro.models import moe as moe_mod

mesh = make_host_mesh(4, 2)
cfg = get_reduced("dbrx_132b").replace(
    moe=MoEConfig(n_experts=4, top_k=2, d_ff=64, capacity_factor=8.0,
                  dispatch_chunks=2))
params = moe_mod.moe_init(jax.random.PRNGKey(1), cfg)
x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, cfg.d_model)) * 0.5
y_ref, _ = moe_mod.moe_ref(params, x, cfg)
with jax.set_mesh(mesh):
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    y_ep, _ = jax.jit(lambda p, xx: moe_mod.moe_apply_ep(p, xx, cfg, mesh))(params, xs)
np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ep),
                           rtol=2e-4, atol=2e-4)
txt = jax.jit(lambda p, xx: moe_mod.moe_apply_ep(p, xx, cfg, mesh)
              ).lower(params, xs).compile().as_text()
assert "all-to-all" in txt, "EP dispatch must lower to all-to-all"
print("EP-OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "EP-OK" in out.stdout, out.stderr[-3000:]


def test_capacity_drops_are_bounded():
    """With capacity_factor=1.0 some tokens drop, but the output stays
    finite and within a sane norm of the reference."""
    cfg = _cfg(cf=1.0)
    params = moe_mod.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    y_ref, _ = moe_mod.moe_ref(params, x, cfg)
    # single-device mesh exercise of the EP code path
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    with jax.set_mesh(mesh):
        y_ep, _ = moe_mod.moe_apply_ep(params, x, cfg, mesh)
    assert np.isfinite(np.asarray(y_ep)).all()
    # dropped tokens produce zero expert output -> norm can only shrink
    assert (np.linalg.norm(np.asarray(y_ep))
            <= np.linalg.norm(np.asarray(y_ref)) * 1.05)


def test_ep_small_token_path_equivalence():
    """Decode-time MoE path (replicated tokens, local experts + psum) ==
    dense reference, on a 4x2 fake-device mesh."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_reduced
from repro.configs.base import MoEConfig
from repro.launch.mesh import make_host_mesh
from repro.models import moe as moe_mod

mesh = make_host_mesh(4, 2)
cfg = get_reduced("dbrx_132b").replace(
    moe=MoEConfig(n_experts=4, top_k=2, d_ff=64, capacity_factor=8.0))
params = moe_mod.moe_init(jax.random.PRNGKey(1), cfg)
# T=6 tokens < 4*dp_size -> the small path triggers
x = jax.random.normal(jax.random.PRNGKey(2), (6, cfg.d_model)) * 0.5
y_ref, _ = moe_mod.moe_ref(params, x, cfg)
with jax.set_mesh(mesh):
    y_ep, _ = jax.jit(lambda p, xx: moe_mod.moe_apply_ep(p, xx, cfg, mesh))(params, x)
np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ep),
                           rtol=2e-4, atol=2e-4)
print("SMALL-EP-OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=os.path.dirname(
                             os.path.dirname(os.path.abspath(__file__))))
    assert "SMALL-EP-OK" in out.stdout, out.stderr[-3000:]
