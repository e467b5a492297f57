"""Token-choice top-k MoE.

Two implementations with identical math (tests assert equivalence when the
capacity factor is generous):

* ``moe_ref``    — single-device reference: computes every expert densely and
                   combines with the top-k weights. O(E) FLOPs; fine for the
                   reduced (<=4 expert) smoke configs only.
* ``moe_apply_ep`` — production expert-parallel path under ``shard_map``:
                   experts sharded over the ``data`` mesh axis, expert ffn dim
                   over ``model``. Tokens are capacity-bucketed, exchanged with
                   ``lax.all_to_all``, run through blocked per-expert matmuls,
                   and returned. Token-chunked with ``lax.scan`` to bound the
                   top_k× dispatch inflation (DESIGN.md §5).

Router aux loss is the standard load-balance term E·Σ_e f_e·P_e.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init


def moe_init(key, cfg, dtype=jnp.float32):
    d, m = cfg.d_model, cfg.moe
    ks = jax.random.split(key, 4)
    return {
        "w_router": dense_init(ks[0], (d, m.n_experts), dtype=dtype),
        "w_gate": dense_init(ks[1], (m.n_experts, d, m.d_ff),
                             scale=d ** -0.5, dtype=dtype),
        "w_up": dense_init(ks[2], (m.n_experts, d, m.d_ff),
                           scale=d ** -0.5, dtype=dtype),
        "w_down": dense_init(ks[3], (m.n_experts, m.d_ff, d),
                             scale=m.d_ff ** -0.5, dtype=dtype),
    }


def moe_specs(cfg):
    return {"w_router": ("embed", "router"),
            "w_gate": ("experts", "embed", "ff"),
            "w_up": ("experts", "embed", "ff"),
            "w_down": ("experts", "ff", "embed")}


def _router(x, w_router, top_k):
    """x: (T,D) → probs (T,E), weights (T,k), ids (T,k), aux scalar."""
    logits = (x @ w_router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    weights, ids = lax.top_k(probs, top_k)
    weights = weights / jnp.sum(weights, -1, keepdims=True)
    E = probs.shape[-1]
    assign = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], ids].set(1.0)
    f = jnp.mean(assign, 0) / top_k
    p = jnp.mean(probs, 0)
    aux = E * jnp.sum(f * p)
    return probs, weights.astype(x.dtype), ids, aux


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------

def moe_ref(params, x, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (..., D). Returns (y, aux_loss)."""
    shape = x.shape
    xf = x.reshape(-1, shape[-1])
    m = cfg.moe
    _, weights, ids, aux = _router(xf, params["w_router"], m.top_k)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xf, params["w_gate"])) \
        * jnp.einsum("td,edf->tef", xf, params["w_up"])
    y_all = jnp.einsum("tef,efd->ted", h, params["w_down"])     # (T,E,D)
    T = xf.shape[0]
    sel = y_all[jnp.arange(T)[:, None], ids]                     # (T,k,D)
    y = jnp.sum(sel * weights[..., None], axis=1)
    return y.reshape(shape), aux


# ---------------------------------------------------------------------------
# expert-parallel shard_map path
# ---------------------------------------------------------------------------

def _bucketize(keys, n_buckets, cap):
    """Stable-sort rows by bucket key; per-bucket slot positions with a
    capacity limit. Returns (order, key_sorted, pos_clipped, keep_sorted):
    rows beyond ``cap`` in their bucket get pos == cap (overflow slot)."""
    order = jnp.argsort(keys, stable=True)
    ks = keys[order]
    start = jnp.searchsorted(ks, ks, side="left")
    pos = jnp.arange(keys.shape[0]) - start
    keep = pos < cap
    return order, ks, jnp.where(keep, pos, cap), keep


def _moe_chunk(x_c, wr, w_gate, w_up, w_down, *, cfg, ep_axis, tp_axis):
    """One token chunk on one data shard inside shard_map.
    x_c: (t, D) local tokens; expert weights are local shards
    (E_loc, D, F_loc) / (E_loc, F_loc, D)."""
    m = cfg.moe
    t, D = x_c.shape
    ep = lax.axis_size(ep_axis)
    E_loc = w_gate.shape[0]
    _, weights, ids, aux = _router(x_c, wr, m.top_k)
    R = t * m.top_k
    eid = ids.reshape(R)
    dst = eid // E_loc                                   # owning data shard
    C = max(1, math.ceil(R / ep * m.capacity_factor))

    order, dst_s, pos_cl, keep = _bucketize(dst, ep, C)
    rows = x_c[order // m.top_k]
    send_x = jnp.zeros((ep, C + 1, D), x_c.dtype).at[dst_s, pos_cl].set(rows)
    send_le = jnp.zeros((ep, C + 1), jnp.int32).at[dst_s, pos_cl].set(
        (eid % E_loc)[order])
    send_ok = jnp.zeros((ep, C + 1), bool).at[dst_s, pos_cl].set(keep)
    send_x, send_le, send_ok = (a[:, :C] for a in (send_x, send_le, send_ok))

    recv_x = lax.all_to_all(send_x, ep_axis, 0, 0, tiled=True)
    recv_le = lax.all_to_all(send_le, ep_axis, 0, 0, tiled=True)
    recv_ok = lax.all_to_all(send_ok, ep_axis, 0, 0, tiled=True)

    # local per-expert capacity buckets
    R2 = ep * C
    rows2 = recv_x.reshape(R2, D)
    le = jnp.where(recv_ok.reshape(R2), recv_le.reshape(R2), E_loc)
    Ce = max(1, math.ceil(R2 / E_loc * m.capacity_factor))
    order2, le_s, pos2_cl, keep2 = _bucketize(le, E_loc + 1, Ce)
    xb = jnp.zeros((E_loc + 1, Ce + 1, D), x_c.dtype).at[
        le_s, pos2_cl].set(rows2[order2])
    xe = xb[:E_loc, :Ce]

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate)) \
        * jnp.einsum("ecd,edf->ecf", xe, w_up)
    ye = jnp.einsum("ecf,efd->ecd", h, w_down)
    ye = lax.psum(ye, tp_axis)                          # combine ff shards

    # invert local bucketing
    yb = jnp.zeros((E_loc + 1, Ce + 1, D), ye.dtype).at[:E_loc, :Ce].set(ye)
    y_sorted = yb[le_s, pos2_cl] * keep2[:, None].astype(ye.dtype)
    y_rows2 = jnp.zeros((R2, D), ye.dtype).at[order2].set(y_sorted)
    recv_y = y_rows2.reshape(ep, C, D)

    send_y = lax.all_to_all(recv_y, ep_axis, 0, 0, tiled=True)

    # invert dispatch bucketing
    send_y = jnp.pad(send_y, ((0, 0), (0, 1), (0, 0)))
    y_sorted_src = send_y[dst_s, pos_cl] * keep[:, None].astype(ye.dtype)
    y_flat = jnp.zeros((R, D), ye.dtype).at[order].set(y_sorted_src)
    y = jnp.sum(y_flat.reshape(t, m.top_k, D) * weights[..., None], axis=1)
    return y, aux


def _moe_body(wr, w_gate, w_up, w_down, x_loc, *, cfg, ep_axis, tp_axis,
              dp_axes):
    T_loc, D = x_loc.shape
    n_chunks = 1
    for c in range(min(cfg.moe.dispatch_chunks, T_loc), 0, -1):
        if T_loc % c == 0:
            n_chunks = c
            break
    chunks = x_loc.reshape(n_chunks, T_loc // n_chunks, D)
    fn = partial(_moe_chunk, wr=wr, w_gate=w_gate, w_up=w_up, w_down=w_down,
                 cfg=cfg, ep_axis=ep_axis, tp_axis=tp_axis)
    if n_chunks == 1:
        y, aux = fn(chunks[0])
        y, aux = y[None], aux[None]
    else:
        _, (y, aux) = lax.scan(lambda c, x_c: (c, fn(x_c)), 0, chunks)
    aux = lax.pmean(jnp.mean(aux), dp_axes)
    return y.reshape(T_loc, D), aux


def _moe_small_body(wr, w_gate, w_up, w_down, x, *, cfg, ep_axis, tp_axis):
    """Decode-time path: token count too small to shard — tokens are
    replicated; each shard runs only its LOCAL experts densely and the
    outputs combine with one psum. Exact (no capacity drops)."""
    E_loc = w_gate.shape[0]
    eidx = lax.axis_index(ep_axis)
    _, weights, ids, aux = _router(x, wr, cfg.moe.top_k)
    local = (ids >= eidx * E_loc) & (ids < (eidx + 1) * E_loc)
    w_loc = jnp.where(local, weights, 0.0)
    onehot = jax.nn.one_hot(ids - eidx * E_loc, E_loc, dtype=x.dtype)
    w_te = jnp.sum(onehot * w_loc[..., None], axis=1)          # (T, E_loc)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", x, w_gate)) \
        * jnp.einsum("td,edf->tef", x, w_up)
    y_e = jnp.einsum("tef,efd->ted", h, w_down)
    y = jnp.einsum("ted,te->td", y_e, w_te.astype(y_e.dtype))
    y = lax.psum(y, (ep_axis, tp_axis))
    return y, aux


def moe_apply_ep(params, x, cfg, mesh, dp_axes=("data",), ep_axis="data",
                 tp_axis="model"):
    """x: (..., D) with leading dims sharded over ``dp_axes``."""
    shape = x.shape
    xf = x.reshape(-1, shape[-1])
    T = xf.shape[0]
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape.get(a, 1)
    w_specs = (P(), P(ep_axis, None, tp_axis), P(ep_axis, None, tp_axis),
               P(ep_axis, tp_axis, None))
    if T % dp_size != 0 or T < 4 * dp_size:
        body = partial(_moe_small_body, cfg=cfg, ep_axis=ep_axis,
                       tp_axis=tp_axis)
        y, aux = jax.shard_map(
            body, mesh=mesh, in_specs=w_specs + (P(),),
            out_specs=(P(), P()), check_vma=False,
        )(params["w_router"], params["w_gate"], params["w_up"],
          params["w_down"], xf)
        return y.reshape(shape), jnp.mean(aux)
    body = partial(_moe_body, cfg=cfg, ep_axis=ep_axis, tp_axis=tp_axis,
                   dp_axes=dp_axes)
    y, aux = jax.shard_map(
        body, mesh=mesh, in_specs=w_specs + (P(dp_axes, None),),
        out_specs=(P(dp_axes, None), P()), check_vma=False,
    )(params["w_router"], params["w_gate"], params["w_up"],
      params["w_down"], xf)
    return y.reshape(shape), aux


def moe_apply(params, x, cfg, mesh=None, dp_axes=("data",)):
    if mesh is None:
        return moe_ref(params, x, cfg)
    return moe_apply_ep(params, x, cfg, mesh, dp_axes=dp_axes)
