"""Sharded memo store — the multi-device tier (DESIGN.md §2.12).

One host's memo store stops scaling at one accelerator's HBM: PR 1–8
made the single-host store fast, compressed, crash-consistent and
disk-backed, but its device tier is a single replicated allocation.
This module partitions the device tier over a mesh axis so capacity and
search throughput scale with device count:

* ``ShardedDeviceDB`` / ``ShardedDeviceIndex`` — every row-indexed leaf
  (embedding table, slot map, codec-part arenas) is laid out as a flat
  ``(S*M, ...)`` array row-sharded over the ``store`` axis: shard ``s``
  owns positions ``[s*M, (s+1)*M)``. Routing state (k-means centroids +
  their owning shard) and a small hot-entry set replicate everywhere.

* Centroid-routed search: a query computes its ``route_nprobe`` nearest
  centroids; only shards owning one of them compete (the others submit
  +inf), so the per-shard work stays one local matmul. Every shard also
  scores the replicated hot set (top reuse-count rows, refreshed each
  maintenance sync) so skewed traffic against a single hot shard never
  serializes the batch. Shard winners — distance, GLOBAL slot id, and
  the candidate's codec-part rows — combine through exactly ONE
  ``all_gather`` + argmin under ``shard_map``: the one-barrier-per-batch
  invariant holds in meshed mode (trace-counted in tests/test_shard.py).

* ``ShardedMemoStore`` — admission and CLOCK eviction become per-shard
  under the same global byte budget: a dirty slot routes to the shard
  owning its nearest centroid; a full shard runs a shard-local CLOCK
  sweep before spilling to the emptiest shard. Delta sync ships only
  shard-local dirty positions and bumps only the touched shards'
  generations (``shard_snapshots``); the global ``StoreSnapshot``
  publish protocol is unchanged.

``mesh_search`` is the plain entry-sharded exact search (the retired
``database.distributed_search``), still used by the flat/clustered
indexes when constructed with a mesh.
"""
from __future__ import annotations

import math
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.codec import hbm_form
from repro.core.database import pad_delta_parts, pad_delta_pow2
from repro.core.faults import MemoStoreError
from repro.core.index import TOMBSTONE, _kmeans
from repro.core.registry import DEVICE_INDEXES
from repro.core.store import MemoStore
from repro.sharding.rules import memo_row_spec

# module-level indirection so the trace-time collective count is
# observable: tests monkeypatch ``shard._ALL_GATHER`` and assert the
# whole sharded search traces exactly ONE cross-shard collective
_ALL_GATHER = jax.lax.all_gather


def make_store_mesh(n_shards: Optional[int] = None,
                    axis: str = "store") -> Mesh:
    """A 1-D mesh over the local devices for the sharded store. Asking
    for more shards than ``jax.device_count()`` raises: a store that
    quietly shrank would hold a fraction of the budget it was given. The
    8-way CPU runs set ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    before jax import."""
    devs = np.asarray(jax.devices())
    n = devs.size if n_shards is None else int(n_shards)
    if not 1 <= n <= devs.size:
        raise ValueError(f"{n} store shards requested but {devs.size} "
                         f"{devs[0].platform} device(s) exist")
    return Mesh(devs[:n], (axis,))


def mesh_search(embs, queries, mesh, *, db_axis: str = "data"):
    """Distributed exact top-1 over an entry-sharded embedding table:
    each shard computes its local argmin (one MXU matmul), then a small
    (n_shards, B) all-gather + global argmin. embs: (N, dim) sharded
    P(db_axis); queries: (B, dim) replicated. Returns (sq_dists (B,),
    global_idx (B,)). The flat/clustered device indexes fall back to
    this under a mesh; the full sharded store uses
    ``ShardedDeviceIndex.search_fetch`` (centroid routing + hot set +
    fetch in the same single collective)."""
    def body(db, q):
        n_loc = db.shape[0]
        d2 = (jnp.sum(q * q, -1, keepdims=True)
              - 2.0 * q @ db.T + jnp.sum(db * db, -1)[None, :])
        loc_arg = jnp.argmin(d2, axis=-1)
        loc_min = jnp.take_along_axis(d2, loc_arg[:, None], -1)[:, 0]
        shard = jax.lax.axis_index(db_axis)
        gidx = loc_arg + shard * n_loc
        mins, idxs = _ALL_GATHER((loc_min, gidx), db_axis)  # (shards, B)
        best = jnp.argmin(mins, axis=0)                     # (B,)
        cols = jnp.arange(q.shape[0])
        return mins[best, cols], idxs[best, cols]

    smap = jax.shard_map(body, mesh=mesh, in_specs=(P(db_axis, None), P()),
                         out_specs=(P(), P()), check_vma=False)
    return smap(embs, queries)


class ShardSnapshot(NamedTuple):
    """Per-shard publish record: generation bumps only when THAT shard's
    rows changed, so a reader (delta replication, the benchmarks' balance
    probe) can tell which shards a sync actually touched."""
    shard: int
    generation: int
    live: int          # occupied positions
    free: int          # free positions remaining


class ShardedDeviceDB:
    """Position-indexed device arenas, row-sharded over the mesh axis.

    Same surface as ``DeviceDB`` (``parts`` tuple consumed by the fused
    jit, ``update`` scatter deltas, ``transfer_bytes``), but rows are
    device POSITIONS (shard*M + row), not host slot ids — the sharded
    index returns each winner's codec rows from the combine, so the
    engine never indexes these arenas by slot. Float16 parts are held as
    their int16 bit pattern (``codec.hbm_form``), as in ``DeviceDB``."""

    def __init__(self, host_parts: Sequence[np.ndarray], mesh: Mesh,
                 axis: str, codec=None):
        self.codec = codec
        self.mesh = mesh
        self.axis = axis
        parts = []
        for p in host_parts:
            p = hbm_form(p)
            sh = NamedSharding(mesh, memo_row_spec(mesh, p.ndim, axis=axis,
                                                   shape=p.shape))
            parts.append(jax.device_put(p, sh))
        self.parts: Tuple[jnp.ndarray, ...] = tuple(parts)
        self.transfer_bytes = sum(int(p.nbytes) for p in self.parts)

    @property
    def capacity(self) -> int:
        return int(self.parts[0].shape[0])

    @property
    def nbytes(self) -> int:
        return sum(int(p.nbytes) for p in self.parts)

    def __len__(self):
        return self.capacity

    def update(self, positions: np.ndarray,
               host_parts: Sequence[np.ndarray]) -> int:
        """Scatter compressed rows into device positions (pow2-padded so
        compiled scatter shapes stay log2-bounded). Returns bytes."""
        positions = np.asarray(positions).reshape(-1)
        if positions.size == 0:
            return 0
        if int(positions.max()) >= self.capacity:
            raise ValueError("sharded delta past device position capacity")
        pos, parts = pad_delta_parts(positions, host_parts)
        pos_dev = jnp.asarray(pos)
        shipped = int(pos.size * 8)
        new_parts = []
        for arr, spec, p in zip(self.parts, self.codec.parts, parts):
            p = jnp.asarray(hbm_form(np.asarray(p, spec.dtype)))
            new_parts.append(arr.at[pos_dev].set(p))
            shipped += int(p.nbytes)
        self.parts = tuple(new_parts)
        self.transfer_bytes += shipped
        return shipped


class ShardedDeviceIndex:
    """Centroid-routed sharded top-1 index (DESIGN.md §2.12).

    Row-sharded state: ``table`` (S*M, dim) embeddings at device
    positions, ``slot_at`` (S*M,) the GLOBAL host slot each position
    holds (−1 free). Replicated state: k-means ``centroids`` (C, dim) +
    ``owner`` (C,) shard id per centroid, and the hot set (``hot_table``
    / ``hot_slots`` / ``hot_parts`` — top reuse-count rows).

    ``search_fetch`` runs the whole search under ``shard_map`` with ONE
    ``all_gather`` combine and returns (d2, slot, codec rows) — global
    slot ids, so the engine's length gate and reuse drain are unchanged
    from the single-host path."""

    is_sharded = True

    def __init__(self, dim: int, *, mesh: Mesh, axis: str = "store",
                 capacity: int = 0, nprobe: int = 4, hot_k: int = 32,
                 interpret: Optional[bool] = None, **_):
        self.dim = dim
        self.mesh = mesh
        self.axis = axis
        self.n_shards = int(mesh.shape[axis])
        self.nprobe = max(1, int(nprobe))
        self.hot_k = max(0, int(hot_k))
        self.interpret = interpret
        self.transfer_bytes = 0
        self._table: Optional[jnp.ndarray] = None
        self._slot_at: Optional[jnp.ndarray] = None
        self._centroids: Optional[jnp.ndarray] = None
        self._owner: Optional[jnp.ndarray] = None
        H = max(1, self.hot_k)
        self._hot_table = jnp.full((H, dim), TOMBSTONE, jnp.float32)
        self._hot_slots = jnp.full((H,), -1, jnp.int32)
        self._hot_parts: Tuple[jnp.ndarray, ...] = ()
        self._norms: Optional[jnp.ndarray] = None
        if capacity:
            self.load(np.full((capacity, dim), TOMBSTONE, np.float32),
                      np.full((capacity,), -1, np.int64))
            self.set_centroids(
                np.full((1, dim), TOMBSTONE, np.float32),
                np.zeros((1,), np.int32))

    # ------------------------------------------------------------- state
    def _row_sharding(self, ndim: int, shape) -> NamedSharding:
        return NamedSharding(self.mesh, memo_row_spec(
            self.mesh, ndim, axis=self.axis, shape=tuple(shape)))

    @property
    def capacity(self) -> int:
        return 0 if self._table is None else int(self._table.shape[0])

    def __len__(self):
        return self.capacity

    def load(self, table: np.ndarray, slot_at: np.ndarray) -> None:
        """Full rebuild: upload position-indexed table + slot map."""
        table = np.asarray(table, np.float32)
        slot_at = np.asarray(slot_at, np.int64)
        self._table = jax.device_put(
            table, self._row_sharding(2, table.shape))
        self._slot_at = jax.device_put(
            slot_at, self._row_sharding(1, slot_at.shape))
        self._norms = None
        self.transfer_bytes += int(table.nbytes + slot_at.nbytes)

    def set_centroids(self, centroids: np.ndarray,
                      owner: np.ndarray) -> None:
        self._centroids = jnp.asarray(np.asarray(centroids, np.float32))
        self._owner = jnp.asarray(np.asarray(owner, np.int32))
        self.transfer_bytes += int(self._centroids.nbytes
                                   + self._owner.nbytes)

    def set_hot(self, table: np.ndarray, slots: np.ndarray,
                parts: Tuple[np.ndarray, ...]) -> int:
        """Refresh the replicated hot set (fixed H rows — shapes never
        change across refreshes, so no consumer retrace). Returns the
        bytes shipped."""
        self._hot_table = jnp.asarray(np.asarray(table, np.float32))
        self._hot_slots = jnp.asarray(np.asarray(slots, np.int32))
        self._hot_parts = tuple(jnp.asarray(hbm_form(p)) for p in parts)
        shipped = int(self._hot_table.nbytes + self._hot_slots.nbytes
                      + sum(int(p.nbytes) for p in self._hot_parts))
        self.transfer_bytes += shipped
        return shipped

    def update(self, positions: np.ndarray, embs: np.ndarray,
               slots: np.ndarray) -> int:
        """Delta: write embedding rows + their global slot ids at device
        positions (pow2-padded scatters)."""
        positions = np.asarray(positions).reshape(-1)
        if positions.size == 0:
            return 0
        pos, vals = pad_delta_pow2(positions,
                                   np.asarray(embs, np.float32))
        _, sl = pad_delta_pow2(positions, np.asarray(slots, np.int64))
        pos_dev = jnp.asarray(pos)
        self._table = self._table.at[pos_dev].set(jnp.asarray(vals))
        self._slot_at = self._slot_at.at[pos_dev].set(jnp.asarray(sl))
        self._norms = None
        shipped = int(vals.nbytes + sl.nbytes + pos.size * 8)
        self.transfer_bytes += shipped
        return shipped

    def kill(self, positions: np.ndarray) -> int:
        """Tombstone freed device positions (slot −1, TOMBSTONE row)."""
        positions = np.asarray(positions).reshape(-1)
        if positions.size == 0:
            return 0
        pos, _ = pad_delta_pow2(positions)
        pos_dev = jnp.asarray(pos)
        self._table = self._table.at[pos_dev].set(TOMBSTONE)
        self._slot_at = self._slot_at.at[pos_dev].set(-1)
        self._norms = None
        shipped = int(pos.size * 8)
        self.transfer_bytes += shipped
        return shipped

    # ------------------------------------------------------------ search
    @property
    def search_args(self):
        """The traced pytree the fused jit consumes — per-row ‖d‖² for
        the sharded table, centroid norms and the hot set are cached per
        mutation generation at publish, exactly like the flat index."""
        if self._norms is None:
            self._norms = jnp.sum(self._table * self._table, axis=-1)
        cnorms = jnp.sum(self._centroids * self._centroids, axis=-1)
        hnorms = jnp.sum(self._hot_table * self._hot_table, axis=-1)
        return (self._table, self._norms, self._slot_at, self._centroids,
                cnorms, self._owner, self._hot_table, hnorms,
                self._hot_slots, self._hot_parts)

    def _combine(self, args, q, parts, with_rows: bool):
        """The one-collective sharded search. Local per shard: one
        (B, M) matmul + centroid-routing mask + the replicated hot-set
        scores; global: ONE pytree ``all_gather`` of each shard's winner
        (distance, slot id, codec rows) followed by a replicated argmin.
        Masked shards (no probed centroid owned) submit +inf."""
        (table, norms, slot_at, cents, cnorms, owner, hot_t, hnorms,
         hot_s, hot_parts) = args
        axis = self.axis
        nprobe = min(self.nprobe, int(cents.shape[0]))

        def body(table, norms, slot_at, cents, cnorms, owner, hot_t,
                 hnorms, hot_s, q, hot_parts, parts):
            me = jax.lax.axis_index(axis)
            qq = jnp.sum(q * q, axis=-1, keepdims=True)        # (B, 1)
            d2 = qq + norms[None, :] - 2.0 * (q @ table.T)     # (B, M)
            loc = jnp.argmin(d2, axis=1)                       # (B,)
            dloc = jnp.take_along_axis(d2, loc[:, None], 1)[:, 0]
            # centroid routing: only shards owning one of the query's
            # nprobe nearest centroids compete for it
            cd = cnorms[None, :] - 2.0 * (q @ cents.T)         # (B, C)
            _, probes = jax.lax.top_k(-cd, nprobe)             # (B, P)
            mine = jnp.any(owner[probes] == me, axis=1)        # (B,)
            dloc = jnp.where(mine, dloc, jnp.float32(np.inf))
            sloc = slot_at[loc]
            # replicated hot set: every shard scores it (H is tiny), so
            # a skew-hot entry is served without routing to its shard
            dh = qq + hnorms[None, :] - 2.0 * (q @ hot_t.T)    # (B, H)
            hloc = jnp.argmin(dh, axis=1)
            dhot = jnp.take_along_axis(dh, hloc[:, None], 1)[:, 0]
            use_hot = dhot < dloc
            dbest = jnp.where(use_hot, dhot, dloc)
            sbest = jnp.where(use_hot, hot_s[hloc].astype(sloc.dtype),
                              sloc)
            payload = [dbest, sbest]
            if with_rows:
                for p, hp in zip(parts, hot_parts):
                    lr = jnp.take(p, loc, axis=0)              # (B, ...)
                    hr = jnp.take(hp, hloc, axis=0)
                    sel = use_hot.reshape(
                        (-1,) + (1,) * (lr.ndim - 1))
                    payload.append(jnp.where(sel, hr, lr))
            g = _ALL_GATHER(tuple(payload), axis)   # ONE collective
            win = jnp.argmin(g[0], axis=0)                     # (B,)
            cols = jnp.arange(g[0].shape[1])
            out = [g[0][win, cols], g[1][win, cols]]
            if with_rows:
                out.append(tuple(r[win, cols] for r in g[2:]))
            return tuple(out)

        row = P(self.axis)
        n_in = 10  # table..q specs below
        in_specs = (P(self.axis, None), row, row, P(), P(), P(), P(),
                    P(), P(), P(), P(), P(self.axis))
        assert len(in_specs) == n_in + 2
        out_specs = ((P(), P(), P()) if with_rows else (P(), P()))
        smap = jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
        return smap(table, norms, slot_at, cents, cnorms, owner, hot_t,
                    hnorms, hot_s, jnp.asarray(q, jnp.float32),
                    hot_parts, tuple(parts or ()))

    def search_device(self, q, k: int = 1, *, table=None, args=None,
                      fused: bool = False):
        """DeviceIndex-compat search: (sq_dists (B, k), slot ids (B, k)).
        Top-1 only (the sharded combine carries one winner per shard);
        ``fused`` is accepted for API parity — the search is already the
        one-matmul-per-shard form."""
        if k != 1:
            raise NotImplementedError("sharded index serves top-1 only")
        if args is None:
            args = self.search_args
        d2, slot = self._combine(args, q, None, with_rows=False)
        return d2[:, None], slot.astype(jnp.int32)[:, None]

    def search_fetch(self, q, *, args, parts):
        """Search + fetch in the SAME collective: returns (sq_dists
        (B, 1), slot ids (B, 1), codec-part rows tuple (B, ...)). The
        winning shard's arena rows ride the all_gather payload, so the
        engine never gathers from the sharded arenas by index — which
        would be a second cross-shard collective."""
        d2, slot, rows = self._combine(args, q, parts, with_rows=True)
        return d2[:, None], slot.astype(jnp.int32)[:, None], rows

    def search(self, q, k: int = 1):
        """Host-compat API (L2, not squared — same as ExactIndex)."""
        d2, idx = self.search_device(jnp.asarray(q, jnp.float32), k)
        return (np.sqrt(np.maximum(np.asarray(d2), 0.0)),
                np.asarray(idx))


class ShardedMemoStore(MemoStore):
    """MemoStore whose device tier is partitioned over a mesh axis.

    The host tier (arena, host index, capacity tier, budgets) is exactly
    the base store — global admission still enforces the ONE byte budget.
    What changes is device placement: every live slot is assigned a
    device POSITION on the shard owning its nearest centroid; a full
    shard runs a shard-local CLOCK sweep (per-shard eviction) before
    spilling to the emptiest shard. Delta sync ships only the touched
    shards' positions and bumps only their ``shard_snapshots``
    generations; full sync re-runs k-means and rebalances ownership."""

    def __init__(self, apm_shape, embed_dim, *, n_shards: int = 0,
                 shard_axis: str = "store", hot_k: int = 32,
                 route_nprobe: Optional[int] = None,
                 refresh_spills: int = 0, mesh=None, **kw):
        if kw.get("index_kind") == "device":
            raise MemoStoreError(
                "ShardedMemoStore needs a host-tier index separate from "
                "the device table (index_kind='device' is single-host "
                "only); use index_kind='exact' or 'ivf'")
        if mesh is None:
            mesh = make_store_mesh(n_shards or None, shard_axis)
        kw.pop("device_index_kind", None)   # the sharded layout is fixed
        kw.pop("mesh", None)
        super().__init__(apm_shape, embed_dim,
                         device_index_kind="sharded", mesh=None, **kw)
        self.shard_mesh = mesh
        self.shard_axis = shard_axis
        self.n_shards = int(mesh.shape[shard_axis])
        self.hot_k = max(0, int(hot_k))
        self.route_nprobe = (max(1, int(route_nprobe))
                             if route_nprobe is not None
                             else max(1, int(self.nprobe)))
        # position bookkeeping (all rebuilt by each full sync)
        self._pos_per_shard = 0
        self._slot_pos: Dict[int, int] = {}
        self._pos_slot = np.full((0,), -1, np.int64)
        self._shard_free: List[List[int]] = [[] for _ in
                                             range(self.n_shards)]
        self._shard_hands = [0] * self.n_shards
        self._centroids_host = np.full((1, embed_dim), TOMBSTONE,
                                       np.float32)
        self._owner_host = np.zeros((1,), np.int32)
        self._shard_gens = np.zeros(self.n_shards, np.int64)
        self.shard_snapshots: Tuple[ShardSnapshot, ...] = ()
        self.n_shard_evictions = 0
        self.n_spills = 0
        # routing-drift repair (ROADMAP item 1): after this many delta-
        # sync spills since the last centroid fit, recompute centroids
        # from the current embedding table (0 disables)
        self.refresh_spills = max(0, int(refresh_spills))
        self._spills_since_refresh = 0
        self.n_centroid_refreshes = 0

    # -------------------------------------------------------- accounting
    def shard_occupancy(self) -> np.ndarray:
        """(S,) live positions per shard — the balance probe."""
        occ = np.zeros(self.n_shards, np.int64)
        if self._pos_per_shard:
            held = np.flatnonzero(self._pos_slot >= 0)
            np.add.at(occ, held // self._pos_per_shard, 1)
        return occ

    def shard_stats(self) -> Dict[str, object]:
        occ = self.shard_occupancy()
        mean = float(occ.mean()) if occ.size else 0.0
        return {
            "n_shards": self.n_shards,
            "positions_per_shard": self._pos_per_shard,
            "occupancy": [int(c) for c in occ],
            "imbalance": (float(occ.max()) / mean if mean > 0 else 1.0),
            "hot_k": self.hot_k,
            "n_shard_evictions": self.n_shard_evictions,
            "n_spills": self.n_spills,
            "n_centroid_refreshes": self.n_centroid_refreshes,
        }

    @property
    def per_shard_budget_bytes(self) -> Optional[int]:
        """The byte budget one shard's positions can hold — what 'a
        database too big for one shard' is measured against."""
        if self._pos_per_shard == 0:
            return None
        return self._pos_per_shard * self.entry_nbytes

    # ---------------------------------------------------------- routing
    def _route_shards(self, embs: np.ndarray) -> np.ndarray:
        """Host-side nearest-centroid → owning shard per row."""
        c = self._centroids_host
        d2 = ((c * c).sum(1)[None, :] - 2.0 * embs @ c.T)
        return self._owner_host[np.argmin(d2, axis=1)]

    def _free_position_locked(self, slot: int,
                              killed: List[int]) -> None:
        pos = self._slot_pos.pop(int(slot), None)
        if pos is not None:
            self._pos_slot[pos] = -1
            self._shard_free[pos // self._pos_per_shard].append(pos)
            killed.append(pos)

    def _evict_shard_locked(self, shard: int, n: int) -> List[int]:
        """Shard-local CLOCK: sweep only this shard's positions with the
        same decaying-second-chance rule as the global clock; falls back
        to coldest-resident when everything is hot. Victims retire
        through the shared path (demotion, tombstones, dirty marking)."""
        M = self._pos_per_shard
        lo = shard * M
        counts = self.db.reuse_counts
        hand = self._shard_hands[shard]
        victims: List[int] = []
        scanned = 0
        while len(victims) < n and scanned < 2 * M:
            pos = lo + (hand % M)
            hand += 1
            scanned += 1
            slot = int(self._pos_slot[pos])
            if slot < 0 or not self.db._live[slot]:
                continue
            if counts[slot] > 0:
                counts[slot] //= 2
            else:
                victims.append(slot)
        self._shard_hands[shard] = hand % M
        if len(victims) < n:      # all hot: coldest resident on the shard
            res = [int(s) for s in self._pos_slot[lo: lo + M]
                   if s >= 0 and self.db._live[s] and s not in victims]
            res.sort(key=lambda s: int(counts[s]))
            victims.extend(res[: n - len(victims)])
        if victims:
            self._retire_slots_locked(victims)
            self.stats.n_evicted += len(victims)
            self.n_shard_evictions += len(victims)
        return victims

    # ------------------------------------------------------------- sync
    def _need_full_sync_locked(self, n: int, force_full: bool) -> bool:
        if (force_full or self.device_db is None
                or self.device_index is None or self._dev_lens is None
                or n > int(self._dev_lens.shape[0])):
            return True
        pending = sum(1 for s in self._dirty
                      if s < n and self.db._live[s]
                      and s not in self._slot_pos)
        total_free = sum(len(f) for f in self._shard_free)
        return pending > total_free

    def _full_sync_device_locked(self, n: int) -> int:
        S = self.n_shards
        live = (np.flatnonzero(self.db.live_mask[:n]) if n
                else np.zeros(0, np.int64))
        nl = int(live.size)
        # per-shard position capacity: the whole live set + device slack,
        # rounded up so every shard can absorb deltas before a re-pack
        budgeted = nl + max(8, int(nl * self.device_slack))
        M = max(4, -(-budgeted // S))
        total = S * M
        # centroids: at least one per shard (ownership must cover the
        # mesh) — k-means clamps k <= live rows itself
        C = int(self.n_clusters or round(math.sqrt(max(1, nl))))
        C = max(S, min(max(1, C), max(1, nl)))
        if nl:
            cents, assign = _kmeans(self._embs_host[live], C, iters=5,
                                    seed=0)
        else:
            cents = np.full((1, self.embed_dim), TOMBSTONE, np.float32)
            assign = np.zeros(0, np.int64)
        # balanced ownership: biggest clusters first, each to the
        # least-loaded shard — per-shard occupancy stays within the
        # largest single cluster of even
        sizes = np.bincount(assign, minlength=cents.shape[0])
        owner = np.zeros(cents.shape[0], np.int32)
        load = np.zeros(S, np.int64)
        for c in np.argsort(-sizes, kind="stable"):
            s = int(np.argmin(load))
            owner[int(c)] = s
            load[s] += int(sizes[int(c)])
        self._centroids_host = np.asarray(cents, np.float32)
        self._owner_host = owner
        # assign every live slot a position on its owning shard;
        # overfull shards spill to the globally emptiest
        self._pos_per_shard = M
        self._pos_slot = np.full((total,), -1, np.int64)
        self._slot_pos = {}
        nxt = [s * M for s in range(S)]
        pref = (owner[assign] if nl else np.zeros(0, np.int32))
        for slot, p in zip(live, pref):
            p = int(p)
            if nxt[p] >= (p + 1) * M:
                p = int(np.argmin([nxt[s] - s * M for s in range(S)]))
                self.n_spills += 1
            pos = nxt[p]
            nxt[p] += 1
            self._slot_pos[int(slot)] = pos
            self._pos_slot[pos] = int(slot)
        self._shard_free = [
            list(range((s + 1) * M - 1, nxt[s] - 1, -1))
            for s in range(S)]
        self._shard_hands = [0] * S
        # host staging at positions → sharded device arrays
        table = np.full((total, self.embed_dim), TOMBSTONE, np.float32)
        held = np.flatnonzero(self._pos_slot >= 0)
        slots_held = self._pos_slot[held]
        table[held] = self._embs_host[slots_held]
        host_parts = [np.zeros((total,) + p.shape, p.dtype)
                      for p in self.codec.parts]
        if held.size:
            rows = self.db.parts_at(slots_held)
            for dst, src in zip(host_parts, rows):
                dst[held] = src
        self.device_db = ShardedDeviceDB(host_parts, self.shard_mesh,
                                         self.shard_axis,
                                         codec=self.codec)
        di = ShardedDeviceIndex(
            self.embed_dim, mesh=self.shard_mesh, axis=self.shard_axis,
            nprobe=self.route_nprobe, hot_k=self.hot_k,
            interpret=self._interpret)
        di._registry_kind = "sharded"
        di.load(table, self._pos_slot)
        di.set_centroids(self._centroids_host, self._owner_host)
        self.device_index = di
        # slot-indexed device lengths (replicated — tiny, and the length
        # gate indexes it by the GLOBAL slot id the combine returns)
        cap_slots = n + max(8, int(n * self.device_slack))
        lens = np.full((cap_slots,), -1, np.int32)
        lens[:n] = self._lens_host[:n]
        self._dev_lens = jnp.asarray(lens)
        shipped = (self.device_db.transfer_bytes
                   + di.transfer_bytes + int(lens.nbytes))
        shipped += self._refresh_hot_locked()
        self._shard_gens += 1
        self._spills_since_refresh = 0    # fresh fit: drift clock restarts
        return shipped

    def _delta_sync_device_locked(self, n: int,
                                  slots: np.ndarray) -> int:
        M = self._pos_per_shard
        killed: List[int] = []
        touched = set(int(s) for s in slots)
        # every dirty slot's old position frees first: dead slots stay
        # free, live ones re-route by their CURRENT embedding (an evicted
        # slot recycled by admission may belong to a different shard now)
        for s in slots:
            self._free_position_locked(int(s), killed)
        live = [int(s) for s in slots if self.db._live[s]]
        write_pos: List[int] = []
        write_slots: List[int] = []
        if live:
            pref = self._route_shards(self._embs_host[np.asarray(live)])
            for slot, p in zip(live, pref):
                if not self.db._live[slot]:
                    continue    # evicted below by an earlier shard sweep
                p = int(p)
                if not self._shard_free[p]:
                    # placement pressure: the routed shard is full while
                    # the sync proceeds — whether resolved by eviction or
                    # by spilling, it is the drift signal the centroid
                    # refresh triggers on
                    self._spills_since_refresh += 1
                    for v in self._evict_shard_locked(p, 1):
                        touched.add(int(v))
                        self._free_position_locked(int(v), killed)
                    if not self._shard_free[p]:
                        p = int(max(range(self.n_shards),
                                    key=lambda s: len(
                                        self._shard_free[s])))
                        self.n_spills += 1
                        if not self._shard_free[p]:
                            raise MemoStoreError(
                                "sharded device tier out of positions "
                                "(needs a full resync)")
                pos = self._shard_free[p].pop()
                self._slot_pos[slot] = pos
                self._pos_slot[pos] = slot
                write_pos.append(pos)
                write_slots.append(slot)
        shipped = 0
        if write_pos:
            posa = np.asarray(write_pos, np.int64)
            sla = np.asarray(write_slots, np.int64)
            shipped += self.device_db.update(posa, self.db.parts_at(sla))
            shipped += self.device_index.update(
                posa, self._embs_host[sla], sla)
        kill = sorted(set(killed) - set(write_pos))
        if kill:
            shipped += self.device_index.kill(np.asarray(kill, np.int64))
        # slot-indexed device lengths for every slot this sync touched
        # (dirty + shard-eviction victims)
        ta = np.asarray(sorted(touched), np.int64)
        ta = ta[ta < int(self._dev_lens.shape[0])]
        if ta.size:
            sl, vals = pad_delta_pow2(ta, self._lens_host[ta])
            self._dev_lens = self._dev_lens.at[jnp.asarray(sl)].set(
                jnp.asarray(vals))
            shipped += int(vals.nbytes + sl.size * 4)
        for sh in {pos // M for pos in write_pos + killed}:
            self._shard_gens[sh] += 1
        if self.refresh_spills \
                and self._spills_since_refresh >= self.refresh_spills:
            shipped += self._refresh_centroids_locked()
        shipped += self._refresh_hot_locked()
        return shipped

    def _refresh_centroids_locked(self) -> int:
        """Lightweight routing-drift repair between full syncs (ROADMAP
        item 1): when enough delta-sync admissions spilled off their
        preferred shard, the centroid fit no longer describes the
        embedding distribution. Re-run k-means over the RESIDENT rows'
        current embeddings and re-derive each centroid's owner by
        majority vote of its assigned rows' resident shard — no row
        moves, no arena traffic; only the tiny replicated routing state
        ships. Future admissions then route to where the data actually
        lives, so the spill rate decays instead of compounding. Runs
        under the store lock on the maintenance cadence (off-thread
        under the MemoServer)."""
        self._spills_since_refresh = 0
        M = self._pos_per_shard
        if M == 0 or not self._slot_pos or self.device_index is None:
            return 0
        n = len(self.db)
        if n == 0:
            return 0
        resident = np.asarray(sorted(self._slot_pos), np.int64)
        resident = resident[resident < n]
        resident = resident[self.db.live_mask[resident]]
        if resident.size == 0:
            return 0
        # keep the centroid count (and therefore the search_args shapes)
        # fixed: k-means may clamp k below C on tiny stores — pad back
        # with TOMBSTONE rows, which are never the nearest probe
        C = int(self._centroids_host.shape[0])
        cents, assign = _kmeans(self._embs_host[resident], C, iters=5,
                                seed=1 + self.n_centroid_refreshes)
        row_shard = np.asarray(
            [self._slot_pos[int(s)] // M for s in resident], np.int64)
        c_eff = int(cents.shape[0])
        owner = np.zeros(C, np.int32)
        for c in range(c_eff):
            m = assign == c
            if np.any(m):
                owner[c] = np.int32(np.bincount(
                    row_shard[m], minlength=self.n_shards).argmax())
            elif c < self._owner_host.shape[0]:
                owner[c] = self._owner_host[c]
        if c_eff < C:
            pad = np.full((C - c_eff, self.embed_dim), TOMBSTONE,
                          np.float32)
            cents = np.concatenate([np.asarray(cents, np.float32), pad])
        self._centroids_host = np.asarray(cents, np.float32)
        self._owner_host = owner
        self.device_index.set_centroids(self._centroids_host,
                                        self._owner_host)
        self.n_centroid_refreshes += 1
        return int(self._centroids_host.nbytes + owner.nbytes)

    def _refresh_hot_locked(self) -> int:
        """Rebuild the replicated hot set: the top ``hot_k`` live slots
        by reuse count, shipped as fixed-H padded arrays (embedding,
        slot id, codec rows). Runs on every sync — which the MemoServer
        moves to the maintenance worker — so the skew absorber tracks
        the live reuse signal."""
        if self.device_index is None:
            return 0
        H = max(1, self.hot_k)
        n = len(self.db)
        live = np.flatnonzero(self.db.live_mask[:n]) if n else \
            np.zeros(0, np.int64)
        take = np.zeros(0, np.int64)
        if self.hot_k and live.size:
            order = np.argsort(-self.db.reuse_counts[live],
                               kind="stable")
            take = live[order[: self.hot_k]]
        table = np.full((H, self.embed_dim), TOMBSTONE, np.float32)
        slots = np.full((H,), -1, np.int32)
        parts = [np.zeros((H,) + p.shape, p.dtype)
                 for p in self.codec.parts]
        if take.size:
            table[: take.size] = self._embs_host[take]
            slots[: take.size] = take
            for dst, src in zip(parts, self.db.parts_at(take)):
                dst[: take.size] = src
        return self.device_index.set_hot(table, slots, tuple(parts))

    # ----------------------------------------------------------- publish
    def _publish_locked(self):
        snap = super()._publish_locked()
        occ = self.shard_occupancy()
        self.shard_snapshots = tuple(
            ShardSnapshot(shard=s, generation=int(self._shard_gens[s]),
                          live=int(occ[s]),
                          free=len(self._shard_free[s]))
            for s in range(self.n_shards))
        return snap


DEVICE_INDEXES.register(
    "sharded", lambda dim, *, capacity=0, nprobe=16, n_clusters=None,
    interpret=None, mesh=None, axis="store", hot_k=32, **_:
    ShardedDeviceIndex(dim, mesh=(mesh if mesh is not None
                                  else make_store_mesh(None, axis)),
                       axis=axis, capacity=capacity, nprobe=nprobe,
                       hot_k=hot_k, interpret=interpret))
