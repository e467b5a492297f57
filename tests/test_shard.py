"""Sharded memo store (ISSUE 9 / DESIGN.md §2.12).

Covers: the ONE-collective-per-batch invariant in meshed mode (trace
counted by patching ``shard._ALL_GATHER``), top-1 + payload parity with
the admitted entries, per-shard generation publish, the replicated hot
set absorbing centroid-routing masks, shard-local eviction/spill
bookkeeping, the host-index guard, engine-level logits parity vs the
select reference, and the real 8-way mesh in a subprocess (device count
locks at first jax init, so the in-process tests run the same code on
the clamped 1-shard mesh and the subprocess runs S=8).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.shard as shard
from repro.core.faults import MemoStoreError
from repro.core.shard import ShardedMemoStore, ShardSnapshot

APM = (2, 4, 4)
DIM = 8
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _entries(rng, n):
    """n unique, well-separated entries (same recipe as test_store)."""
    apms = rng.random((n, *APM)).astype(np.float16)
    embs = rng.normal(0, 0.01, (n, DIM)).astype(np.float32)
    embs[:, 0] += 10.0 * np.arange(1, n + 1)
    return apms, embs


def _mk(n_shards=1, **kw):
    kw.setdefault("index_kind", "exact")
    kw.setdefault("codec", "f16")
    kw.setdefault("capacity", 8)
    return ShardedMemoStore(APM, DIM, n_shards=n_shards, **kw)


# ------------------------------------------------------------- guards

def test_rejects_host_device_index_kind():
    """The sharded store owns the device layout; a single-host 'device'
    host index would duplicate the table unsharded."""
    with pytest.raises(MemoStoreError, match="single-host"):
        _mk(index_kind="device")


# ------------------------------------------------- search + collectives

def test_top1_parity_and_fetched_payload():
    """Every admitted entry finds ITSELF (global slot id through the
    combine) and ``search_fetch`` returns the winner's own codec rows —
    the engine never re-gathers from the sharded arenas."""
    rng = np.random.default_rng(0)
    s = _mk()
    apms, embs = _entries(rng, 12)
    slots = s.admit(apms, embs)
    s.sync(force_full=True)
    di = s.device_index
    # the f16 arena and the hot set in their HBM form, as in DeviceDB
    assert s.device_db.parts[0].dtype == jnp.int16
    assert di._hot_parts[0].dtype == jnp.int16
    d2, got, rows = di.search_fetch(jnp.asarray(embs), args=di.search_args,
                                    parts=s.device_db.parts)
    np.testing.assert_array_equal(np.asarray(got)[:, 0], slots)
    assert np.all(np.asarray(d2)[:, 0] < 0.1)
    dec = np.asarray(s.codec.decode_rows(rows), np.float32)
    np.testing.assert_allclose(dec, np.asarray(apms, np.float32),
                               atol=1e-3, rtol=0)
    # host-compat API agrees (L2, not squared)
    _, idx = di.search(embs)
    np.testing.assert_array_equal(idx[:, 0], slots)


def test_search_fetch_traces_exactly_one_collective(monkeypatch):
    """The sharded search+fetch — distances, slot ids AND codec rows —
    must combine through ONE all_gather (acceptance criterion, ISSUE 9):
    the one-barrier-per-batch invariant from the single-host fast path
    holds in meshed mode. Counted at trace time via the module-level
    ``_ALL_GATHER`` indirection every combine routes through."""
    rng = np.random.default_rng(1)
    s = _mk()
    apms, embs = _entries(rng, 8)
    s.admit(apms, embs)
    s.sync(force_full=True)
    calls = []
    real = shard._ALL_GATHER

    def counting(*a, **k):
        calls.append(a)
        return real(*a, **k)

    monkeypatch.setattr(shard, "_ALL_GATHER", counting)
    di = s.device_index
    di.search_fetch(jnp.asarray(embs), args=di.search_args,
                    parts=s.device_db.parts)
    assert len(calls) == 1
    # the rows ride the same gather: its payload is a pytree, not a
    # second collective per codec part
    calls.clear()
    di.search_device(jnp.asarray(embs))
    assert len(calls) == 1


# -------------------------------------------------- publish + snapshots

def test_publish_carries_per_shard_snapshots():
    rng = np.random.default_rng(2)
    s = _mk()
    apms, embs = _entries(rng, 6)
    s.admit(apms, embs)
    s.sync(force_full=True)
    s.publish()
    snaps = s.shard_snapshots
    assert len(snaps) == s.n_shards
    assert all(isinstance(x, ShardSnapshot) for x in snaps)
    assert sum(x.live for x in snaps) == 6
    occ = s.shard_occupancy()
    assert occ.sum() == 6
    st = s.shard_stats()
    assert st["n_shards"] == s.n_shards
    assert sum(st["occupancy"]) == 6
    assert st["imbalance"] >= 1.0
    assert s.per_shard_budget_bytes == s._pos_per_shard * s.entry_nbytes


def test_delta_sync_bumps_touched_generations():
    rng = np.random.default_rng(3)
    s = _mk()
    apms, embs = _entries(rng, 6)
    s.admit(apms, embs)
    s.sync(force_full=True)
    s.publish()
    g0 = [x.generation for x in s.shard_snapshots]
    a2, e2 = _entries(rng, 2)
    e2[:, 0] += 200.0
    s.admit(a2, e2)
    s.sync()                       # delta: 2 dirty slots route + ship
    s.publish()
    g1 = [x.generation for x in s.shard_snapshots]
    assert any(b > a for a, b in zip(g0, g1))
    assert sum(x.live for x in s.shard_snapshots) == 8


# -------------------------------------------------------------- hot set

def test_hot_set_absorbs_routing_mask():
    """A query masked away from the shard owning its nearest entry is
    still served when that entry is in the replicated hot set: score the
    index with centroids that route EVERY query to a far-off region, so
    only the hot scores can win."""
    rng = np.random.default_rng(4)
    s = _mk(hot_k=2, route_nprobe=1)
    apms, embs = _entries(rng, 8)
    slots = s.admit(apms, embs)
    s.sync(force_full=True)
    di = s.device_index
    # route everything toward a centroid far from every entry; with
    # nprobe=1 a shard only competes for queries probing its centroid
    far = np.full((1, DIM), 1e6, np.float32)
    di.set_centroids(far, np.zeros((1,), np.int32))
    # make slot[3] hot: every shard scores the replicated hot rows
    hot = 3
    table = np.full((max(1, di.hot_k), DIM), shard.TOMBSTONE, np.float32)
    hslots = np.full((max(1, di.hot_k),), -1, np.int32)
    parts = [np.zeros((max(1, di.hot_k),) + p.shape, p.dtype)
             for p in s.codec.parts]
    table[0] = embs[hot]
    hslots[0] = slots[hot]
    rows = s.db.parts_at(np.asarray([slots[hot]]))
    for dst, src in zip(parts, rows):
        dst[0] = src[0]
    di.set_hot(table, hslots, tuple(parts))
    d2, idx = di.search_device(jnp.asarray(embs[hot][None]))
    assert int(np.asarray(idx)[0, 0]) == int(slots[hot])
    assert float(np.asarray(d2)[0, 0]) < 0.1


def test_sync_refreshes_hot_set_by_reuse():
    """The maintenance sync ships the top reuse-count rows as the hot
    set, in fixed-H arrays (no consumer retrace across refreshes)."""
    rng = np.random.default_rng(5)
    s = _mk(hot_k=2)
    apms, embs = _entries(rng, 6)
    slots = s.admit(apms, embs)
    s.sync(force_full=True)
    di = s.device_index
    shape0 = (di._hot_table.shape, di._hot_slots.shape)
    s.db.get(np.asarray([slots[4], slots[4], slots[4], slots[1]]))
    s.admit(*_entries(np.random.default_rng(6), 1))  # dirty -> delta sync
    s.sync()
    hs = set(int(x) for x in np.asarray(di._hot_slots))
    assert int(slots[4]) in hs
    assert (di._hot_table.shape, di._hot_slots.shape) == shape0


# ------------------------------------------------------- engine parity

@pytest.fixture(scope="module")
def sharded_engine():
    from repro.configs import get_reduced
    from repro.core.engine import MemoEngine
    from repro.data import TemplateCorpus
    from repro.memo import MemoSpec
    from repro.models import build_model

    cfg = get_reduced("bert_base").replace(n_classes=4, n_layers=2,
                                           d_model=128, d_ff=256,
                                           n_heads=4)
    m = build_model(cfg, layer_loop="unroll")
    params = m.init(jax.random.PRNGKey(0))
    corpus = TemplateCorpus(vocab=cfg.vocab, seq_len=32, n_templates=6,
                            slot_fraction=0.2)
    eng = MemoEngine(m, params, MemoSpec.flat(
        threshold=0.6, embed_steps=40, mode="bucket", shards=1,
        shard_hot=8))
    batches = [{"tokens": jnp.asarray(corpus.sample(16)[0])}
               for _ in range(3)]
    eng.build(jax.random.PRNGKey(1), batches)
    return eng, corpus


def test_engine_builds_sharded_store_from_spec(sharded_engine):
    eng, _ = sharded_engine
    assert isinstance(eng.store, ShardedMemoStore)
    assert eng.store.hot_k == 8
    assert getattr(eng.store.device_index, "is_sharded", False)


@pytest.mark.parametrize("thr", [-1e9, 0.6, 1e9])
def test_engine_sharded_matches_select(sharded_engine, thr):
    """Memoized serving through the sharded tier == the select reference
    across all-hit / mixed / all-miss thresholds (acceptance criterion,
    ISSUE 9: logits matching select parity)."""
    eng, corpus = sharded_engine
    toks = jnp.asarray(corpus.sample(8)[0])
    eng.mc.mode = "select"
    try:
        ref, _ = eng.infer({"tokens": toks}, threshold=thr)
    finally:
        eng.mc.mode = "bucket"
    out, st = eng.infer({"tokens": toks}, threshold=thr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    if thr == -1e9:
        assert st.memo_rate == 1.0
    if thr == 1e9:
        assert st.memo_rate == 0.0


# ---------------------------------- centroid refresh (ISSUE 10 satellite)

def test_centroid_refresh_trigger_and_fixed_shapes():
    """Routing-drift repair between full syncs (ROADMAP item 1): once
    the spill counter crosses ``refresh_spills``, the NEXT delta sync
    refits centroids from the resident embeddings in place — fixed
    centroid count (no search_args retrace), no row movement, counter
    reset — and routed search still resolves every entry. Pressure
    itself needs a full preferred shard while others have room, which
    the clamped 1-shard mesh cannot produce; the 8-way subprocess test
    drives that end-to-end, so here the drift clock is primed directly
    to pin down the trigger + refresh mechanics."""
    rng = np.random.default_rng(5)
    s = _mk(refresh_spills=2)
    apms, embs = _entries(rng, 10)
    slots = s.admit(apms, embs)
    s.sync(force_full=True)
    shape0 = s._centroids_host.shape
    assert s.n_centroid_refreshes == 0
    pos0 = dict(s._slot_pos)
    s._spills_since_refresh = 2          # primed past the threshold
    a2, e2 = _entries(rng, 2)
    e2[:, 0] += 120.0                    # clear of the first batch
    new = s.admit(a2, e2)
    s.sync()
    assert s.n_centroid_refreshes == 1
    assert s._spills_since_refresh == 0  # fresh fit restarts the clock
    assert s.shard_stats()["n_centroid_refreshes"] == 1
    # the refresh ships only the tiny replicated routing state: the
    # centroid table keeps its shape and no resident row moved
    assert s._centroids_host.shape == shape0
    assert all(s._slot_pos.get(k) == v for k, v in pos0.items()
               if k in s._slot_pos)
    q = np.concatenate([embs, e2])
    _, idx = s.device_index.search(q)
    np.testing.assert_array_equal(idx[:, 0], np.concatenate([slots, new]))
    # a full sync refits from scratch and restarts the drift clock
    s._spills_since_refresh = 1
    s.sync(force_full=True)
    assert s._spills_since_refresh == 0
    assert s.n_centroid_refreshes == 1   # full sync is not a "refresh"


def test_centroid_refresh_disabled_by_default():
    """``refresh_spills=0`` (the default) never refreshes between full
    syncs no matter how much placement pressure accumulates."""
    rng = np.random.default_rng(6)
    s = _mk()
    assert s.refresh_spills == 0
    apms, embs = _entries(rng, 6)
    s.admit(apms, embs)
    s.sync(force_full=True)
    s._spills_since_refresh = 10 ** 6
    a2, e2 = _entries(rng, 2)
    e2[:, 0] += 120.0
    s.admit(a2, e2)
    s.sync()
    assert s.n_centroid_refreshes == 0
    assert s.shard_stats()["n_centroid_refreshes"] == 0


# ---------------------------------------------------------- 8-way mesh

_MESH8_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
import repro.core.shard as shard
from repro.core.shard import ShardedMemoStore

APM, DIM, N = (2, 4, 4), 8, 96
rng = np.random.default_rng(0)
apms = rng.random((N, *APM)).astype(np.float16)
embs = rng.normal(0, 0.01, (N, DIM)).astype(np.float32)
embs[:, 0] += 10.0 * np.arange(1, N + 1)

s = ShardedMemoStore(APM, DIM, n_shards=8, capacity=16, hot_k=4,
                     route_nprobe=2, index_kind="exact", codec="f16",
                     refresh_spills=6)
assert s.n_shards == 8, s.n_shards
slots = s.admit(apms, embs)
s.sync(force_full=True)
C0 = s._centroids_host.shape[0]
st = s.shard_stats()
occ = np.asarray(st["occupancy"])
assert occ.sum() == N, occ
assert (occ > 0).all(), occ                      # every shard holds rows
assert st["imbalance"] <= 2.0, st
# parity under ACTIVE routing masks: nprobe=2 of >=8 centroids means
# most shards submit +inf for any query, yet every entry finds itself
di = s.device_index
d2, idx, rows = di.search_fetch(jnp.asarray(embs), args=di.search_args,
                                parts=s.device_db.parts)
assert (np.asarray(idx)[:, 0] == slots).all()
assert np.asarray(d2).max() < 0.1
dec = np.asarray(s.codec.decode_rows(rows), np.float32)
np.testing.assert_allclose(dec, np.asarray(apms, np.float32), atol=1e-3)
# ONE cross-shard collective on the REAL 8-way mesh
calls = []
real = shard._ALL_GATHER
shard._ALL_GATHER = lambda *a, **k: (calls.append(a) or real(*a, **k))
di.search_fetch(jnp.asarray(embs[:8]), args=di.search_args,
                parts=s.device_db.parts)
shard._ALL_GATHER = real
assert len(calls) == 1, len(calls)
# delta sync touches only the routed shards' generations
s.publish()
g0 = np.asarray([x.generation for x in s.shard_snapshots])
a2, e2 = apms[:3].copy(), embs[:3].copy()
e2[:, 0] += 0.05                                  # near existing entries
s.admit(a2, e2)
s.sync()
s.publish()
g1 = np.asarray([x.generation for x in s.shard_snapshots])
bumped = int((g1 > g0).sum())
assert 1 <= bumped < 8, (g0.tolist(), g1.tolist())
# skewed burst at one centroid region: the target shard runs out of
# free positions -> shard-local CLOCK eviction and/or spill
burst = 40
ab = rng.random((burst, *APM)).astype(np.float16)
eb = rng.normal(0, 0.01, (burst, DIM)).astype(np.float32)
eb[:, 0] += 10.0                                  # all near entry 1
s.admit(ab, eb)
s.sync()
assert s.n_shard_evictions + s.n_spills > 0, \
    (s.n_shard_evictions, s.n_spills)
occ2 = s.shard_occupancy()
live = int(s.db.live_mask[: len(s.db)].sum())
assert occ2.sum() == live, (occ2.tolist(), live)
# the same pressure is the drift signal: it crossed refresh_spills=6,
# so a delta-sync centroid refresh re-fit routing to the RESIDENT
# distribution (fixed C — no search_args retrace) without moving rows
assert s.n_centroid_refreshes >= 1, s._spills_since_refresh
assert s.shard_stats()["n_centroid_refreshes"] == s.n_centroid_refreshes
assert s._centroids_host.shape[0] == C0, (s._centroids_host.shape, C0)
d3, idx3 = s.device_index.search(eb[:8])   # post-refresh routing works
assert np.asarray(d3)[:, 0].max() < 1.0, np.asarray(d3)[:, 0]
print("SHARD8-OK", st["imbalance"], bumped, s.n_shard_evictions,
      s.n_spills, s.n_centroid_refreshes)
"""


def test_eight_way_mesh_subprocess():
    """The full sharded tier on a real 8-device mesh: balanced
    occupancy, routed-search parity, one collective, selective
    generation bumps, shard-local eviction under skew."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", _MESH8_CODE],
                         capture_output=True, text=True, env=env,
                         cwd=REPO, timeout=560)
    assert "SHARD8-OK" in out.stdout, out.stderr[-3000:]


def test_make_store_mesh_refuses_more_shards_than_devices():
    """Asking for more shards than devices raises instead of quietly
    shrinking the store to the devices that exist."""
    from repro.core.shard import make_store_mesh
    n = jax.device_count()
    assert make_store_mesh(n).shape["store"] == n
    with pytest.raises(ValueError, match="store shards requested"):
        make_store_mesh(n + 1)
