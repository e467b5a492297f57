"""AttMemo online inference engine (paper §5.1 Fig. 5).

Orchestrates, per memoizable layer:
    hidden state → MLP embedding → index search → threshold check →
    APM fetch from the attention database → memoized attention.

Execution modes (DESIGN.md §2, the TPU adaptation of "dynamic fallback"):

* ``select``  — both paths are computed and combined with ``jnp.where``
                (reference semantics; used for accuracy studies).
* ``bucket``  — the batch is split into hit/miss sub-batches
                (continuous-batching style): hits run the memo-only
                attention (no Q/K projection, no QKᵀ, no softmax), misses
                run normal attention. This is where the latency win is real.

The engine also builds the database: run the model with APM capture on a
calibration corpus, train the Siamese embedder, index the embeddings.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.database import AttentionDB, DeviceDB
from repro.core.embedding import Embedder, train_embedder
from repro.core.faults import FaultInjector
from repro.core.index import DeviceIndex
from repro.core.prefill import PrefillCodec, stack_kv, unstack_kv_rows
from repro.core.selective import LayerProfile, PerfModel, timeit_median
from repro.core.similarity import similarity_score
from repro.core.spans import span
from repro.core.store import MemoStore, StoreSnapshot
# MemoConfig/MemoSpec live in repro.memo.specs (the public API v1 config
# surface); re-exported here so ``from repro.core.engine import
# MemoConfig`` keeps working for one release
from repro.memo.specs import MemoConfig, MemoSpec  # noqa: F401
from repro.models import attention as attn_mod
from repro.models import backbone as bb

# paper Table 2 — per-model threshold levels
LEVELS = {"conservative": 0.98, "moderate": 0.97, "aggressive": 0.96}


class SimReservoir:
    """Bounded reservoir sample (Algorithm R) of predicted similarities.

    `MemoStats.sims` used to be an unbounded list — a serving loop that
    threads one MemoStats through the whole run leaked forever. The
    reservoir keeps a uniform sample, so percentile summaries (the
    `suggest_levels`-style reporting) stay accurate while memory is O(cap).

    Mutation and summary are lock-guarded: under the MemoServer runtime
    the serving thread and the maintenance worker both merge per-batch
    stats into one shared reservoir (DESIGN.md §2.7) — without the lock,
    interleaved Algorithm-R updates lose or duplicate samples and the
    ``seen`` counter drifts from reality.
    """

    def __init__(self, cap: int = 4096, seed: int = 0):
        self.cap = cap
        self.seen = 0                 # total values offered
        self._vals: List[float] = []
        self._rng = np.random.default_rng(seed)
        self._lock = threading.Lock()

    def _append_locked(self, v: float) -> None:
        self.seen += 1
        if len(self._vals) < self.cap:
            self._vals.append(float(v))
        else:
            j = int(self._rng.integers(0, self.seen))
            if j < self.cap:
                self._vals[j] = float(v)

    def append(self, v: float) -> None:
        with self._lock:
            self._append_locked(v)

    def extend(self, values) -> None:
        values = list(values)
        with self._lock:
            if len(self._vals) + len(values) <= self.cap:
                self.seen += len(values)
                self._vals.extend(float(v) for v in values)
                return
            for v in values:
                self._append_locked(v)

    def percentile(self, q) -> float:
        with self._lock:
            if not self._vals:
                return float("nan")
            return float(np.percentile(self._vals, q))

    def __len__(self):
        return len(self._vals)        # retained (bounded); .seen = total

    def __iter__(self):
        return iter(list(self._vals))


@dataclass
class MemoStats:
    n_inputs: int = 0
    n_layer_attempts: int = 0
    n_hits: int = 0
    sims: SimReservoir = field(default_factory=SimReservoir)
    t_embed: float = 0.0
    t_search: float = 0.0
    t_fetch: float = 0.0
    t_attn: float = 0.0
    t_total: float = 0.0            # whole-batch wall time (fast path)
    per_layer_hits: Dict[int, int] = field(default_factory=dict)
    n_admitted: int = 0             # entries admitted via miss capture
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)

    @property
    def memo_rate(self) -> float:
        return self.n_hits / max(1, self.n_layer_attempts)

    def merge(self, other: "MemoStats") -> "MemoStats":
        """Fold another stats object into this one under the lock — the
        MemoServer accumulates per-batch stats this way so the serving
        thread and the off-thread maintenance worker never race on the
        counters (they used to be bare ``+=`` on shared fields)."""
        with self._lock:
            self.n_inputs += other.n_inputs
            self.n_layer_attempts += other.n_layer_attempts
            self.n_hits += other.n_hits
            self.t_embed += other.t_embed
            self.t_search += other.t_search
            self.t_fetch += other.t_fetch
            self.t_attn += other.t_attn
            self.t_total += other.t_total
            self.n_admitted += other.n_admitted
            for li, nh in other.per_layer_hits.items():
                self.per_layer_hits[li] = self.per_layer_hits.get(li, 0) + nh
        self.sims.extend(other.sims)          # reservoir has its own lock
        return self

    def add_admitted(self, n: int) -> None:
        """Maintenance-side counter bump (worker thread under the async
        runtime), guarded like ``merge``."""
        with self._lock:
            self.n_admitted += int(n)


@dataclass
class PreparedBatch:
    """Everything ``run_layers``/``finalize`` need for one device-resident
    batch — produced by ``prepare_batch``, which is where the runtime's
    batching policy hands over to the engine (DESIGN.md §2.7)."""
    tokens: jnp.ndarray
    h: jnp.ndarray
    positions: jnp.ndarray
    kpad: Optional[jnp.ndarray]          # (B, S) bool key-validity mask
    lengths_dev: Optional[jnp.ndarray]   # (B,) int32 true lengths (device)
    lengths: Optional[np.ndarray]        # host copy (drain/admission)
    n_valid: int                         # real rows; the rest are padding
    thr: float
    active: set
    capture: bool
    view: StoreSnapshot                  # the store generation this batch
    #                                      serves against, end to end
    t0: float = 0.0
    pend: list = field(default_factory=list)
    # prefill serving (DESIGN.md §2.13): per-layer decode-cache templates
    # split from model.init_caches, and the caches each layer produced
    prefill: bool = False
    cache_len: int = 0
    cache_tpls: Optional[dict] = None
    caches_by_li: dict = field(default_factory=dict)


@dataclass
class MaintenancePayload:
    """Host-tier store work drained from one finished batch. Applying it
    (``MemoEngine.apply_maintenance``) is the ONLY thing that mutates the
    MemoStore — the runtime either does it inline (sync mode) or hands it
    to the background worker (async mode, overlapped with batch t+1's
    device compute)."""
    reuse_slots: Optional[np.ndarray] = None        # device-tier hits
    admissions: List[Tuple] = field(default_factory=list)
    #   (apms, embs, lens, kv) — kv is the stacked (B, 2, S, D) K/V plane
    #   under prefill capture, None for APM-only admissions
    generation: int = -1        # the store generation the batch served
    #                             against (failure-report context)

    @property
    def empty(self) -> bool:
        return not self.admissions and (
            self.reuse_slots is None or self.reuse_slots.size == 0)


class MemoEngine:
    def __init__(self, model, params,
                 memo_cfg: Optional[MemoSpec] = None):
        self.model = model
        self.params = params
        self.cfg = model.cfg
        # None → a fresh default spec PER ENGINE (a shared default
        # instance would leak one engine's mc mutations — threshold
        # autotune, mode flips — into every other default-configured one)
        self.mc = MemoSpec() if memo_cfg is None else memo_cfg
        self.is_encdec = getattr(model, "is_encdec", False)
        if self.is_encdec:
            # enc-dec (whisper): memoize ENCODER self-attention — fixed
            # frame count, bidirectional APMs, reused across requests
            self.layers = list(range(self.cfg.encoder.n_layers))
        else:
            self.layers = list(self.cfg.memoizable_layers())
        if self.mc.max_layers:
            self.layers = self.layers[: self.mc.max_layers]
        # ALL memoization state (both tiers) lives in the MemoStore; the
        # engine only orchestrates (DESIGN.md §2.5). Created by build().
        self.store: Optional[MemoStore] = None
        self.embedder: Optional[Embedder] = None
        self.perf: Optional[PerfModel] = None
        self._jit_cache: Dict = {}
        self._interpret = (self.mc.interpret if self.mc.interpret
                           is not None else jax.default_backend() == "cpu")
        self._layers_cache = None
        self._serve_batches = 0          # admission-sampling counter
        self._pending_admissions: List = []   # host-path capture staging
        self._recal_buf: List = []       # rolling (apms, embs) captures
        self._flush_count = 0
        # fault injection (DESIGN.md §2.9): None unless the spec opts in
        # (RuntimeSpec.faults), so production serving pays one `is None`
        self.faults = FaultInjector.from_spec(self.mc.runtime.faults)

    @property
    def _kernel_impl(self) -> str:
        """Resolved memo_attention implementation for kernel mode
        ("pallas" | "xla"). Explicit ``mc.kernel_impl`` wins; an explicit
        ``mc.interpret`` pins the Pallas path (that is how kernel tests
        keep exercising the kernel); otherwise the backend decides —
        the one-matmul XLA form on CPU (the Pallas interpreter is ~30x
        slower there), the compiled kernel on TPU/GPU. A property, not
        an ``__init__`` capture: callers mutate ``mc`` between builds."""
        ki = self.mc.kernel_impl
        if ki:
            return ki
        if self.mc.interpret is not None:
            return "pallas"
        return "xla" if jax.default_backend() == "cpu" else "pallas"

    # --- store delegation (compat: the pre-store attribute API) ---------
    @property
    def db(self) -> Optional[AttentionDB]:
        return self.store.db if self.store is not None else None

    @property
    def index(self):
        return self.store.index if self.store is not None else None

    @property
    def device_db(self) -> Optional[DeviceDB]:
        return self.store.device_db if self.store is not None else None

    @property
    def device_index(self) -> Optional[DeviceIndex]:
        return self.store.device_index if self.store is not None else None

    @property
    def sim_cal(self):
        return self.store.sim_cal if self.store is not None else (-1.0, 1.0)

    @sim_cal.setter
    def sim_cal(self, value):
        if self.store is None:
            raise AttributeError("sim_cal lives on the MemoStore; "
                                 "build() the engine first")
        self.store.sim_cal = tuple(value)

    def _iter_layers(self):
        """Params are fixed per engine: slice the stacked layer params
        once and reuse — ``bb.iter_layers`` re-slices with eager tree_map
        gathers on every call, which is pure host overhead per batch."""
        if self._layers_cache is None:
            self._layers_cache = list(bb.iter_layers(self.params, self.cfg))
        return self._layers_cache

    def _make_store(self, apm_shape, *, capacity: int,
                    n_lists: Optional[int] = None) -> MemoStore:
        """Construct the MemoStore exactly as the spec describes — the
        single construction path shared by ``build()`` and
        ``MemoSession.load``. A loaded store must be configured
        identically to the saved one for lookups to round-trip:
        ``n_lists`` (derived from the CALIBRATION size at build, which a
        grown store no longer knows) is therefore persisted and passed
        back explicitly on load."""
        mc = self.mc
        budget = (None if mc.budget_mb is None
                  else int(mc.budget_mb * 1e6))
        codec = mc.apm_codec
        if mc.prefill.enabled:
            # prefill memoization (DESIGN.md §2.13): wrap the APM codec so
            # every entry carries per-layer K/V parts — the SAME store,
            # arenas, sync, capacity tier and save format serve both
            from repro.core.codec import get_codec
            base = get_codec(codec, tuple(apm_shape), rank=mc.apm_rank)
            codec = PrefillCodec(
                base, kv_dim=self.cfg.n_kv_heads * self.cfg.head_dim,
                kv_codec=mc.prefill.kv_codec, kv_rank=mc.prefill.kv_rank)
        kw = dict(
            index_kind=mc.index_kind, budget_bytes=budget,
            capacity=capacity, interpret=self._interpret,
            device_slack=mc.device_slack,
            n_lists=(n_lists if n_lists is not None
                     else max(4, int(np.sqrt(max(1, capacity))))),
            codec=codec, apm_rank=mc.apm_rank,
            cluster_crossover=mc.cluster_crossover,
            nprobe=mc.nprobe, n_clusters=mc.n_clusters,
            eviction=mc.eviction.kind, faults=self.faults,
            capacity_dir=mc.capacity.dir,
            capacity_budget_mb=mc.capacity.budget_mb,
            capacity_fsync=mc.capacity.fsync,
            capacity_stall_s=mc.capacity.stall_s)
        if getattr(mc, "shards", 0):
            from repro.core.shard import ShardedMemoStore
            return ShardedMemoStore(
                tuple(apm_shape), mc.embed_dim,
                n_shards=mc.shards, shard_axis=mc.shard_axis,
                hot_k=mc.shard_hot, route_nprobe=mc.shard_route_nprobe,
                refresh_spills=mc.shard_refresh_spills,
                **kw)
        return MemoStore(tuple(apm_shape), mc.embed_dim,
                         device_index_kind=mc.device_index, **kw)

    # ------------------------------------------------------------------ build
    def build(self, key, batches: Sequence[dict], *, train_pairs=512,
              verbose=False):
        """Populate the attention + index databases from a calibration
        corpus and train the embedding model. With prefill memoization
        enabled, every calibration entry also stores the layer's post-RoPE
        K/V (recomputed from the captured attention input — the capture
        dict's ``hidden`` IS the normed x that ``_qkv`` consumes), so the
        first epoch is immediately servable for prefill."""
        prefill = self.mc.prefill.enabled
        if prefill:
            self._check_prefill_supported()
        lps = ({li: lp for li, _, lp in self._iter_layers()}
               if prefill else None)
        hiddens, apms, kvs = [], [], []
        for batch in batches:
            _, caps = self.model.classify(self.params, batch, capture=True) \
                if self.cfg.n_classes else self.model.forward(
                    self.params, batch, capture=True)[:2]
            for li in self.layers:
                if li in caps:
                    hid = np.asarray(caps[li]["hidden"])
                    hiddens.append(hid)
                    apms.append(np.asarray(caps[li]["apm"], np.float16))
                    if prefill:
                        kvs.append(np.asarray(self._kv_probe(
                            lps[li], jnp.asarray(hid))))
        hiddens = np.concatenate(hiddens, 0)      # (N, L, H)
        apms = np.concatenate(apms, 0)            # (N, heads, L, L)
        kv = np.concatenate(kvs, 0) if prefill else None
        n, L, H = hiddens.shape

        self.store = self._make_store(apms.shape[1:], capacity=n)

        k1, k2 = jax.random.split(key)
        emb = Embedder.init(k1, L, H, dim=self.mc.embed_dim,
                            pool=self.mc.embed_pool, act=self.mc.embed_act)
        sub = min(n, max(64, train_pairs))
        self.embedder, hist = train_embedder(
            k2, emb, jnp.asarray(hiddens[:sub]), jnp.asarray(apms[:sub]),
            steps=self.mc.embed_steps)
        if verbose:
            print(f"embedder loss {hist[0]:.4f} -> {hist[-1]:.4f}")

        embs = np.asarray(self._embed(jnp.asarray(hiddens)))
        self.store.admit(apms, embs, kv=kv)   # calibration = first epoch
        self._calibrate(hiddens, apms)
        # materialize the serving tier only when the fast path can reach
        # it (select-mode engines would duplicate the arena for nothing);
        # mode switches after build are covered by the lazy sync in
        # _infer_device/_layer_kernel
        if self.mc.store == "device" and self.mc.mode in ("bucket",
                                                          "kernel"):
            self.store.sync()
        return self

    # -------------------------------------------------------- device tier
    def _sync_device_tier(self):
        """Bring the serving tier (DeviceDB + DeviceIndex) up to date.
        Generation-counted in the store: a clean store is a host-side
        no-op, host-tier changes move as slot deltas into preallocated
        device slack, and only arena growth past the device allocation
        re-materializes (never on the serving hot path)."""
        return self.store.sync()

    def _use_fast_path(self) -> bool:
        if self.is_encdec or self.store is None or self.db is None:
            return False
        if self.mc.mode not in ("bucket", "kernel"):
            return False                 # select stays the host reference
        if self.mc.device_fast_path is not None:
            return self.mc.device_fast_path
        return self.mc.store == "device"

    def _embed(self, hiddens, lengths=None):
        key = ("embed", lengths is not None)
        fn = self._jit_cache.get(key)
        if fn is None:
            pool, act = self.embedder.pool, self.embedder.act
            from repro.core.embedding import embed_apply
            if lengths is None:
                fn = jax.jit(lambda p, h: embed_apply(p, h, pool, act))
            else:
                fl = self.store.apm_shape[-1]   # chunk-scale anchor
                fn = jax.jit(lambda p, h, ln: embed_apply(
                    p, h, pool, act, lengths=ln, full_len=fl))
            self._jit_cache[key] = fn
        if lengths is None:
            return fn(self.embedder.params, hiddens)
        return fn(self.embedder.params, hiddens,
                  jnp.asarray(lengths, jnp.int32))

    def _calibrate(self, hiddens, apms, n_pairs=256):
        """Fit sim ≈ a·dist + b so search distances predict similarity."""
        rng = np.random.default_rng(0)
        n = hiddens.shape[0]
        ia, ib = rng.integers(0, n, n_pairs), rng.integers(0, n, n_pairs)
        ea = np.asarray(self._embed(jnp.asarray(hiddens[ia])))
        eb = np.asarray(self._embed(jnp.asarray(hiddens[ib])))
        dist = np.linalg.norm(ea - eb, axis=-1)
        sim = np.asarray(jax.vmap(similarity_score)(
            jnp.asarray(apms[ia]), jnp.asarray(apms[ib])))
        if np.std(dist) < 1e-9:
            self.sim_cal = (0.0, float(np.mean(sim)))
        else:
            a, b = np.polyfit(dist, sim, 1)
            self.sim_cal = (float(a), float(b))

    def predict_sim(self, dist: np.ndarray) -> np.ndarray:
        a, b = self.sim_cal
        return a * dist + b

    def suggest_levels(self, batches) -> Dict[str, float]:
        """Per-model threshold levels (paper Table 2 tunes these per model;
        §5.4 suggests an autotuner). Percentiles of the top-1 predicted
        similarity on calibration queries: conservative admits only the
        best-matched quartile, aggressive admits three quartiles."""
        sims = []
        for batch in batches:
            h = bb.embed_tokens(self.params, batch["tokens"], self.cfg)
            for li, kind, lp in self._iter_layers():
                if li in self.layers and kind in ("attn", "mla"):
                    x = bb.norm_apply(lp["norm1"], h, self.cfg.norm)
                    emb = self._embed(x)
                    dist, _ = self.index.search(np.asarray(emb), 1)
                    sims.extend(self.predict_sim(dist[:, 0]).tolist())
                h = self._layer_plain(lp, h, kind, li, None,
                                      jnp.broadcast_to(
                                          jnp.arange(h.shape[1],
                                                     dtype=jnp.int32),
                                          h.shape[:2]))
        sims = np.asarray(sims)
        return {"conservative": float(np.percentile(sims, 75)),
                "moderate": float(np.percentile(sims, 50)),
                "aggressive": float(np.percentile(sims, 25))}

    # ------------------------------------------------------------------ infer
    def infer(self, batch, *, threshold: Optional[float] = None,
              active_layers: Optional[Sequence[int]] = None,
              stats: Optional[MemoStats] = None, use_memo: bool = True):
        """Memoized forward. Returns (logits, stats).

        ``batch`` may carry ``lengths`` (B,) for padded variable-length
        inputs (tokens past a sequence's length are padding: masks flow
        through attention, memo lookup and the head) and ``n_valid`` (the
        runtime's batch padding — trailing rows are shape filler and are
        excluded from stats and admission). Variable length is served by
        the device fast path, the select reference and kernel mode (the
        memo_attention ``lengths`` operand); only the host-synchronous
        bucket path stays fixed-length."""
        thr = self.mc.threshold if threshold is None else threshold
        active = set(self.layers if active_layers is None else active_layers)
        st = stats or MemoStats()
        cfg = self.cfg
        if self.is_encdec:
            return self._infer_encdec(batch, thr, active, st, use_memo)
        if use_memo and self._use_fast_path():
            # step-wise executor with inline (synchronous batch-boundary)
            # maintenance — the MemoServer runtime calls the same three
            # steps but moves apply_maintenance onto its worker thread
            prep = self.prepare_batch(batch, threshold=thr,
                                      active_layers=active)
            self.run_layers(prep)
            out, st, payload = self.finalize(prep, stats=st)
            self.apply_maintenance(payload, stats=st)
            return out, st
        capture = self._capture_now(use_memo)
        if use_memo:
            self._serve_batches += 1
        tokens = batch["tokens"]
        lengths = batch.get("lengths")
        if lengths is not None and use_memo and self.mc.mode == "bucket":
            raise ValueError(
                "variable-length batches are served by the device fast "
                "path, the select reference, or kernel mode (the "
                "memo_attention lengths operand); the host-synchronous "
                "bucket path is fixed-length")
        B, S = tokens.shape[0], tokens.shape[1]
        n_valid = int(batch.get("n_valid", B))
        st.n_inputs += n_valid
        h = bb.embed_tokens(self.params, tokens, cfg)
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32), (B, S))
        kpad = None
        if lengths is not None:
            kpad = (jnp.arange(S, dtype=jnp.int32)[None, :]
                    < jnp.asarray(lengths, jnp.int32)[:, None])

        for li, kind, lp in self._iter_layers():
            memo = None
            if use_memo and li in active and kind in ("attn", "mla") \
                    and self.db is not None:
                memo = self._lookup(lp, h, kind, thr, st, li,
                                    positions=positions, capture=capture,
                                    lengths=lengths, kpad=kpad,
                                    n_valid=n_valid)
            t0 = time.perf_counter()
            if memo is not None and self.mc.mode == "bucket":
                h = self._layer_bucket(lp, h, kind, li, memo, positions)
            elif memo is not None and self.mc.mode == "kernel" \
                    and kind == "attn":
                h = self._layer_kernel(lp, h, li, memo, positions,
                                       lengths=lengths)
            else:
                h = self._layer_plain(lp, h, kind, li, memo, positions,
                                      kpad=kpad)
            jax.block_until_ready(h)
            st.t_attn += time.perf_counter() - t0
        self._flush_admissions(st)        # batch boundary: admit + sync
        if cfg.n_classes:
            return bb.classify_from_hidden(self.params, h, cfg,
                                           kpad=kpad), st
        return bb.logits_from_hidden(self.params, h, cfg), st

    # ------------------------------------- step-wise fast-path executor
    def prepare_batch(self, batch, *, threshold: Optional[float] = None,
                      active_layers: Optional[Sequence[int]] = None,
                      sync_store: bool = True,
                      prefill: bool = False) -> PreparedBatch:
        """Stage one device-resident batch (DESIGN.md §2.7): freeze the
        policy inputs (threshold, active layers, admission sampling), read
        the store snapshot the WHOLE batch will serve against, and run the
        prologue jit (token embed, positions, padding mask). The serving
        runtime owns batching and calls prepare/run/finalize itself;
        ``infer`` composes them with inline maintenance.

        ``sync_store=False`` is the async-maintenance contract: the
        serving thread never mutates the store — it reads the latest
        atomically-published snapshot and leaves sync to the worker.

        ``prefill=True`` stages a memoized causal prefill (DESIGN.md
        §2.13): the batch additionally carries per-layer decode-cache
        templates, memoized layers run ``_layer_fused_prefill`` (a hit
        materializes the decode cache from the stored KV entry), and
        ``finalize`` returns ``(last_logits, caches)``."""
        if not self._use_fast_path():
            raise RuntimeError(
                "prepare_batch drives the device fast path; build() the "
                "engine in bucket/kernel mode (select and host paths go "
                "through infer())")
        with span("prepare"):
            cfg = self.cfg
            tokens = jnp.asarray(batch["tokens"])
            lengths = batch.get("lengths")
            thr = self.mc.threshold if threshold is None else float(threshold)
            active = set(self.layers if active_layers is None
                         else active_layers)
            capture = self._capture_now(True, prefill=prefill)
            self._serve_batches += 1
            if sync_store:
                self.store.sync()     # generation-counted: no-op unless stale
            view = self.store.snapshot
            if view is None:          # bootstrap: materialize + publish once
                self.store.sync()
                view = self.store.snapshot
            B, S = tokens.shape[0], tokens.shape[1]
            n_valid = int(batch.get("n_valid", B))
            cache_len, cache_tpls = 0, None
            if prefill:
                if not self.mc.prefill.enabled:
                    raise RuntimeError(
                        "prefill serving needs PrefillSpec(enabled=True) "
                        "at build time — the store must carry KV-bearing "
                        "entries")
                if not isinstance(self.store.codec, PrefillCodec):
                    raise RuntimeError(
                        "this store's entries carry no KV parts; rebuild (or "
                        "re-save) it with prefill_enabled=True")
                self._check_prefill_supported()
                cache_len = self._prefill_cache_len(S)
                cache_tpls = self._split_caches(
                    self.model.init_caches(B, cache_len))
                for li in sorted(set(self.layers) & active):
                    cl = bb.cache_len_from(cache_tpls[li])
                    if cl < S:
                        raise ValueError(
                            f"layer {li} decode cache holds {cl} slots < "
                            f"prompt length {S} (sliding windows shorter "
                            f"than the prompt cannot replay a stored "
                            f"prefix)")
            t0 = time.perf_counter()
            key = ("prolog", lengths is not None)
            prolog = self._jit_cache.get(key)
            if prolog is None:
                def memo_prolog(params, tokens, ln):
                    h = bb.embed_tokens(params, tokens, cfg)
                    S = tokens.shape[1]
                    positions = jnp.broadcast_to(
                        jnp.arange(S, dtype=jnp.int32), tokens.shape[:2])
                    kpad = (None if ln is None else
                            jnp.arange(S, dtype=jnp.int32)[None, :]
                            < ln[:, None])
                    return h, positions, kpad
                prolog = self._jit_cache[key] = jax.jit(memo_prolog)
            len_dev = (None if lengths is None
                       else jnp.asarray(lengths, jnp.int32))
            if lengths is not None and not isinstance(lengths, np.ndarray):
                lengths = np.asarray(lengths)
            h, positions, kpad = prolog(self.params, tokens, len_dev)
            return PreparedBatch(
                tokens=tokens, h=h, positions=positions, kpad=kpad,
                lengths_dev=len_dev, lengths=lengths,
                n_valid=n_valid, thr=thr, active=active, capture=capture,
                view=view, t0=t0, prefill=prefill, cache_len=cache_len,
                cache_tpls=cache_tpls)

    def run_layers(self, prep: PreparedBatch) -> PreparedBatch:
        """The device-resident serving loop (DESIGN.md §2): every layer is
        a chained jitted dispatch — fused lookup (embed → nn_search →
        threshold → length gate → gather) feeding the layer body — with
        ZERO per-layer host synchronization (the one barrier lives in
        ``finalize``). Stats are event-based: hit masks, predicted sims
        and matched slots accumulate as device arrays in ``prep.pend``.
        With ``prep.capture`` (online admission), miss embeddings + APMs
        are STAGED ON DEVICE the same way — the loop never blocks."""
        thr_dev = jnp.float32(prep.thr)
        h = prep.h
        with span("run_layers"):
            for li, kind, lp in self._iter_layers():
                with span("layer", layer=li):
                    h = self._dispatch_layer(prep, li, kind, lp, h, thr_dev)
        prep.h = h
        return prep

    def _dispatch_layer(self, prep: PreparedBatch, li, kind, lp, h,
                        thr_dev):
        """Issue one layer of ``run_layers`` and return its hidden-state
        output, device arrays in and out. A memoized causal prefill's
        memoized layers hand back the layer's decode cache alongside h
        (hits from the stored KV entry, misses from the freshly computed
        K/V); its other layers run the backbone's exact prefill step."""
        if prep.prefill:
            if li in prep.active and kind == "attn":
                h, ck, cv, *rest = self._layer_fused_prefill(
                    lp, h, li, thr_dev, prep.positions,
                    view=prep.view, cache_tpl=prep.cache_tpls[li],
                    kpad=prep.kpad, qlen=prep.lengths_dev,
                    capture=prep.capture)
                prep.caches_by_li[li] = {"k": ck, "v": cv}
                prep.pend.append((li, *rest))
            else:
                h, c = self._layer_plain_prefill(
                    lp, h, kind, li, prep.positions,
                    prep.cache_tpls[li], kpad=prep.kpad)
                prep.caches_by_li[li] = c
            return h
        if li in prep.active and kind in ("attn", "mla"):
            h, *rest = self._layer_fused(
                lp, h, kind, li, thr_dev, prep.positions,
                view=prep.view, kpad=prep.kpad,
                qlen=prep.lengths_dev, capture=prep.capture)
            prep.pend.append((li, *rest))
            return h
        return self._layer_plain(lp, h, kind, li, None, prep.positions,
                                 kpad=prep.kpad)

    def finalize(self, prep: PreparedBatch,
                 stats: Optional[MemoStats] = None):
        """Head jit and the ``memo_drain`` stats program, then the ONE
        trailing barrier, then the event-based stats drain. Returns
        ``(outputs, stats, payload)`` — the payload carries every piece of
        host-tier store work from this batch; the caller decides WHERE it
        runs (inline vs the maintenance worker)."""
        st = stats or MemoStats()
        cfg = self.cfg
        if prep.prefill:
            # the prefill head byte-mirrors Model.prefill (last-position
            # logits), so exact-vs-memoized parity compares like for like
            headpf = self._jit_cache.get("headpf")
            if headpf is None:
                def memo_head_prefill(params, h):
                    return bb.logits_from_hidden(
                        params, h[:, -1:], cfg)[:, 0]
                headpf = self._jit_cache["headpf"] = jax.jit(
                    memo_head_prefill)
            logits = headpf(self.params, prep.h)
            drained = self._memo_drain(prep)
            with span("barrier"):
                logits = jax.block_until_ready(logits)      # ONE barrier
            out = (logits, self._merge_caches(prep.caches_by_li))
        else:
            key = ("head", prep.kpad is not None)
            head = self._jit_cache.get(key)
            if head is None:
                def memo_head(params, h, kpad):
                    return (bb.classify_from_hidden(params, h, cfg,
                                                    kpad=kpad)
                            if cfg.n_classes
                            else bb.logits_from_hidden(params, h, cfg))
                head = self._jit_cache[key] = jax.jit(memo_head)
            out = head(self.params, prep.h, prep.kpad)
            drained = self._memo_drain(prep)
            with span("barrier"):
                out = jax.block_until_ready(out)            # ONE barrier
        st.n_inputs += prep.n_valid
        st.t_total += time.perf_counter() - prep.t0
        with span("drain", layers=len(prep.pend)):
            payload = self._drain_stats(prep, st, drained)
        return out, st, payload

    def _layer_fused(self, lp, h, kind, li, thr_dev, positions, view,
                     kpad=None, qlen=None, capture: bool = False):
        """The fused serving layer: embed → nn_search → threshold → gather
        → attention → channel mixer, ONE jitted dispatch per layer, device
        arrays in and out (no np.asarray, no block_until_ready). Returns
        (h', sims, hits, slots) — plus (embs, apms_f16) under ``capture``,
        staged on device for the batch-boundary admission drain; the hit
        decision itself is consumed on-device.

        * ``bucket`` — rows are sorted hit-first ON DEVICE (stable argsort
          of the hit mask) and processed in fixed ``bucket_quantum``-sized
          quanta; each quantum picks its path with an XLA conditional on a
          device scalar. After the sort at most ONE quantum is mixed, so
          hit quanta genuinely skip Q/K projection + QKᵀ + softmax and
          miss quanta skip the memo combine — the same compute savings as
          host-side bucketing, but the batch composition never leaves the
          accelerator and shapes stay static (no recompiles across hit
          counts, unlike the host path's per-bucket-size cache entries).
        * ``kernel`` — ONE fused dispatch end to end: the search runs
          with ``fused=True`` (the one-matmul prologue, reusing the
          snapshot's cached DB norms) so the only Pallas kernel a
          memoized layer issues is memo_attention itself. The APM gather
          is elided entirely: the kernel gathers its own tiles from the
          device DB via the scalar-prefetched hit index, and the hit
          flag drives the BlockSpec index maps — hit programs alias the
          Q/K fetch to one resident tile and stream only APM tiles, miss
          programs alias the APM (and int8 scale-sliver) fetch and run
          pure flash attention, never touching the DB or the host arena.
          Under the int8 codec the kernel gathers codes + scale slivers
          and dequantizes in VMEM (the fused-dequant gather, DESIGN.md
          §2.6). On CPU the same math runs as the one-matmul XLA form
          (``_kernel_impl``); variable length rides the ``lengths``
          operand instead of erroring.

        Compression plumbing: the device DB rides in as its codec
        ``parts`` tuple and the index as its ``search_args`` pytree —
        read from the ``view`` (a StoreSnapshot), so one batch serves one
        atomically-published store generation end to end; an index
        rebuild or codec-shape change retraces automatically because the
        traced pytree changes.

        Variable length (``qlen``/``kpad`` both set): the embedding pools
        mask-aware over the true length, the hit decision additionally
        requires the matched entry's stored length to EQUAL the query's
        (a padded APM row is only valid at its own length), the gathered
        arena rows are sliced to the bucket length, and every attention
        branch masks pad keys.
        """
        cfg = self.cfg
        kernel_path = self.mc.mode == "kernel" and kind == "attn"
        varlen = qlen is not None
        impl = self._kernel_impl if kernel_path else None
        key = ("fused", kernel_path, kind, li if cfg.moe else 0, h.shape,
               self.mc.device_quanta, capture, view.codec_key,
               view.index_key, varlen, impl)
        fn = self._jit_cache.get(key)
        if fn is None:
            pool, act = self.embedder.pool, self.embedder.act
            from repro.core.embedding import embed_apply
            interpret = self._interpret
            codec = self.store.codec
            codec_name = codec.name
            # search_device is pure given ``args``; the instance only
            # contributes static config (nprobe/backend), which is fixed
            # per store — so closing over this view's index is safe even
            # after a rebuild swaps in a new instance of the same class
            # (the class itself is part of the jit key via index_key)
            index = view.index
            # sharded store (DESIGN.md §2.12): the index returns the
            # winner's codec rows FROM its single-collective combine —
            # the device arenas are position-indexed per shard, so a
            # slot-id gather against them would be wrong (and a second
            # cross-shard collective)
            sharded = getattr(index, "is_sharded", False)
            f_memo = (attn_mod.gqa_apply_memo if kind == "attn"
                      else attn_mod.mla_apply_memo)
            f_attn = (attn_mod.gqa_apply if kind == "attn"
                      else attn_mod.mla_apply)
            mask_kind = "causal" if cfg.causal else "bidir"
            B = h.shape[0]
            # quanta must tile the batch; otherwise one whole-batch quantum
            nq = (self.mc.device_quanta
                  if (1 < self.mc.device_quanta <= B
                      and B % self.mc.device_quanta == 0) else 1)

            def bucketed(lp, xs, apm, hit, pos, kp, size):
                def all_hit(ops):
                    xs, apm, hit, pos, kp = ops
                    return f_memo(lp["mix"], xs, cfg,
                                  apm.astype(jnp.float32))

                def all_miss(ops):
                    xs, apm, hit, pos, kp = ops
                    y, _ = f_attn(lp["mix"], xs, cfg, positions=pos,
                                  mask_kind=mask_kind,
                                  window=cfg.sliding_window, kpad=kp)
                    return y

                def mixed(ops):
                    xs, apm, hit, pos, kp = ops
                    y, _ = f_attn(lp["mix"], xs, cfg, positions=pos,
                                  mask_kind=mask_kind,
                                  window=cfg.sliding_window, kpad=kp,
                                  memo=attn_mod.Memo(apm=apm, hit=hit))
                    return y

                n_hit = jnp.sum(hit.astype(jnp.int32))
                return jax.lax.cond(
                    n_hit == size, all_hit,
                    lambda ops: jax.lax.cond(n_hit == 0, all_miss, mixed,
                                             ops),
                    (xs, apm, hit, pos, kp))

            arena_len = self.store.apm_shape[-1]

            def memo_layer(lp, emb_p, sargs, db_parts, ent_lens, h, thr,
                           a, b, positions, qlen, kpad):
                x = bb.norm_apply(lp["norm1"], h, cfg.norm)
                emb = embed_apply(emb_p, x, pool, act, lengths=qlen,
                                  full_len=arena_len)
                # fused=True on the kernel path forces the one-matmul
                # search prologue so memo_attention is the layer's ONLY
                # Pallas dispatch (the norms cached in sargs keep it cheap)
                if sharded:
                    d2, idx, drows = index.search_fetch(
                        emb, args=sargs, parts=db_parts)
                else:
                    drows = None
                    d2, idx = index.search_device(emb, args=sargs,
                                                  fused=kernel_path)
                dist = jnp.sqrt(jnp.maximum(d2[:, 0], 0.0))
                sim = a * dist + b
                hit = sim > thr
                idx0 = idx[:, 0].astype(jnp.int32)
                S = x.shape[1]
                # the length gate — ALWAYS on: a hit may only reuse an
                # APM captured at the query's own true length (a
                # fixed-length batch's true length is S); without it a
                # fixed-length query could replay a shorter entry whose
                # rows past its length are hard zeros
                hit = hit & (jnp.take(ent_lens, idx0)
                             == (qlen if varlen else S))

                def gather_apm():
                    """Compressed gather + on-device dequant — the only
                    place the decoded APM batch exists. Decoded THROUGH
                    f16 (host-decode parity) but returned as f32: the
                    cast fuses the rounding into the dequant pipeline,
                    whereas an f16 result would materialize as a cond
                    operand — software-emulated f16 stores are ~4× the
                    whole dequant cost on CPU. Arena rows are stored at
                    the calibration length; padded-row gathers slice to
                    this bucket's length (parity with the select path's
                    host-side slice)."""
                    rows = (drows if sharded
                            else tuple(jnp.take(p, idx0, axis=0)
                                       for p in db_parts))
                    apm = codec.decode_rows(rows).astype(jnp.float32)
                    if apm.shape[-1] != S:
                        apm = apm[..., :S, :S]
                    return apm

                if kernel_path:
                    from repro.kernels.memo_attention.ops import \
                        memo_attention
                    qq, kk, vv = attn_mod._qkv(lp["mix"], x, cfg, positions)
                    kw = dict(causal=cfg.causal, window=cfg.sliding_window,
                              impl=impl,
                              interpret=(interpret if impl == "pallas"
                                         else None))
                    if varlen:      # padded key positions mask per sequence
                        kw["lengths"] = qlen
                    if codec_name == "int8" and not sharded:
                        # fused-dequant gather: int8 tiles + scale slivers,
                        # dequantized in the kernel's VMEM
                        out = memo_attention(
                            qq, kk, vv, db_parts[0], idx0,
                            hit.astype(jnp.int32), db_scales=db_parts[1],
                            **kw)
                    elif codec_name == "f16" and not sharded:
                        out = memo_attention(
                            qq, kk, vv, db_parts[0], idx0,
                            hit.astype(jnp.int32), **kw)
                    else:
                        # factorized codecs — and ANY codec on the
                        # sharded path, whose arenas are position-
                        # indexed: decode the B gathered rows (not the
                        # DB) and feed them as a B-row database
                        ops = ((qq, kk, vv, gather_apm(),
                                hit.astype(jnp.int32))
                               + ((kw.pop("lengths"),) if varlen else ()))

                        def attend(qq, kk, vv, rows, hit, *lens):
                            return memo_attention(
                                qq, kk, vv, rows,
                                jnp.arange(B, dtype=jnp.int32), hit,
                                lengths=lens[0] if lens else None, **kw)
                        if sharded:
                            # XLA cannot partition a Pallas call: run it
                            # replicated on every device of the store
                            # mesh, like the rest of the layer
                            attend = jax.shard_map(
                                attend, mesh=index.mesh,
                                in_specs=(P(),) * len(ops), out_specs=P(),
                                check_vma=False)
                        out = attend(*ops)
                    y = jnp.einsum("bshe,hed->bsd", out, lp["mix"]["wo"])
                elif nq == 1:
                    apm = gather_apm()
                    y = bucketed(lp, x, apm, hit, positions, kpad, B)
                else:
                    apm = gather_apm()
                    order = jnp.argsort(jnp.logical_not(hit))  # hits first
                    qs = B // nq
                    x_s = jnp.take(x, order, 0)
                    apm_s = jnp.take(apm, order, 0)
                    hit_s = jnp.take(hit, order, 0)
                    pos_s = jnp.take(positions, order, 0)
                    kp_s = (None if kpad is None
                            else jnp.take(kpad, order, 0))
                    parts = [bucketed(lp, x_s[g * qs:(g + 1) * qs],
                                      apm_s[g * qs:(g + 1) * qs],
                                      hit_s[g * qs:(g + 1) * qs],
                                      pos_s[g * qs:(g + 1) * qs],
                                      None if kp_s is None
                                      else kp_s[g * qs:(g + 1) * qs], qs)
                             for g in range(nq)]
                    y = jnp.take(jnp.concatenate(parts, 0),
                                 jnp.argsort(order), 0)
                out = (self._chan_tail(lp, h + y, li), sim, hit, idx0)
                if capture:
                    # miss capture for online admission: the TRUE APM of
                    # this input, computed exactly like the miss path (so
                    # an admitted entry replays bit-for-bit). Only the apm
                    # output is consumed, so XLA dead-code-eliminates the
                    # probe's APM·V and output projection; staged in the
                    # arena dtype to halve the drain transfer.
                    _, apm_cap = f_attn(lp["mix"], x, cfg,
                                        positions=positions,
                                        mask_kind=mask_kind,
                                        window=cfg.sliding_window,
                                        kpad=kpad, return_apm=True)
                    out = out + (emb, apm_cap.astype(jnp.float16))
                return out
            fn = jax.jit(memo_layer)
            self._jit_cache[key] = fn
        return fn(lp, self.embedder.params, view.search_args,
                  view.db_parts, view.lengths, h, thr_dev,
                  jnp.float32(view.sim_a), jnp.float32(view.sim_b),
                  positions, qlen, kpad)

    def _layer_fused_prefill(self, lp, h, li, thr_dev, positions, view,
                             cache_tpl, kpad=None, qlen=None,
                             capture: bool = False):
        """The fused memoized-prefill layer (DESIGN.md §2.13): ONE jitted
        dispatch extending ``_layer_fused`` with the KV leg. The gather
        decodes the entry's KV suffix next to its APM; hit quanta skip
        Q/K projection + QKᵀ + softmax via the memo-only attention AND
        take their decode cache straight from the stored KV; miss quanta
        run exact attention and cache their freshly computed K/V. Both
        legs zero-pad the cache to ``cache_len`` — the same convention
        as ``gqa_prefill_cache`` — so a hit's cache and an exact prefill
        cache differ only by the KV codec's quantization. Returns
        (h', k_cache, v_cache, sims, hits, slots[, embs, apms, kvs]).

        Kernel-mode engines also land here for prefill batches:
        memo_attention produces attention outputs only (it cannot hand
        K/V back), so prefill always uses the bucketed-quanta
        formulation."""
        cfg = self.cfg
        varlen = qlen is not None
        Sc = bb.cache_len_from(cache_tpl)
        cdt = jax.tree.leaves(cache_tpl)[0].dtype
        key = ("fusedpf", li if cfg.moe else 0, h.shape,
               self.mc.device_quanta, capture, view.codec_key,
               view.index_key, varlen, Sc, cdt)
        fn = self._jit_cache.get(key)
        if fn is None:
            pool, act = self.embedder.pool, self.embedder.act
            from repro.core.embedding import embed_apply
            codec = self.store.codec
            index = view.index
            sharded = getattr(index, "is_sharded", False)
            B = h.shape[0]
            nq = (self.mc.device_quanta
                  if (1 < self.mc.device_quanta <= B
                      and B % self.mc.device_quanta == 0) else 1)
            n_kv, dh = cfg.n_kv_heads, cfg.head_dim
            arena_len = self.store.apm_shape[-1]

            def true_kv(lp, xs, pos, kp):
                """Exact post-RoPE K/V of a (sub-)batch, padded rows
                zeroed so a served miss cache and an admitted entry both
                follow the stored-KV convention (zeros past the true
                length)."""
                _, k, v = attn_mod._qkv(lp["mix"], xs, cfg, pos)
                if kp is not None:
                    m = kp[:, :, None, None].astype(k.dtype)
                    k, v = k * m, v * m
                return k.astype(jnp.float32), v.astype(jnp.float32)

            def bucketed(lp, xs, apm, mk, mv, hit, pos, kp, size):
                def all_hit(ops):
                    xs, apm, mk, mv, hit, pos, kp = ops
                    y = attn_mod.gqa_apply_memo(
                        lp["mix"], xs, cfg, apm.astype(jnp.float32))
                    return y, mk, mv

                def all_miss(ops):
                    xs, apm, mk, mv, hit, pos, kp = ops
                    y, _ = attn_mod.gqa_apply(
                        lp["mix"], xs, cfg, positions=pos,
                        mask_kind="causal", window=cfg.sliding_window,
                        kpad=kp)
                    k, v = true_kv(lp, xs, pos, kp)
                    return y, k, v

                def mixed(ops):
                    xs, apm, mk, mv, hit, pos, kp = ops
                    y, _ = attn_mod.gqa_apply(
                        lp["mix"], xs, cfg, positions=pos,
                        mask_kind="causal", window=cfg.sliding_window,
                        kpad=kp, memo=attn_mod.Memo(apm=apm, hit=hit))
                    k, v = true_kv(lp, xs, pos, kp)
                    m = hit[:, None, None, None]
                    return y, jnp.where(m, mk, k), jnp.where(m, mv, v)

                n_hit = jnp.sum(hit.astype(jnp.int32))
                return jax.lax.cond(
                    n_hit == size, all_hit,
                    lambda ops: jax.lax.cond(n_hit == 0, all_miss, mixed,
                                             ops),
                    (xs, apm, mk, mv, hit, pos, kp))

            def memo_layer_prefill(lp, emb_p, sargs, db_parts, ent_lens,
                                   h, thr, a, b, positions, qlen, kpad):
                x = bb.norm_apply(lp["norm1"], h, cfg.norm)
                emb = embed_apply(emb_p, x, pool, act, lengths=qlen,
                                  full_len=arena_len)
                if sharded:
                    d2, idx, drows = index.search_fetch(
                        emb, args=sargs, parts=db_parts)
                else:
                    drows = None
                    d2, idx = index.search_device(emb, args=sargs)
                dist = jnp.sqrt(jnp.maximum(d2[:, 0], 0.0))
                sim = a * dist + b
                hit = sim > thr
                idx0 = idx[:, 0].astype(jnp.int32)
                S = x.shape[1]
                # the length gate (see _layer_fused) — doubly load-
                # bearing here: a replayed KV prefix is only valid at
                # the length it was captured at
                hit = hit & (jnp.take(ent_lens, idx0)
                             == (qlen if varlen else S))
                rows = (drows if sharded
                        else tuple(jnp.take(p, idx0, axis=0)
                                   for p in db_parts))
                apm = codec.decode_rows(rows).astype(jnp.float32)
                if apm.shape[-1] != S:
                    apm = apm[..., :S, :S]
                kv = codec.decode_kv_rows(rows).astype(jnp.float32)
                mk, mv = unstack_kv_rows(kv[:, :, :S], n_kv, dh)
                if nq == 1:
                    y, k_new, v_new = bucketed(
                        lp, x, apm, mk, mv, hit, positions, kpad, B)
                else:
                    order = jnp.argsort(jnp.logical_not(hit))
                    inv = jnp.argsort(order)
                    qs = B // nq

                    def take(arr):
                        return (None if arr is None
                                else jnp.take(arr, order, 0))
                    x_s, apm_s, mk_s, mv_s = map(take, (x, apm, mk, mv))
                    hit_s, pos_s, kp_s = map(take, (hit, positions, kpad))
                    ys, ks, vs = [], [], []
                    for g in range(nq):
                        sl = slice(g * qs, (g + 1) * qs)
                        yq, kq, vq = bucketed(
                            lp, x_s[sl], apm_s[sl], mk_s[sl], mv_s[sl],
                            hit_s[sl], pos_s[sl],
                            None if kp_s is None else kp_s[sl], qs)
                        ys.append(yq)
                        ks.append(kq)
                        vs.append(vq)
                    y = jnp.take(jnp.concatenate(ys, 0), inv, 0)
                    k_new = jnp.take(jnp.concatenate(ks, 0), inv, 0)
                    v_new = jnp.take(jnp.concatenate(vs, 0), inv, 0)
                pad = ((0, 0), (0, Sc - S), (0, 0), (0, 0))
                ck = jnp.pad(k_new, pad).astype(cdt)
                cv = jnp.pad(v_new, pad).astype(cdt)
                out = (self._chan_tail(lp, h + y, li), ck, cv,
                       sim, hit, idx0)
                if capture:
                    # miss capture: the true APM + KV, computed exactly
                    # like the miss path (an admitted entry replays
                    # bit-for-bit); only these outputs are consumed, so
                    # XLA dead-code-eliminates the probe's APM·V
                    _, apm_cap = attn_mod.gqa_apply(
                        lp["mix"], x, cfg, positions=positions,
                        mask_kind="causal", window=cfg.sliding_window,
                        kpad=kpad, return_apm=True)
                    kc, vc = true_kv(lp, x, positions, kpad)
                    kv_cap = jnp.stack(
                        [kc.reshape(B, S, -1), vc.reshape(B, S, -1)],
                        1).astype(jnp.float16)
                    out = out + (emb, apm_cap.astype(jnp.float16), kv_cap)
                return out
            fn = jax.jit(memo_layer_prefill)
            self._jit_cache[key] = fn
        return fn(lp, self.embedder.params, view.search_args,
                  view.db_parts, view.lengths, h, thr_dev,
                  jnp.float32(view.sim_a), jnp.float32(view.sim_b),
                  positions, qlen, kpad)

    def _layer_plain_prefill(self, lp, h, kind, li, positions, cache,
                             kpad=None):
        """Non-memoized layers of a prefill batch: the backbone's exact
        prefill step (attention + cache build for attn/mla, recurrent
        state for the linear mixers) as one jitted dispatch."""
        key = ("plainpf", kind, li if self.cfg.moe else 0, h.shape,
               kpad is not None, bb.cache_len_from(cache))
        fn = self._jit_cache.get(key)
        if fn is None:
            cfg = self.cfg

            def memo_layer_plain_prefill(lp, h, positions, cache, kpad):
                out, c, _, _ = bb._layer_apply(
                    lp, h, cfg, kind, li, mode="prefill",
                    positions=positions, pos=None, cache=cache,
                    kpad=kpad)
                return out, c
            fn = jax.jit(memo_layer_plain_prefill)
            self._jit_cache[key] = fn
        return fn(lp, h, positions, cache, kpad)

    # ------------------------------------------------------- prefill API
    def prefill(self, batch, *, threshold: Optional[float] = None,
                active_layers: Optional[Sequence[int]] = None,
                stats: Optional[MemoStats] = None):
        """Memoized causal prefill (DESIGN.md §2.13). Returns
        (last-token logits (B, V), decode caches, stats): a hit skips
        the layer's attention AND materializes that layer's decode cache
        from the stored KV entry; a miss runs exact prefill and (under
        admission sampling) captures APM + KV. Decode continues via
        ``self.model.decode_step`` on the returned caches."""
        st = stats or MemoStats()
        prep = self.prepare_batch(batch, threshold=threshold,
                                  active_layers=active_layers,
                                  prefill=True)
        self.run_layers(prep)
        (logits, caches), st, payload = self.finalize(prep, stats=st)
        self.apply_maintenance(payload, stats=st)
        return logits, caches, st

    def prefill_exact(self, batch, *, cache_len: Optional[int] = None):
        """Exact (memo-free) prefill: the degraded-mode leg the
        MemoServer falls back to, and the parity reference the prefill
        benchmark asserts against. Returns (logits (B, V), caches)."""
        tokens = jnp.asarray(batch["tokens"])
        Sc = (int(cache_len) if cache_len
              else self._prefill_cache_len(int(tokens.shape[1])))
        key = ("pfexact", Sc)
        fn = self._jit_cache.get(key)
        if fn is None:
            model = self.model

            def memo_prefill_exact(params, tokens):
                return model.prefill(params, {"tokens": tokens},
                                     cache_len=Sc)
            fn = self._jit_cache[key] = jax.jit(memo_prefill_exact)
        return fn(self.params, tokens)

    def _capture_now(self, use_memo: bool, prefill: bool = False) -> bool:
        """Admission sampling: capture misses on every Nth served batch
        (``admit_every``) when online admission is enabled. With prefill
        memoization on, ONLY prefill batches capture — an APM-only
        admission would store zero KV planes and a later prefill hit
        would replay an empty decode cache."""
        if self.mc.prefill.enabled and not prefill:
            return False
        return (use_memo and self.mc.admit and self.store is not None
                and not self.is_encdec
                and self._serve_batches % max(1, self.mc.admit_every) == 0)

    # --------------------------------------------------- prefill serving
    def _check_prefill_supported(self):
        """Prefill memoization preconditions (DESIGN.md §2.13). The
        causal requirement IS the mask-kind gate: every stored entry was
        captured under the causal prefill mask, and a causal-only engine
        can never replay one against a bidirectional query."""
        if self.is_encdec:
            raise ValueError(
                "prefill memoization needs a decoder-only model (enc-dec "
                "hands no decode cache back from its encoder)")
        if not self.cfg.causal:
            raise ValueError(
                "prefill memoization requires a causal model: stored "
                "entries are causal-prefill states and may only be "
                "replayed under the same mask kind")
        bad = sorted(li for li, kind, _ in self._iter_layers()
                     if li in self.layers and kind != "attn")
        if bad:
            raise ValueError(
                f"prefill memoization serves GQA 'attn' layers only "
                f"(MLA caches latents, not K/V); memoized layers {bad} "
                f"are a different mixer kind")

    def _prefill_cache_len(self, S: int) -> int:
        """Decode-cache length for a prompt of length ``S``:
        ``prefill_cache_len`` if set, else 2·S headroom."""
        cl = self.mc.prefill.cache_len
        Sc = int(cl) if cl else 2 * S
        if Sc < S:
            raise ValueError(
                f"prefill_cache_len={Sc} is shorter than the prompt "
                f"({S}): the decode cache must hold the whole prefix")
        return Sc

    def _kv_probe(self, lp, x):
        """Post-RoPE K/V of one captured block, stacked into the stored
        (B, 2, S, D) plane — the KV side-channel for build-time prefill
        admission. Positions run from 0 (prefill is absolute), so the
        stored K drops into a decode cache verbatim."""
        key = ("kv_probe", x.shape)
        fn = self._jit_cache.get(key)
        if fn is None:
            cfg = self.cfg

            def run(lp, x):
                B, S = x.shape[0], x.shape[1]
                positions = jnp.broadcast_to(
                    jnp.arange(S, dtype=jnp.int32), (B, S))
                _, k, v = attn_mod._qkv(lp["mix"], x, cfg, positions)
                return jnp.stack([k.reshape(B, S, -1),
                                  v.reshape(B, S, -1)],
                                 1).astype(jnp.float16)
            fn = self._jit_cache[key] = jax.jit(run)
        return fn(lp, x)

    def _split_caches(self, caches) -> dict:
        """Flatten a ``model.init_caches`` pytree into {layer_idx: cache}
        — the per-layer view the step-wise prefill executor works in.
        Scan segments carry a leading reps axis; slicing it off here and
        re-stacking in ``_merge_caches`` mirrors exactly what the
        backbone's unroll branch does."""
        out = {}
        for si, seg in enumerate(bb.scan_plan(self.cfg)):
            grp = caches[f"seg{si}"]
            if seg.kind == "single":
                for u in range(len(seg.unit)):
                    out[seg.start + u] = grp[f"l{u}"]
            else:
                for r in range(seg.reps):
                    rep = jax.tree.map(lambda a: a[r], grp)
                    for u in range(len(seg.unit)):
                        out[seg.start + r * len(seg.unit) + u] = rep[f"l{u}"]
        return out

    def _merge_caches(self, by_li: dict):
        """Inverse of ``_split_caches``: {layer_idx: cache} → the segment
        pytree ``model.decode_step`` consumes."""
        caches = {}
        for si, seg in enumerate(bb.scan_plan(self.cfg)):
            if seg.kind == "single":
                caches[f"seg{si}"] = {
                    f"l{u}": by_li[seg.start + u]
                    for u in range(len(seg.unit))}
            else:
                groups = [
                    {f"l{u}": by_li[seg.start + r * len(seg.unit) + u]
                     for u in range(len(seg.unit))}
                    for r in range(seg.reps)]
                caches[f"seg{si}"] = jax.tree.map(
                    lambda *a: jnp.stack(a), *groups)
        return caches

    def split_cache_rows(self, caches) -> tuple:
        """Cut a batch's decode caches (the segment pytree ``finalize`` or
        ``prefill_exact`` returns) into one pytree per batch row, row i's
        in the layout ``model.decode_step`` takes. ONE jitted program,
        ``memo_split_caches``, cached per cache tree and leaf shapes (so
        per padded row count): a batch's fill never retraces it, and the
        caller drops filler rows' trees. Every leaf is sliced the same
        way, in single and scan segments alike (slicing the merged tree
        directly would hit scan segments' leading reps axis instead of
        the batch axis)."""
        leaves, tree = jax.tree.flatten(caches)
        key = ("split", tree, tuple((a.shape, a.dtype) for a in leaves))
        fn = self._jit_cache.get(key)
        if fn is None:
            def memo_split_caches(caches):
                by_li = self._split_caches(caches)
                rows = jax.tree.leaves(by_li)[0].shape[0]
                return tuple(self._merge_caches({
                    li: jax.tree.map(lambda a, i=i: a[i: i + 1], c)
                    for li, c in by_li.items()}) for i in range(rows))
            fn = self._jit_cache[key] = jax.jit(memo_split_caches)
        return fn(caches)

    def _memo_drain(self, prep: PreparedBatch):
        """Dispatch ``memo_drain``: ONE jitted program that packs the
        per-layer device stats of ``prep.pend`` into one int32 block
        (L, 3, B) — sims bit-cast from float32, hits, slots — plus, under
        capture, the stacked embs, APMs and (prefill) KV planes. Issued
        before the barrier so its dispatch overlaps the device; returns
        the device outputs, or None when no layer was memoized. All B
        rows are packed: ``_drain_stats`` slices ``n_valid`` on the host,
        so a batch's fill never retraces the program."""
        pend = prep.pend
        if not pend:
            return None
        n_cap = len(pend[0]) - 4 if prep.capture else 0
        key = ("drain", prep.h.shape, len(pend), n_cap)
        fn = self._jit_cache.get(key)
        if fn is None:
            def memo_drain(per_layer):
                stats = jnp.stack([jnp.stack([
                    jax.lax.bitcast_convert_type(
                        sims.astype(jnp.float32), jnp.int32),
                    hits.astype(jnp.int32), slots.astype(jnp.int32)])
                    for sims, hits, slots, *_ in per_layer])
                return (stats,) + tuple(
                    jnp.stack([p[3 + k] for p in per_layer])
                    for k in range(n_cap))
            fn = self._jit_cache[key] = jax.jit(memo_drain)
        return fn(tuple(p[1:4 + n_cap] for p in pend))

    def _drain_stats(self, prep: PreparedBatch, st: MemoStats,
                     drained) -> MaintenancePayload:
        """Read ``memo_drain``'s outputs (``drained``, dispatched before
        the barrier) to the host in ONE ``jax.device_get`` and fold them
        into ``st``. Rows past ``n_valid`` (runtime batch padding) are
        dropped here, on the host. Returns the MaintenancePayload — reuse
        slots and captured misses — WITHOUT touching the store: the
        caller decides where maintenance runs (inline vs the MemoServer
        worker)."""
        pend = prep.pend
        out = MaintenancePayload(
            generation=getattr(prep.view, "generation", -1))
        if drained is None:
            return out
        nv = prep.n_valid
        stats, *cap = jax.device_get(drained)
        sims = stats[:, 0, :nv].view(np.float32)                 # (L, nv)
        hits = stats[:, 1, :nv] > 0
        slots = stats[:, 2, :nv]
        for p, s_row, h_row in zip(pend, sims, hits):
            li = p[0]
            st.n_layer_attempts += int(s_row.shape[0])
            nh = int(h_row.sum())
            st.n_hits += nh
            st.per_layer_hits[li] = st.per_layer_hits.get(li, 0) + nh
            st.sims.extend(s_row.tolist())
        if hits.any():
            out.reuse_slots = slots[hits]
        if cap:
            embs, apms = cap[0][:, :nv], cap[1][:, :nv]
            # prefill capture stages the KV plane as a third block
            kvs = cap[2][:, :nv] if len(cap) > 2 else None
            lens = None if prep.lengths is None else prep.lengths[:nv]
            for l in range(embs.shape[0]):
                miss = ~hits[l]
                if miss.any():
                    out.admissions.append(self._stage_capture(
                        apms[l][miss], embs[l][miss],
                        None if lens is None else lens[miss],
                        None if kvs is None else kvs[l][miss]))
        return out

    def _stage_capture(self, apms, embs, lens, kv=None):
        """Normalize one captured miss block for admission: pad the APMs
        (and the KV plane, when prefill capture staged one) to the arena
        (calibration) length and zero the pad-query rows, so a stored
        entry is identical no matter which bucket captured it — only its
        true length matters (the length gate guarantees it is only ever
        replayed at that length)."""
        S_max = self.store.apm_shape[-1]
        B, H, S = apms.shape[:3]
        if lens is None:
            lens = np.full(B, S, np.int32)
        elif isinstance(lens, np.ndarray):
            lens = lens.astype(np.int32, copy=False)
        else:
            lens = np.asarray(lens, np.int32)
        if S < S_max:
            padded = np.zeros((B, H, S_max, S_max), apms.dtype)
            padded[:, :, :S, :S] = apms
            apms = padded
            if kv is not None:
                pk = np.zeros(kv.shape[:2] + (S_max, kv.shape[-1]),
                              kv.dtype)
                pk[:, :, :S] = kv
                kv = pk
        if (lens < S_max).any():
            row_ok = np.arange(S_max)[None, :] < lens[:, None]
            apms = apms * row_ok[:, None, :, None].astype(apms.dtype)
            if kv is not None:
                kv = kv * row_ok[:, None, :, None].astype(kv.dtype)
        return apms, embs, lens, kv

    def apply_maintenance(self, payload: Optional[MaintenancePayload],
                          stats: Optional[MemoStats] = None) -> None:
        """Run one batch's host-tier store work — reuse-clock feeding,
        budgeted admission + eviction, generation-counted delta sync, and
        periodic recalibration — finishing with an atomic snapshot
        publish. ``infer`` calls this inline (synchronous batch-boundary
        maintenance); the MemoServer's background worker calls it
        off-thread, double-buffered against the next batch's device
        compute (DESIGN.md §2.7). Exactly one maintenance actor may run
        at a time; the MemoStore's lock backstops misuse.

        Retry-safe (the supervised worker's contract, DESIGN.md §2.9):
        payload fields are CONSUMED as they land — reuse feeding and the
        move into ``_pending_admissions`` happen at most once — so
        re-applying a payload whose first attempt died mid-sync cannot
        double-admit; the retry just drives the store back to a clean,
        published generation (the trailing ``device_stale`` sync)."""
        if payload is None or self.store is None:
            return
        st = stats or MemoStats()
        if payload.reuse_slots is not None and payload.reuse_slots.size:
            slots, payload.reuse_slots = payload.reuse_slots, None
            self.store.note_reuse(slots)
        if payload.admissions:
            adds, payload.admissions = payload.admissions, []
            self._pending_admissions.extend(adds)
        self._flush_admissions(st)
        if self.store.device_stale:
            # nothing pending but host/device generations diverged — a
            # previous attempt admitted and then failed to sync (or a
            # quarantine dirtied slots); one generation-counted sync
            # re-converges (a clean store skips this entirely)
            self.store.sync()

    def _flush_admissions(self, st: MemoStats):
        """Batch-boundary admission: push captured misses into the host
        tier under the byte budget, then delta-sync the device tier. Never
        on the per-layer hot path."""
        if not self._pending_admissions:
            return
        pend, self._pending_admissions = self._pending_admissions, []
        apms = np.concatenate([p[0] for p in pend], 0)
        embs = np.concatenate([p[1] for p in pend], 0)
        lens = np.concatenate([p[2] for p in pend], 0)
        # KV planes ride along iff every staged block carries one (APM-
        # only and prefill captures never mix: _capture_now gates them)
        kv = (np.concatenate([p[3] for p in pend], 0)
              if all(p[3] is not None for p in pend) else None)
        cspec = self.mc.capacity
        if (apms.shape[0] and cspec.promote
                and self.store.capacity is not None):
            # async promotion (DESIGN.md §2.11): misses the disk tier can
            # satisfy are re-admitted bit-identically from their durable
            # copies instead of re-encoded from the fresh capture — the
            # promoted rows ride the same delta sync as the admissions
            promoted = self.store.promote_for(
                embs, lens, threshold=float(self.mc.threshold),
                max_promote=int(cspec.promote_max))
            if promoted.any():
                keep = ~promoted
                apms, embs, lens = apms[keep], embs[keep], lens[keep]
                kv = kv[keep] if kv is not None else None
        if apms.shape[0]:
            slots = self.store.admit(apms, embs, lens, kv=kv)
            st.add_admitted(int(slots.size))
            self.store.sync()
            self._flush_count += 1
            if self.mc.recal_every:
                self._recal_buf.append((apms, embs))
                self._recal_buf = self._recal_buf[-16:]   # rolling window
                if self._flush_count % self.mc.recal_every == 0:
                    self._recalibrate_online()
                    # recal changed sim_cal: re-publish so the next batch
                    # serves the refreshed calibration
                    self.store.publish()

    def _recalibrate_online(self, n_pairs: int = 192, blend: float = 0.5):
        """Refit sim ≈ a·dist + b from recently captured misses — each
        carries its embedding AND its true APM, i.e. exactly the data
        build-time ``_calibrate`` uses. Under drift the stale map
        under-predicts similarity (the top-1 match is the right template,
        but its predicted sim starves the threshold); refitting on
        current-traffic pairs restores the threshold's true-similarity
        meaning. Blended (EMA) for stability."""
        apms = np.concatenate([a for a, _ in self._recal_buf], 0)
        embs = np.concatenate([e for _, e in self._recal_buf], 0)
        n = apms.shape[0]
        if n < 8:
            return
        rng = np.random.default_rng(self._serve_batches)
        ia, ib = rng.integers(0, n, n_pairs), rng.integers(0, n, n_pairs)
        dist = np.linalg.norm(embs[ia] - embs[ib], axis=-1)
        if np.std(dist) < 1e-9:
            return
        sim = np.asarray(jax.vmap(similarity_score)(
            jnp.asarray(apms[ia], jnp.float32),
            jnp.asarray(apms[ib], jnp.float32)))
        a, b = np.polyfit(dist, sim, 1)
        a0, b0 = self.sim_cal
        self.sim_cal = (blend * float(a) + (1 - blend) * a0,
                        blend * float(b) + (1 - blend) * b0)

    def _infer_encdec(self, batch, thr, active, st: MemoStats, use_memo):
        """Whisper path: memoized encoder, plain decoder."""
        from repro.models import encdec as ed
        cfg, params = self.cfg, self.params
        frames = batch["frames"]
        st.n_inputs += frames.shape[0]
        h = (frames.astype(params["enc_pos"].dtype)
             + params["enc_pos"][None, : frames.shape[1]])
        ecfg = self.model._ecfg
        positions = jnp.broadcast_to(
            jnp.arange(h.shape[1], dtype=jnp.int32), h.shape[:2])
        for li in range(cfg.encoder.n_layers):
            lp = jax.tree.map(lambda a: a[li], params["enc_layers"])
            memo = None
            if use_memo and li in active and self.db is not None:
                memo = self._lookup(lp, h, "attn", thr, st, li)
            key = ("enc_layer", memo is not None, h.shape)
            fn = self._jit_cache.get(key)
            if fn is None:
                def run(lp, hh, memo, positions):
                    from repro.models import attention as am
                    from repro.models.layers import mlp_apply
                    x = bb.norm_apply(lp["norm1"], hh, cfg.norm)
                    y, _ = am.gqa_apply(lp["attn"], x, ecfg,
                                        positions=positions,
                                        mask_kind="bidir", memo=memo,
                                        use_rope=False)
                    hh = hh + y
                    x = bb.norm_apply(lp["norm2"], hh, cfg.norm)
                    return hh + mlp_apply(lp["mlp"], x, cfg.act, cfg.glu)
                fn = jax.jit(run)
                self._jit_cache[key] = fn
            h = fn(lp, h, memo, positions)
        enc_h = bb.norm_apply(params["enc_norm"], h, cfg.norm)
        hd, _ = ed.decode_tokens(params, batch["tokens"], enc_h, cfg,
                                 mode="full")
        hd = bb.norm_apply(params["final_norm"], hd, cfg.norm)
        return hd @ params["embed"].T, st

    def _lookup(self, lp, h, kind, thr, st: MemoStats, li,
                positions=None, capture: bool = False, lengths=None,
                kpad=None, n_valid: Optional[int] = None):
        cfg = self.cfg
        S = h.shape[1]
        nv = h.shape[0] if n_valid is None else n_valid
        t0 = time.perf_counter()
        x = bb.norm_apply(lp["norm1"], h, cfg.norm)
        emb = self._embed(x, lengths=lengths)
        jax.block_until_ready(emb)
        t1 = time.perf_counter()
        emb_np = np.asarray(emb)
        dist, idx = self.store.lookup(emb_np, 1)
        sim_est = self.predict_sim(dist[:, 0])
        hit = sim_est > thr
        # length gate (host leg), ALWAYS on — mirrors the fused path: a
        # fixed-length batch's true length is S
        ent = self.store.entry_lengths(idx[:, 0])
        hit = hit & (ent == (np.asarray(lengths, np.int32)
                             if lengths is not None else S))
        t2 = time.perf_counter()
        apm = self.db.get(idx[:, 0])                     # host arena gather
        if apm.shape[-1] != S:
            apm = apm[:, :, :S, :S]      # arena rows sliced to the bucket
        t3 = time.perf_counter()
        st.t_embed += t1 - t0
        st.t_search += t2 - t1
        st.t_fetch += t3 - t2
        st.n_layer_attempts += nv
        nh = int(hit[:nv].sum())
        st.n_hits += nh
        st.per_layer_hits[li] = st.per_layer_hits.get(li, 0) + nh
        st.sims.extend(sim_est[:nv].tolist())
        if capture and positions is not None and (~hit[:nv]).any():
            apm_true = np.asarray(self._apm_probe(lp, x, kind, positions,
                                                  kpad=kpad))
            miss = ~hit[:nv]
            self._pending_admissions.append(self._stage_capture(
                apm_true[:nv][miss], emb_np[:nv][miss],
                None if lengths is None
                else np.asarray(lengths, np.int32)[:nv][miss]))
        # keep the APM batch in the arena dtype (f16) and on the host —
        # the jitted consumer casts on-device (one transfer, no copies)
        return attn_mod.Memo(apm=apm, hit=hit, idx=idx[:, 0])

    def _apm_probe(self, lp, x, kind, positions, kpad=None):
        """The true APM of the normed input, computed with the exact miss
        path semantics — the host-path analogue of the fused capture (only
        the apm output is used, so the probe's APM·V + output projection
        are dead-code-eliminated inside the jit)."""
        key = ("apm_probe", kind, x.shape, kpad is not None)
        fn = self._jit_cache.get(key)
        if fn is None:
            cfg = self.cfg
            f_attn = (attn_mod.gqa_apply if kind == "attn"
                      else attn_mod.mla_apply)
            mask_kind = "causal" if cfg.causal else "bidir"

            def run(lp, x, positions, kpad):
                _, apm = f_attn(lp["mix"], x, cfg, positions=positions,
                                mask_kind=mask_kind, kpad=kpad,
                                window=cfg.sliding_window, return_apm=True)
                return apm.astype(jnp.float16)
            fn = jax.jit(run)
            self._jit_cache[key] = fn
        return fn(lp, x, positions, kpad)

    # -- layer application --------------------------------------------------
    def _chan_tail(self, lp, h, li):
        """norm2 + channel mixer (moe/mlp) tail shared by every jitted
        layer body — traceable, so it is called INSIDE the jits; one copy
        keeps the fast/host/kernel paths from diverging."""
        cfg = self.cfg
        x = bb.norm_apply(lp["norm2"], h, cfg.norm)
        if bb._chan_kind(cfg, li) == "moe":
            from repro.models import moe as moe_mod
            out, _ = moe_mod.moe_apply(lp["chan"], x, cfg)
        else:
            from repro.models.layers import mlp_apply
            out = mlp_apply(lp["chan"], x, cfg.act, cfg.glu)
        return h + out

    def _layer_plain(self, lp, h, kind, li, memo, positions, kpad=None):
        key = ("plain", kind, li if self.cfg.moe else 0, memo is not None,
               h.shape, kpad is not None)
        fn = self._jit_cache.get(key)
        if fn is None:
            cfg = self.cfg

            def memo_layer_plain(lp, h, memo, positions, kpad):
                out, _, _, _ = bb._layer_apply(
                    lp, h, cfg, kind, li, mode="full", positions=positions,
                    pos=None, cache=None, memo=memo, kpad=kpad)
                return out
            fn = jax.jit(memo_layer_plain)
            self._jit_cache[key] = fn
        return fn(lp, h, memo, positions, kpad)

    def _layer_bucket(self, lp, h, kind, li, memo, positions):
        """Split rows into hit/miss buckets; hits use the memo-only
        attention (skips QKᵀ+softmax for real), misses run normally.
        The whole layer (norm → bucketed attention → scatter-combine →
        channel mixer) is ONE jitted dispatch — the engine-level analogue
        of cutting the paper's 'cascaded memory access' chain (§5.3)."""
        cfg = self.cfg
        hit = np.asarray(memo.hit)
        B = h.shape[0]
        hit_idx = np.nonzero(hit)[0]
        miss_idx = np.nonzero(~hit)[0]
        if hit_idx.size == 0:
            return self._layer_plain(lp, h, kind, li, None, positions)
        # power-of-2 bucket padding bounds the number of distinct compiled
        # shapes to log2(B) per layer kind
        q = self.mc.bucket_quantum

        def pad_to(n):
            p = q
            while p < n:
                p *= 2
            return min(p, B)

        nh = pad_to(hit_idx.size)
        nm = pad_to(miss_idx.size) if miss_idx.size else 0
        sel_h = np.concatenate([hit_idx,
                                np.zeros(nh - hit_idx.size, np.int64)])
        sel_m = (np.concatenate([miss_idx,
                                 np.zeros(nm - miss_idx.size, np.int64)])
                 if nm else np.zeros(0, np.int64))
        # ship only the hit APMs, in the arena dtype (f16)
        apm_hit = np.asarray(memo.apm)[sel_h]

        key = ("bucket", kind, li if self.cfg.moe else 0, h.shape, nh, nm)
        fn = self._jit_cache.get(key)
        if fn is None:
            n_hit_real = None  # shapes only; real counts via masks below

            def run(lp, h, apm, sel_h, sel_m, keep_h, keep_m, positions):
                x = bb.norm_apply(lp["norm1"], h, cfg.norm)
                f_memo = (attn_mod.gqa_apply_memo if kind == "attn"
                          else attn_mod.mla_apply_memo)
                y = jnp.zeros_like(h)
                y_hit = f_memo(lp["mix"], jnp.take(x, sel_h, 0), cfg,
                               apm.astype(jnp.float32))
                y = y.at[sel_h].add(y_hit * keep_h[:, None, None])
                if sel_m.shape[0]:
                    f_attn = (attn_mod.gqa_apply if kind == "attn"
                              else attn_mod.mla_apply)
                    y_miss, _ = f_attn(
                        lp["mix"], jnp.take(x, sel_m, 0), cfg,
                        positions=jnp.take(positions, sel_m, 0),
                        mask_kind="causal" if cfg.causal else "bidir",
                        window=cfg.sliding_window)
                    y = y.at[sel_m].add(y_miss * keep_m[:, None, None])
                return self._chan_tail(lp, h + y, li)
            fn = jax.jit(run)
            self._jit_cache[key] = fn
        keep_h = (np.arange(nh) < hit_idx.size).astype(np.float32)
        keep_m = (np.arange(nm) < miss_idx.size).astype(np.float32)
        return fn(lp, h, jnp.asarray(apm_hit), jnp.asarray(sel_h),
                  jnp.asarray(sel_m), jnp.asarray(keep_h),
                  jnp.asarray(keep_m), positions)

    def _layer_kernel(self, lp, h, li, memo, positions, lengths=None):
        """The host-synchronous kernel-mode layer: hits are served by the
        fused memo_attention dispatch — APM tiles gathered from the
        device-resident DB by scalar-prefetched index, the hit flag
        driving the BlockSpec index maps so misses fetch zero DB bytes
        and hits skip the Q/K stream. The implementation is
        ``_kernel_impl`` ("pallas" on accelerators / explicit interpret;
        the one-matmul XLA form on CPU). ``lengths`` (B,) serves
        variable-length batches through the kernel's per-sequence key
        mask."""
        cfg = self.cfg
        self.store.sync()        # generation-counted: no-op unless stale
        hit_idx = jnp.asarray(memo.idx, jnp.int32)
        hit = jnp.asarray(memo.hit, jnp.int32)
        interpret = self._interpret
        impl = self._kernel_impl
        store = self.store
        varlen = lengths is not None
        if varlen:
            lengths = jnp.asarray(lengths, jnp.int32)
        key = ("kernel", li if cfg.moe else 0, h.shape, store.codec.key,
               varlen, impl)
        fn = self._jit_cache.get(key)
        if fn is None:
            codec_name = store.codec.name

            def run(lp, h, db_parts, hit_idx, hit, positions, lengths):
                from repro.kernels.memo_attention.ops import memo_attention
                x = bb.norm_apply(lp["norm1"], h, cfg.norm)
                q, k, v = attn_mod._qkv(lp["mix"], x, cfg, positions)
                kw = dict(causal=cfg.causal, window=cfg.sliding_window,
                          impl=impl,
                          interpret=(interpret if impl == "pallas"
                                     else None))
                if varlen:
                    kw["lengths"] = lengths
                if codec_name == "int8":   # fused-dequant gather in VMEM
                    out = memo_attention(q, k, v, db_parts[0], hit_idx, hit,
                                         db_scales=db_parts[1], **kw)
                elif codec_name == "f16":
                    out = memo_attention(q, k, v, db_parts[0], hit_idx, hit,
                                         **kw)
                else:                      # factorized: decode B rows only
                    rows = tuple(jnp.take(p, hit_idx, axis=0)
                                 for p in db_parts)
                    out = memo_attention(
                        q, k, v,
                        store.codec.decode_rows(rows).astype(jnp.float32),
                        jnp.arange(h.shape[0], dtype=jnp.int32), hit, **kw)
                y = jnp.einsum("bshe,hed->bsd", out, lp["mix"]["wo"])
                return self._chan_tail(lp, h + y, li)
            fn = jax.jit(run)
            self._jit_cache[key] = fn
        return fn(lp, h, self.device_db.parts, hit_idx, hit, positions,
                  lengths)

    def _memo_only(self, lp, x, kind, apm):
        key = ("memo_only", kind, x.shape)
        fn = self._jit_cache.get(key)
        if fn is None:
            cfg = self.cfg
            f = (attn_mod.gqa_apply_memo if kind == "attn"
                 else attn_mod.mla_apply_memo)
            fn = jax.jit(lambda lp, x, apm: f(lp["mix"], x, cfg, apm))
            self._jit_cache[key] = fn
        return fn(lp, x, apm)

    def _attn_only(self, lp, x, kind, positions):
        key = ("attn_only", kind, x.shape)
        fn = self._jit_cache.get(key)
        if fn is None:
            cfg = self.cfg
            mask_kind = "causal" if cfg.causal else "bidir"
            f = attn_mod.gqa_apply if kind == "attn" else attn_mod.mla_apply

            def run(lp, x, positions):
                y, _ = f(lp["mix"], x, cfg, positions=positions,
                         mask_kind=mask_kind, window=cfg.sliding_window)
                return y
            fn = jax.jit(run)
            self._jit_cache[key] = fn
        return fn(lp, x, positions)

    def _chan_only(self, lp, h, li):
        key = ("chan", li if self.cfg.moe else 0, h.shape)
        fn = self._jit_cache.get(key)
        if fn is None:
            cfg = self.cfg
            ck = bb._chan_kind(cfg, li)

            def run(lp, h):
                x = bb.norm_apply(lp["norm2"], h, cfg.norm)
                if ck == "moe":
                    from repro.models import moe as moe_mod
                    y, _ = moe_mod.moe_apply(lp["chan"], x, cfg)
                else:
                    from repro.models.layers import mlp_apply
                    y = mlp_apply(lp["chan"], x, cfg.act, cfg.glu)
                return h + y
            fn = jax.jit(run)
            self._jit_cache[key] = fn
        return fn(lp, h)

    # ------------------------------------------------------------- selective
    def _fused_lookup_probe(self, x):
        """The memo overhead the FAST PATH actually pays, as one jitted
        dispatch: embed → device search → compressed gather → dequant —
        exactly the lookup portion of ``_layer_fused``, minus the
        attention both branches share. Used by ``profile``; the old
        host-synchronous chain (numpy search + arena fetch + per-step
        barriers) overstated t_overhead by the round-trips and disabled
        layers the fused path would win on."""
        store = self.store
        key = ("profov", x.shape, store.codec.key,
               type(store.device_index).__name__)
        fn = self._jit_cache.get(key)
        if fn is None:
            pool, act = self.embedder.pool, self.embedder.act
            from repro.core.embedding import embed_apply

            sharded = getattr(store.device_index, "is_sharded", False)

            def run(emb_p, x, sargs, db_parts, a, b):
                emb = embed_apply(emb_p, x, pool, act)
                if sharded:     # rows ride the combine (position-indexed
                    d2, _, rows = store.device_index.search_fetch(
                        emb, args=sargs, parts=db_parts)    # arenas)
                else:
                    d2, idx = store.device_index.search_device(
                        emb, args=sargs)
                    idx0 = idx[:, 0].astype(jnp.int32)
                    rows = tuple(jnp.take(p, idx0, axis=0)
                                 for p in db_parts)
                dist = jnp.sqrt(jnp.maximum(d2[:, 0], 0.0))
                return (a * dist + b,
                        store.codec.decode_rows(rows).astype(jnp.float32))
            fn = self._jit_cache[key] = jax.jit(run)
        a, b = self.sim_cal
        return fn(self.embedder.params, x, self.device_index.search_args,
                  self.device_db.parts, jnp.float32(a), jnp.float32(b))

    def profile(self, batch, *, alpha_from: Optional[MemoStats] = None
                ) -> PerfModel:
        """Offline profiler (paper §5.4): measure per-layer attention time
        and memo overhead on a calibration batch; α comes from calibration
        stats (or a dry lookup pass). t_overhead is measured on the path
        that will serve: the fused-jit lookup when the device fast path
        is active, the host-synchronous chain otherwise."""
        cfg = self.cfg
        fast = self._use_fast_path()
        if fast:
            self.store.sync()      # materialize the tier the probe times
        h = bb.embed_tokens(self.params, batch["tokens"], cfg)
        positions = jnp.broadcast_to(
            jnp.arange(batch["tokens"].shape[1], dtype=jnp.int32),
            batch["tokens"].shape)
        if alpha_from is None:
            st = MemoStats()
            self.infer(batch, stats=st)
            alpha_from = st
        profiles = {}
        for li, kind, lp in self._iter_layers():
            if li not in self.layers:
                h = self._layer_plain(lp, h, kind, li, None, positions)
                continue
            t_attn = timeit_median(
                lambda lp=lp, h=h, k=kind: self._attn_only(lp, h, k,
                                                           positions), reps=3)
            if fast:
                t_over = timeit_median(
                    lambda h=h: self._fused_lookup_probe(h), reps=3)
            else:
                t_over = timeit_median(
                    lambda h=h: self._embed(h), reps=3)
                emb = np.asarray(self._embed(h))
                t0 = time.perf_counter()
                dist, idx = self.index.search(emb, 1)
                self.db.get(idx[:, 0], count_reuse=False)
                t_over += time.perf_counter() - t0
            alpha = (alpha_from.per_layer_hits.get(li, 0)
                     / max(1, alpha_from.n_inputs))
            profiles[li] = LayerProfile(t_attn=t_attn, t_overhead=t_over,
                                        alpha=min(1.0, alpha))
            h = self._layer_plain(lp, h, kind, li, None, positions)
        self.perf = PerfModel(profiles)
        return self.perf
