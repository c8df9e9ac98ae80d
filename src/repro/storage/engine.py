"""MicroNN: the embeddable engine facade (paper Fig. 1).

Ties together the durable SQLite tier, the device-resident IVF index, the
index monitor, and the hybrid query optimizer -- the public API an
application links against:

    eng = MicroNN(dim=128, n_attr=2)
    with eng.session() as s:         # batched writes: ONE transaction
        s.upsert(ids, vecs, attrs)
        s.delete(stale_ids)
    eng.build()                      # initial clustering
    rs = eng.query(q, Q.knn(k=100).probe(8))
    rs = eng.query(q, Q.knn(k=10).where(Pred(0, "==", 3.0)))
    eng.maintain(until_idle=True)    # drain incremental maintenance
    eng.maintain_step()              # ... or one bounded quantum at a time

`query(vecs, spec)` is the ONE query entry point: the frozen QuerySpec
(core/query.py) routes resident / paged / hybrid-optimized execution and
doubles as the executor's jit cache key; every path returns a ResultSet.
`search(...)` survives as a deprecation-free kwarg shim over spec
construction.

Writes are serialised (single writer, paper §3.6); every write lands in
SQLite (durable, WAL) *and* in the device index (delta-store), so readers
see updates immediately while the host copy guarantees recoverability --
`MicroNN.recover()` rebuilds device state from SQLite after a crash.
`session()` batches a write burst into one SQLite transaction, one
delta-encode batch, and one deferred pager-invalidation pass at commit.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import warnings
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import delta as delta_ops
from ..core import executor, ivf, kmeans, maintenance, quantize
from ..core.hybrid import AttributeStats, Node
from ..core.monitor import IndexMonitor, MonitorConfig, WorkItem
from ..core.optimizer import HybridOptimizer
from ..core.query import Q, QuerySpec, ResultSet
from ..core.types import (INVALID_ID, DeltaStore, IVFConfig, IVFIndex,
                          PagedIndex, SearchResult, effective_pad_to,
                          normalize_if_cosine)
from ..obs import metrics as obs_metrics
from ..obs import recorder as obs_recorder
from ..obs import trace as obs_trace
from . import pager
from .scheduler import MaintenanceScheduler, StepReport
from .store import VectorStore


def _locked(fn):
    """Run the method under the engine's write mutex (`self.lock`).

    Applied to every durable-state writer so a session commit, a direct
    upsert/delete, and a maintenance quantum (foreground or daemon) can
    never interleave partial transactions; re-entrant, so locked paths
    may nest (upsert -> maintain(force="flush"))."""
    import functools

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self.lock:
            return fn(self, *args, **kwargs)
    return wrapper


class WriteSession:
    """Batched write scope: `with db.session() as s: s.upsert(...);
    s.delete(...)`.

    Ops are buffered and coalesced (last write per asset id wins) until
    the `with` block exits cleanly, then committed as ONE unit: one
    SQLite transaction (the durable all-or-nothing boundary), one
    delta-encode batch (a single delta upsert call encodes every new row
    in one pass, instead of one encode per call), and one deferred
    pager-invalidation pass (paged mode drops each touched partition's
    frame exactly once, however many session ops touched it). An
    exception inside the block discards the session -- nothing lands.
    """

    def __init__(self, engine: "MicroNN"):
        self._engine = engine
        self._ops: List[tuple] = []
        self._closed = False

    # -- buffered write ops --------------------------------------------------
    def upsert(self, ids: np.ndarray, vecs: np.ndarray,
               attrs: Optional[np.ndarray] = None):
        assert not self._closed, "session already committed/discarded"
        n_attr = self._engine.store.n_attr
        attrs = np.zeros((len(ids), n_attr), np.float32) if attrs is None \
            else np.array(attrs, np.float32, copy=True)
        self._ops.append(("up", np.array(ids, np.int64, copy=True),
                          np.array(vecs, np.float32, copy=True), attrs))

    def delete(self, ids: np.ndarray):
        assert not self._closed, "session already committed/discarded"
        self._ops.append(("del", np.array(ids, np.int64, copy=True)))

    # -- lifecycle -----------------------------------------------------------
    def commit(self):
        assert not self._closed, "session already committed/discarded"
        self._closed = True
        if self._ops:
            self._engine._commit_session(self._ops)
        self._ops = []

    def discard(self):
        self._closed = True
        self._ops = []

    def __enter__(self) -> "WriteSession":
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            self.commit()
        else:
            self.discard()
        return False


class MicroNN:
    def __init__(self, dim: int, n_attr: int = 0, path: str = ":memory:",
                 config: Optional[IVFConfig] = None,
                 monitor: Optional[MonitorConfig] = None,
                 quantize: Optional[str] = None,
                 rerank_factor: Optional[int] = None,
                 memory_budget_mb: Optional[float] = None,
                 max_rows_per_step: int = 4096,
                 trace_ring_capacity: int = 256,
                 slow_query_ms: float = 100.0,
                 frame_pool=None,
                 tenant: Optional[str] = None):
        """`quantize="int8"` turns on the scalar-quantized tier: searches
        scan int8 codes and rerank `rerank_factor * k` candidates at
        float32. Both knobs land in IVFConfig (explicit kwargs override a
        passed config); codes are durable in the SQLite `codes` table.

        `memory_budget_mb` switches the engine to the paper's actual
        *disk-resident* mode: the scan tier (int8 codes when quantized,
        f32 vectors otherwise) is never fully uploaded -- it stays in
        SQLite and is paged on demand into a budget-bounded frame pool
        (storage/pager.PartitionCache), with the rerank gathering f32
        rows straight from disk. Resident memory is then O(budget +
        centroids + delta) instead of O(collection).

        `max_rows_per_step` bounds the incremental maintenance
        scheduler's work quantum: one `maintain_step()` (or one step of
        `maintain(until_idle=True)`) touches at most this many rows.

        `frame_pool` + `tenant` (PR 9 fleet mode, paged only): page
        partitions through a SHARED `fleet.pool.FramePool` instead of a
        private one -- this engine's frames then compete with every
        co-tenant's under the pool's global CLOCK and ONE fleet-wide
        byte budget. `tenant` is the stable name identifying this
        engine's frames (and its metrics label), so a spilled/reopened
        tenant resumes its cumulative series. Normally wired up by
        `fleet.Fleet`, not called directly."""
        # Engine-level write mutex (PR 7): EVERY durable-state writer --
        # upsert/delete, session commits, build/recover, and each
        # maintenance quantum (hand-cranked or the scheduler daemon's) --
        # holds this RLock, so concurrent writers can no longer
        # interleave partial transactions on the shared
        # check_same_thread=False connection. Reads never take it:
        # resident queries execute against an immutable index-pytree
        # snapshot, paged queries go through the RLock'd PartitionCache
        # and the store's WAL snapshot read connection. Re-entrant
        # because write paths nest (upsert -> maintain(force="flush")).
        self.lock = threading.RLock()
        self.store = VectorStore(path, dim=dim, n_attr=n_attr)
        cfg = config or IVFConfig(dim=dim)
        if quantize is not None:
            cfg = dataclasses.replace(cfg, quantize=quantize)
        if rerank_factor is not None:
            cfg = dataclasses.replace(cfg, rerank_factor=rerank_factor)
        self.config = cfg
        self.monitor = IndexMonitor(monitor)
        if memory_budget_mb is not None:
            assert memory_budget_mb > 0, memory_budget_mb
        self.memory_budget_mb = memory_budget_mb
        if frame_pool is not None:
            assert memory_budget_mb is not None, \
                "a shared frame pool implies paged mode: pass " \
                "memory_budget_mb"
            assert tenant is not None, \
                "a shared frame pool needs a stable tenant name"
        self._frame_pool = frame_pool
        self.tenant = None if tenant is None else str(tenant)
        self.index = None   # IVFIndex (resident) or PagedIndex (paged)
        self.optimizer: Optional[HybridOptimizer] = None
        self.maintenance_log = []
        # observability (PR 8): this engine's labeled view into the ONE
        # process metrics registry -- the pager, scheduler, and front door
        # all hang their counters off sub-scopes of it, so stats() is a
        # derived view of a single source of truth -- plus the trace ring:
        # the last N QueryTraces and maintenance events, with a slow-query
        # log above `slow_query_ms`.
        # fleet tenants label their scope by NAME (not a fresh instance
        # id): a spilled tenant reopened later lands on the same series,
        # so per-tenant counters stay cumulative across its lifetimes
        if self.tenant is not None:
            self.metrics = obs_metrics.default_registry().scope(
                component="engine", tenant=self.tenant)
        else:
            self.metrics = obs_metrics.default_registry().scope(
                component="engine", inst=str(obs_metrics.next_instance()))
        self.traces = obs_trace.TraceRing(capacity=trace_ring_capacity,
                                          slow_ms=slow_query_ms)
        self._c_queries = self.metrics.counter("queries")
        # per-tenant query-latency histogram (fleet mode only): the SLO
        # layer's burn-rate source (Fleet.health()). Solo engines keep
        # the untimed hot path -- `_h_query_s is None` is one branch
        self._h_query_s = self.metrics.histogram("query_s") \
            if self.tenant is not None else None
        self.scheduler = MaintenanceScheduler(
            self, max_rows_per_step=max_rows_per_step,
            metrics=self.metrics.scope(component="scheduler"))
        # serving front door attached to this engine (if any) -- set by
        # serving.frontdoor.FrontDoor so stats() can surface its counters
        self._frontdoor = None

    @property
    def paged(self) -> bool:
        return self.memory_budget_mb is not None

    # -- lifecycle -----------------------------------------------------------
    @_locked
    def build(self):
        """Initial clustering from the durable tier (mini-batch k-means
        streams from SQLite -- never the full dataset in memory), then
        the split pass: every partition above FRAME_ROWS_MAX rows is
        2-means-split until none is (maintenance.split_to_bar; p_max stays
        where the paged frame scan compiles). With
        quantize="int8" the build also trains the quantizer from the
        store's rows (build_index trains min/max on the same data, so no
        second pass over SQLite) and persists codes + stats durably
        *before* the clustering swap: after a crash at any point the
        codes table is always decode-consistent with the stored qstats.
        """
        if self.paged:
            self.scheduler.record_build(self._build_paged())
            return
        ids, _, vecs = self.store.all_rows()
        attrs = self.store.attributes_for(ids)
        self.index, split = ivf.build_with_split(
            vecs, ids.astype(np.int32), attrs, cfg=self.config)
        self.scheduler.record_build(split)
        self._persist_codes()
        # persist the clustering back to the clustered table
        assign = self._current_assignment()
        self.store.set_partitions(ids, assign[ids], *self._centroid_state())
        self._persist_maintenance_state()
        self._refresh_stats()

    @_locked
    def recover(self):
        """Rebuild device state from SQLite after a crash/restart."""
        if self.paged:
            self._recover_paged()
            return
        ids, parts, vecs = self.store.all_rows()
        attrs = self.store.attributes_for(ids)
        cents, csizes = self.store.centroids()
        if len(cents) == 0:
            # No durable clustering: drop *all* derived state. A stale
            # index/optimizer pair from a previous build must not keep
            # answering (hybrid) queries for a store that no longer backs
            # them.
            self.index = None
            self.optimizer = None
            return
        live = parts >= 0
        # the durable tier stores raw rows; the packed device index (and
        # the code tier) hold metric-normalised ones -- normalise the
        # main-tier rows before packing so recovery reproduces exactly
        # what build() put on device. Pending delta rows stay raw here:
        # the replay upsert below normalises them itself, exactly once,
        # like the live engine's write path did.
        vecs_live = np.asarray(normalize_if_cosine(
            jnp.asarray(vecs[live], jnp.float32), self.config.metric))
        qstats = None
        codes_live = None
        if self.config.quantize == "int8":
            qs = self.store.qstats()
            if qs is not None:
                # codes were persisted at build/upsert time: restore them
                # without re-encoding (the durable tier is authoritative);
                # rows missing a durable code (e.g. written by a pre-
                # quantization engine) are re-encoded from float32
                qstats = quantize.stats_from_arrays(*qs)
                codes_live, found = self.store.codes_for(ids[live])
                if not found.all():
                    codes_live[~found] = quantize.encode_np(
                        qstats, vecs_live[~found])
        packed = ivf.pack_partitions(
            vecs_live, ids[live].astype(np.int32), attrs[live],
            parts[live].astype(np.int64), len(cents),
            pad_to=effective_pad_to(self.config), codes=codes_live)
        vec, vid, vat, val, counts, cod = packed
        code_tier = None if cod is None else jnp.asarray(cod)
        idx = IVFIndex(
            centroids=jnp.asarray(cents), csizes=jnp.asarray(csizes),
            vectors=jnp.asarray(vec), ids=jnp.asarray(vid),
            attrs=jnp.asarray(vat), valid=jnp.asarray(val),
            counts=jnp.asarray(counts),
            delta=DeltaStore.empty(self.config.delta_capacity, self.store.dim,
                                   attrs.shape[1],
                                   quantized=cod is not None),
            base_mean_size=jnp.asarray(max(counts.mean(), 1.0), jnp.float32),
            codes=code_tier,
            qstats=qstats,
            code_norms=None if cod is None else quantize.row_norms(
                qstats, code_tier),
            drift=jnp.zeros((len(cents),), jnp.float32),
            config=self.config)
        # restore the monitor's maintenance signals (drift accumulators +
        # rebuild baseline) persisted alongside the clustering -- a
        # recovered index resumes maintenance where the crash left off
        mstate = self.store.maintenance_state()
        if mstate is not None:
            base, drift = mstate
            if drift.shape[0] == len(cents):
                idx = dataclasses.replace(
                    idx, drift=jnp.asarray(drift, jnp.float32),
                    base_mean_size=jnp.asarray(base, jnp.float32))
        self.index = idx
        # replay delta rows (partition -1); upsert re-encodes them into
        # the delta's code block from the same stats, deterministically.
        # Replay in capacity-sized chunks with a flush in between -- the
        # store may hold more pending rows than the delta can seat (the
        # delta scatter would silently drop the overflow otherwise).
        if (~live).any():
            rv = vecs[~live]
            ri = ids[~live].astype(np.int32)
            ra = attrs[~live]
            cap = self.config.delta_capacity
            for s in range(0, len(rv), cap):
                e = min(s + cap, len(rv))
                if delta_ops.delta_free_slots(self.index) < e - s:
                    self.maintain(force="flush")
                self.index = delta_ops.upsert(
                    self.index, jnp.asarray(rv[s:e]), jnp.asarray(ri[s:e]),
                    jnp.asarray(ra[s:e]))
        self._refresh_stats()

    # -- writes ---------------------------------------------------------------
    @_locked
    def upsert(self, ids: np.ndarray, vecs: np.ndarray,
               attrs: Optional[np.ndarray] = None):
        n_attr = self.store.n_attr
        attrs = np.zeros((len(ids), n_attr), np.float32) if attrs is None \
            else attrs
        old_main = None
        if self.paged and self.index is not None:
            # paged mode has no resident main-tier ids to tombstone: note
            # which partitions hold stale copies BEFORE the durable upsert
            # moves them, then invalidate those frames. Unique ids only --
            # a duplicated id in the batch still removes one durable row,
            # so it must decrement its partition's count exactly once.
            old = self.store.partitions_for(np.unique(np.asarray(ids)))
            old_main = old[old >= 0]
        self.store.upsert(ids, vecs, attrs, partition_id=-1)
        if self.index is None:
            return
        if self.paged:
            if old_main is not None and old_main.size:
                self.index.cache.invalidate(np.unique(old_main))
                self.index.counts = self.index.counts - np.bincount(
                    old_main, minlength=self.index.k)
            if delta_ops.delta_free_slots(self.index) < len(ids):
                self.maintain(force="flush")
            self.index.delta = delta_ops.delta_only_upsert(
                self.index.delta, jnp.asarray(vecs, jnp.float32),
                jnp.asarray(ids, jnp.int32), jnp.asarray(attrs, jnp.float32),
                self.config.metric, self.index.qstats)
            return
        if delta_ops.delta_free_slots(self.index) < len(ids):
            self.maintain(force="flush")
        self.index = delta_ops.upsert(
            self.index, jnp.asarray(vecs, jnp.float32),
            jnp.asarray(ids, jnp.int32), jnp.asarray(attrs, jnp.float32))
        # NB: no durable code write here -- pending (partition -1) rows are
        # replayed through delta_ops.upsert on recover(), which re-encodes
        # them deterministically; their durable codes are first written by
        # the next build()/rebuild's _persist_codes.

    @_locked
    def delete(self, ids: np.ndarray):
        old_main = None
        if self.paged and self.index is not None:
            # unique ids: one durable row removed -> one count decrement
            old = self.store.partitions_for(np.unique(np.asarray(ids)))
            old_main = old[old >= 0]
        self.store.delete(ids)
        if self.index is None:
            return
        if self.paged:
            if old_main is not None and old_main.size:
                self.index.cache.invalidate(np.unique(old_main))
                self.index.counts = self.index.counts - np.bincount(
                    old_main, minlength=self.index.k)
            self.index.delta = delta_ops.delta_only_delete(
                self.index.delta, jnp.asarray(ids, jnp.int32))
            return
        self.index = delta_ops.delete(self.index,
                                      jnp.asarray(ids, jnp.int32))

    def session(self) -> WriteSession:
        """Open a batched write session: buffered upserts/deletes commit
        as one SQLite transaction + one delta-encode batch + one deferred
        pager-invalidation pass when the `with` block exits cleanly."""
        return WriteSession(self)

    @_locked
    def _commit_session(self, ops: List[tuple]):
        """Apply a session's coalesced net effect atomically (single
        writer, paper §3.6). Per-id last-write-wins: an upsert overridden
        by a later delete never lands, and vice versa -- matching what
        sequential upsert()/delete() calls would have left behind."""
        # vectorized last-write-wins coalescing: concatenate every op's
        # ids in order and keep each id's LAST occurrence (reverse +
        # np.unique-first-hit) -- no per-row Python loop, so a bulk-load
        # session coalesces at array speed
        id_chunks, kind_chunks, row_chunks = [], [], []
        vec_chunks, attr_chunks = [], []
        row_off = 0
        for op in ops:
            if op[0] == "up":
                _, ids, vecs, attrs = op
                row_chunks.append(row_off + np.arange(len(ids)))
                vec_chunks.append(vecs)
                attr_chunks.append(attrs)
                row_off += len(ids)
                kind_chunks.append(np.ones(len(ids), bool))
            else:
                ids = op[1]
                row_chunks.append(np.full(len(ids), -1))
                kind_chunks.append(np.zeros(len(ids), bool))
            id_chunks.append(ids)
        ids_all = np.concatenate(id_chunks)
        kind_all = np.concatenate(kind_chunks)       # True = upsert
        rows_all = np.concatenate(row_chunks)
        _, first_rev = np.unique(ids_all[::-1], return_index=True)
        last = len(ids_all) - 1 - first_rev          # last op per id
        is_up = kind_all[last]
        up_ids = ids_all[last[is_up]]
        del_ids = ids_all[last[~is_up]]
        vecs_all = np.concatenate(vec_chunks) if vec_chunks \
            else np.zeros((0, self.store.dim), np.float32)
        attrs_all = np.concatenate(attr_chunks) if attr_chunks \
            else np.zeros((0, self.store.n_attr), np.float32)
        up_vecs = vecs_all[rows_all[last[is_up]]]
        up_attrs = attrs_all[rows_all[last[is_up]]]
        touched = np.concatenate([up_ids, del_ids])
        old_main = None
        if self.paged and self.index is not None:
            # partitions holding stale copies, noted BEFORE the durable
            # write moves/removes them -- invalidated once, at commit
            old = self.store.partitions_for(touched)
            old_main = old[old >= 0]
        with self.store.transaction():    # ONE durable transaction
            if len(up_ids):
                self.store.upsert(up_ids, up_vecs, up_attrs, partition_id=-1)
            if len(del_ids):
                self.store.delete(del_ids)
        if self.index is None:
            return
        if self.paged:
            if old_main is not None and old_main.size:
                # the single deferred invalidation pass
                self.index.cache.invalidate(np.unique(old_main))
                self.index.counts = self.index.counts - np.bincount(
                    old_main, minlength=self.index.k)
            if len(del_ids):
                self.index.delta = delta_ops.delta_only_delete(
                    self.index.delta, jnp.asarray(del_ids, jnp.int32))
        elif len(del_ids):
            self.index = delta_ops.delete(self.index,
                                          jnp.asarray(del_ids, jnp.int32))
        # one delta-encode batch: a single append call quantizes every
        # new row in one encode (chunked only past the delta capacity)
        self._delta_append(up_ids, up_vecs, up_attrs)

    def _delta_append(self, ids: np.ndarray, vecs: np.ndarray,
                      attrs: np.ndarray):
        """Append rows to the device delta in capacity-sized chunks,
        flushing when full -- the shared tail of upsert and session
        commit in both modes."""
        cap = self.config.delta_capacity
        for s in range(0, len(ids), cap):
            e = min(s + cap, len(ids))
            if delta_ops.delta_free_slots(self.index) < e - s:
                self.maintain(force="flush")
            if self.paged:
                self.index.delta = delta_ops.delta_only_upsert(
                    self.index.delta, jnp.asarray(vecs[s:e]),
                    jnp.asarray(ids[s:e].astype(np.int32)),
                    jnp.asarray(attrs[s:e]),
                    self.config.metric, self.index.qstats)
            else:
                self.index = delta_ops.upsert(
                    self.index, jnp.asarray(vecs[s:e]),
                    jnp.asarray(ids[s:e].astype(np.int32)),
                    jnp.asarray(attrs[s:e]))

    # -- maintenance ----------------------------------------------------------
    @_locked
    def maintain(self, force: Optional[str] = None,
                 until_idle: bool = False,
                 max_steps: Optional[int] = None):
        """Run maintenance.

        `maintain(until_idle=True)` is the steady-state path (PR 5): the
        budgeted scheduler drains the monitor's work queue -- partial
        delta flushes, 2-means splits of oversized partitions, merges of
        underfull siblings, local reclustering of drifted neighbourhoods
        -- in `max_rows_per_step` quanta, never a full rebuild. Returns
        the list of StepReports executed.

        `maintain(force="flush"|"rebuild")` and the legacy no-arg form
        (single monitor verdict) are kept for whole-index maintenance;
        `full_rebuild` remains the escape hatch, not the steady state.
        """
        if self.index is None:
            return [] if until_idle else None
        if until_idle:
            assert force is None, "until_idle excludes force"
            return self.scheduler.drain(max_steps=max_steps)
        if self.paged:
            return self._maintain_paged(force)
        health = self.monitor.check(self.index)
        action = force or health.action
        if action == "flush":
            self.index, stats = maintenance.flush_delta(self.index)
            self.maintenance_log.append(stats)
            self.store.update_centroids(np.asarray(self.index.centroids),
                                        np.asarray(self.index.csizes))
            self._persist_maintenance_state()
            return "flush"
        if action == "rebuild":
            self.index, stats = maintenance.full_rebuild(self.index)
            self.maintenance_log.append(stats)
            # a rebuild retrains the quantizer -> every code changes;
            # persist codes+stats before the clustering swap (same crash
            # ordering as build())
            self._persist_codes()
            ids, _, _ = self.store.all_rows()
            assign = self._current_assignment()
            self.store.set_partitions(
                ids, assign[ids], *self._centroid_state())
            self._persist_maintenance_state()
            self._refresh_stats()
            return "rebuild"
        return None

    @_locked
    def maintain_step(self) -> Optional[StepReport]:
        """One bounded maintenance quantum (<= max_rows_per_step rows):
        pops the highest-priority item off the monitor's work queue and
        executes it. Queries issued between steps see a consistent mixed
        old/new partition state. Returns None when the index is idle."""
        if self.index is None:
            return None
        return self.scheduler.step()

    def _execute_work_item(self, item: WorkItem,
                           max_rows: int) -> Optional[StepReport]:
        """Scheduler callback: run one work item. Returns None when the
        item plans to a no-op (the scheduler then skips it)."""
        if item.action == "flush":
            return self._flush_step(max_rows)
        if item.action == "repack":
            # device-only tombstone repack: zero durable I/O by contract
            assert not self.paged, "paged frames carry no tombstones"
            self.index = maintenance.repack_partition(
                self.index, item.pids[0])
            return StepReport("repack", item.pids, item.rows, 0)
        idx = self.index
        cents = np.asarray(idx.centroids)
        csz = np.asarray(idx.csizes)
        counts = np.asarray(idx.counts)
        fetch = self._fetch_rows_paged if self.paged \
            else self._fetch_rows_resident
        n_local = self.monitor.cfg.repair_neighbors
        if item.action == "split":
            plan = maintenance.plan_split(
                cents, csz, counts, item.pids[0], fetch,
                row_budget=max_rows,
                n_local=self.monitor.cfg.split_neighbors)
        elif item.action == "merge":
            plan = maintenance.plan_merge(
                cents, csz, counts, item.pids[0], item.pids[1], fetch)
        else:
            assert item.action == "recluster", item.action
            plan = maintenance.plan_local_recluster(
                cents, csz, counts, item.pids[0], fetch,
                row_budget=max_rows, n_local=n_local)
        if plan is None:
            return None
        return self._apply_repair(plan)

    def _flush_step(self, max_rows: int) -> StepReport:
        """A (possibly partial) delta flush as one scheduler quantum.

        Unlike the legacy device-only resident flush, the scheduler's
        flush also moves the rows *durably* (exactly what the paged flush
        does): later repairs then never pay "promotion" writes for rows
        still parked in the pending -1 partition, repair write I/O is
        pure reassignment cost, and the resident and paged engines leave
        identical durable states behind every step."""
        if self.paged:
            stats = self._paged_flush(max_rows=max_rows)
            if stats is None:
                stats = maintenance.MaintenanceStats(
                    "incremental", 0, 0, 0, self.index.cache.p_max,
                    self.index.cache.p_max)
            return StepReport("flush", (), stats.rows_moved,
                              stats.bytes_written)
        idx = self.index
        d = idx.delta
        live = np.nonzero(np.asarray(d.valid))[0]
        if max_rows is not None and live.size > max_rows:
            live = live[:max_rows]
        dids = np.asarray(d.ids)[live]
        dx = np.asarray(d.vectors)[live]      # metric-normalised
        dcod = np.asarray(d.codes)[live] if d.codes is not None else None
        assign = maintenance.assign_nearest_centroid(dx, idx.centroids) \
            if live.size else np.zeros((0,), np.int64)
        self.index, stats = maintenance.flush_delta(
            self.index, max_rows=max_rows, assign=assign)
        self.maintenance_log.append(stats)
        with self.store.transaction():        # one atomic durable flush
            if live.size and dcod is not None:
                # codes first (crash contract: byte-stable either way)
                self.store.set_code_tier(
                    dids, dcod,
                    *quantize.stats_to_arrays(self.index.qstats))
            # row moves + TOUCHED centroid rewrites only -- durable I/O
            # matches the stats accounting (never O(k) per quantum)
            touched = np.unique(assign)
            self.store.apply_repair(
                dids, assign, touched,
                np.asarray(self.index.centroids)[touched],
                np.asarray(self.index.csizes)[touched])
            self._persist_maintenance_state()
        return StepReport("flush", (), stats.rows_moved,
                          stats.bytes_written)

    # -- local repair (split / merge / recluster) -----------------------------
    def _fetch_rows_resident(self, pids):
        """RowFetch over the packed device layout (rows sorted by id, the
        same order SQLite's clustered scan yields -- bit-parity with the
        paged planner)."""
        idx = self.index
        vid = np.asarray(idx.ids)
        val = np.asarray(idx.valid)
        vec = np.asarray(idx.vectors)
        vat = np.asarray(idx.attrs)
        cod = np.asarray(idx.codes) if idx.codes is not None else None
        out = {}
        for p in pids:
            sel = np.nonzero(val[p])[0]
            ids = vid[p][sel]
            order = np.argsort(ids, kind="stable")
            out[int(p)] = maintenance.RowBlock(
                ids=ids[order].astype(np.int32),
                vecs=vec[p][sel][order],
                attrs=vat[p][sel][order],
                codes=None if cod is None else cod[p][sel][order])
        return out

    def _fetch_rows_paged(self, pids):
        """RowFetch streaming the neighbourhood from SQLite in ONE
        batched read (VectorStore.scan_partitions); rows arrive sorted by
        asset id and are metric-normalised exactly like the pager's fault
        path, so the paged planner sees the same bytes the resident
        planner reads from the packed layout."""
        idx = self.index
        counts = np.asarray(idx.counts)
        pids = [int(p) for p in pids]
        p_max = int(max(max(counts[p] for p in pids), 1))
        blocks = self.store.scan_partitions(pids, p_max, with_vecs=True)
        vecs = np.asarray(normalize_if_cosine(
            jnp.asarray(blocks.vecs, jnp.float32), self.config.metric))
        out = {}
        for j, p in enumerate(pids):
            m = int(blocks.valid[j].sum())
            out[p] = maintenance.RowBlock(
                ids=blocks.ids[j, :m].astype(np.int32),
                vecs=vecs[j, :m])
        return out

    def _apply_repair(self, plan) -> StepReport:
        """Persist + apply one RepairPlan. Durability ordering (the crash
        contract pinned by tests/test_maintenance.py): (1) quantized
        codes for the touched rows land first -- byte-stable re-encode
        under the *existing* quantizer, so they are valid under either
        clustering state; (2) the row moves + touched-centroid rewrites
        commit as ONE transaction (VectorStore.apply_repair); a crash
        between the two serves the pre-repair clustering bit-identically.
        Only then does device/paged state update."""
        idx = self.index
        quantized = idx.quantized if self.paged else idx.codes is not None
        qstats = idx.qstats
        code_bytes = 0
        if quantized and plan.rows:
            _, found = self.store.codes_for(plan.row_ids)
            if not found.all():
                missing = ~found
                enc = quantize.encode_np(qstats, plan.row_vecs[missing])
                self.store.set_code_tier(
                    plan.row_ids[missing], enc,
                    *quantize.stats_to_arrays(qstats))
                code_bytes = int(missing.sum()) * self.store.dim
        # -- atomic repair transaction: only durably-moved rows get
        # UPDATEs, only touched partitions get centroid rewrites ---------
        old_pid = self.store.partitions_for(plan.row_ids)
        movedm = old_pid != plan.assign
        k = idx.k
        cents = np.array(idx.centroids)
        csz = np.array(idx.csizes, np.float32)
        if plan.k_after > k:
            cents = np.pad(cents, [(0, plan.k_after - k), (0, 0)])
            csz = np.pad(csz, (0, plan.k_after - k))
        cents[plan.pids] = plan.centroids
        csz[plan.pids] = plan.csizes
        self.store.apply_repair(
            plan.row_ids[movedm], plan.assign[movedm], plan.pids,
            plan.centroids, plan.csizes)
        # -- device / paged state ----------------------------------------
        # write accounting counts the durably-moved rows (can exceed the
        # plan's device moves: rows promoted out of the pending -1
        # partition) plus the touched centroids' rewrite -- I/O scales
        # with the repair neighbourhood, never the collection. A moved
        # row does NOT rewrite its code (the codes table is keyed by
        # asset id and codes are byte-stable under the existing
        # quantizer) -- only backfilled codes count; a full rebuild, by
        # contrast, retrains and rewrites every code.
        n_attr = self.store.n_attr
        row_b = 4 * self.store.dim + 4 + 4 * n_attr + 1
        bytes_written = int(movedm.sum()) * row_b \
            + len(plan.pids) * self.store.dim * 4 + code_bytes
        p_max_before = idx.p_max
        if self.paged:
            self._apply_repair_paged(plan, cents, csz)
        else:
            self.index = maintenance.apply_plan(self.index, plan)
        stats = maintenance.MaintenanceStats(
            kind=plan.kind, rows_moved=int(movedm.sum()),
            partitions_touched=len(plan.pids),
            bytes_written=bytes_written,
            p_max_before=p_max_before, p_max_after=self.index.p_max)
        self.maintenance_log.append(stats)
        self._persist_maintenance_state()
        return StepReport(plan.kind, tuple(int(p) for p in plan.pids),
                          plan.rows, bytes_written)

    def _apply_repair_paged(self, plan, cents: np.ndarray,
                            csz: np.ndarray):
        """Paged-mode apply: the durable tier is the scan tier, so the
        repair is already applied -- update resident metadata (centroids,
        counts, drift), invalidate exactly the touched frames, and grow
        the frame geometry if a merge outgrew p_max."""
        idx = self.index
        k = idx.k
        counts = np.array(idx.counts)
        drift = np.array(idx.drift, np.float32) if idx.drift is not None \
            else np.zeros((k,), np.float32)
        if plan.k_after > k:
            counts = np.pad(counts, (0, plan.k_after - k))
            drift = np.pad(drift, (0, plan.k_after - k))
        sizes = np.asarray([(plan.assign == p).sum() for p in plan.pids])
        counts[plan.pids] = sizes
        drift[plan.pids] = 0.0
        idx.centroids = jnp.asarray(cents)
        idx.csizes = jnp.asarray(csz, jnp.float32)
        idx.counts = counts
        idx.drift = drift
        cache = idx.cache
        cache.invalidate([int(p) for p in plan.pids])
        pad = effective_pad_to(self.config)
        new_p_max = int(max(sizes.max() if sizes.size else 1, 1))
        new_p_max = max(cache.p_max, -(-new_p_max // pad) * pad)
        if new_p_max > cache.p_max:
            cache.resize(new_p_max)

    # -- queries --------------------------------------------------------------
    def query(self, queries: np.ndarray, spec: Optional[QuerySpec] = None,
              *, trace: bool = False) -> ResultSet:
        """THE query entry point: execute a declarative QuerySpec.

        `trace=True` activates a per-query QueryTrace for this call: every
        stage the query crosses (stage-in, planner, dispatch, probe,
        pager, fused scan, rerank, merge) records a span; the call then
        waits for its result and copies it to the host (`device_wait`,
        `fetch`), so the trace covers the whole query before it lands in
        the engine's ring (`self.traces`, incl. the slow-query log) and
        rides back on `result.trace`. With `trace=False` (default) no
        span is allocated -- unless an OUTER trace is already active on
        this thread (the front door's shared fused-call trace), in which
        case the layers keep recording into that one. Under a collecting
        JAX profiler every stage is also a `micronn.<stage>` host event,
        traced or not (obs/trace.py).

        The spec alone routes execution -- resident fused scan, paged
        frame-pool streaming, or the hybrid pre/post-filter choice (the
        optimizer resolves `hybrid='auto'` into a concrete pre/post spec,
        both arms still spec-routed) -- and, being frozen + hashable, it
        is also the executor's jit cache key: issuing an equal spec twice
        never retraces. Returns a ResultSet (ids + exact-f32 scores,
        optional gathered attrs when `spec.with_attrs()`).

        Thread-safety: queries never take the engine write mutex. The
        index reference is read ONCE -- resident repairs rebind
        `self.index` to a new immutable pytree, so an in-flight query
        keeps scanning its consistent snapshot; paged execution is
        protected by the PartitionCache RLock (deferred pinned-frame
        invalidation) and the store's WAL snapshot read connection."""
        # flight-recorder hook (PR 10): recording-off cost is this one
        # global load + branch (plus the fleet-mode SLO histogram
        # check), preserving the <=3% off-path gate in bench_obs
        rec = obs_recorder._ACTIVE
        res = self.query_unrecorded(queries, spec, trace=trace)
        if rec is not None:
            rec.record(obs_recorder.SITE_ENGINE, self.tenant, queries,
                       spec, result=res)
        return res

    def query_unrecorded(self, queries: np.ndarray,
                         spec: Optional[QuerySpec] = None, *,
                         trace: bool = False) -> ResultSet:
        """`query()` without the flight-recorder hook: for callers that
        captured the request themselves (the front door records at
        admission, so its solo dispatch must not record it again)."""
        if self._h_query_s is None:
            if not (trace and obs_trace.enabled()):
                return self._query_inner(queries, spec)
            return self._query_traced(queries, spec)
        t0 = time.perf_counter()
        if not (trace and obs_trace.enabled()):
            res = self._query_inner(queries, spec)
        else:
            res = self._query_traced(queries, spec)
        self._h_query_s.observe(time.perf_counter() - t0)
        return res

    def _query_traced(self, queries: np.ndarray,
                      spec: Optional[QuerySpec]) -> ResultSet:
        tr = obs_trace.QueryTrace(
            mode="paged" if self.paged else "resident")
        with obs_trace.activate(tr):
            res = self._query_inner(queries, spec)
        res.trace = tr
        # the result's own stages (device_wait, fetch) complete the
        # trace; the caller's to_numpy() then reuses the host copy
        res.to_numpy()
        tr.finish()
        tr.result = res
        self.traces.append(tr)
        return res

    def explain(self, queries: np.ndarray,
                spec: Optional[QuerySpec] = None) -> obs_trace.QueryTrace:
        """Execute the query traced and return the QueryTrace (the result
        rides on `trace.result`): the per-stage wall-time + work-counter
        breakdown for this exact spec on this exact engine mode."""
        return self.query(queries, spec, trace=True).trace

    def _query_inner(self, queries: np.ndarray,
                     spec: Optional[QuerySpec]) -> ResultSet:
        idx, optimizer = self.index, self.optimizer
        assert idx is not None, "build() or recover() first"
        spec = QuerySpec() if spec is None else spec
        with obs_trace.stage(obs_trace.STAGE_STAGE_IN, obs_trace.current()):
            q = executor.as_query_batch(queries)
            self._c_queries.inc()
        spec = self._resolve_spec_traced(idx, optimizer, spec,
                                         int(q.shape[0]))
        res = executor.run(idx, q, spec)
        if spec.gather_attrs and self.store.n_attr:
            res.attrs = self._gather_attrs(np.asarray(res.ids))
        return res

    def query_batched(self, chunks: List[np.ndarray],
                      spec: Optional[QuerySpec] = None) -> List[ResultSet]:
        """Cross-request micro-batch entry point (the serving front
        door's fused call): per-caller query chunks sharing ONE spec are
        concatenated, executed as a single bucketed run -- one fused
        scan, one jit cache entry -- and split back into per-caller
        ResultSets. Results are bit-identical to issuing each chunk
        through `query()` alone: the spec resolves once (the optimizer
        rewrite depends only on spec + stats, not on the query vectors)
        and `executor.run_coalesced` slices the batch mechanically."""
        idx, optimizer = self.index, self.optimizer
        assert idx is not None, "build() or recover() first"
        spec = QuerySpec() if spec is None else spec
        self._c_queries.inc(len(chunks))
        spec = self._resolve_spec_traced(
            idx, optimizer, spec, sum(int(np.atleast_2d(c).shape[0])
                                      for c in chunks))
        results = executor.run_coalesced(idx, chunks, spec)
        if spec.gather_attrs and self.store.n_attr:
            for rs in results:
                rs.attrs = self._gather_attrs(np.asarray(rs.ids))
        return results

    def _resolve_spec_traced(self, idx, optimizer, spec: QuerySpec,
                             n_queries: int) -> QuerySpec:
        """Spec resolution as the `plan` stage: records the hybrid
        pre/post decision and the resolved shape when a trace is active
        (the profiler sink alone takes no counters)."""
        tr = obs_trace.current()
        with obs_trace.stage(obs_trace.STAGE_PLAN, tr) as st:
            spec = self._resolve_spec(idx, optimizer, spec)
            if tr is not None:
                st.set(kind=spec.kind, k=int(spec.k),
                       n_probe=int(spec.n_probe), hybrid=spec.hybrid,
                       predicate=spec.predicate is not None)
        if tr is not None:
            tr.spec = spec
            tr.n_queries += n_queries
        return spec

    def _resolve_spec(self, idx, optimizer, spec: QuerySpec) -> QuerySpec:
        """Resolve the hybrid pre/post choice (and/or size the prefilter
        cap) from the selectivity estimate (paper Eqs. 1-3). Opaque
        hand-written filter callables skip the optimizer (nothing to
        estimate) and run as fused post-filters."""
        if not self.paged and spec.predicate_tree is not None \
                and spec.kind == "ann" \
                and (spec.hybrid == "auto"
                     or (spec.hybrid == "pre" and spec.cap is None)):
            spec, _ = optimizer.plan_spec(idx, spec)
        return spec

    def search(self, queries: np.ndarray, k: int = 100, n_probe: int = 8,
               predicate: Optional[Node] = None, exact: bool = False,
               batch_mqo: Optional[bool] = None,
               backend: Optional[str] = None) -> ResultSet:
        """Deprecation shim: kwargs -> QuerySpec -> query(). Kept so
        existing callers survive the API redesign; new code should build
        specs (`Q.knn(...)...`) and call `query()` directly. `batch_mqo`
        is dead -- a batched ANN spec *is* the MQO shared scan (same
        union + selection mask) -- and warns. One deliberate semantic
        fix vs the old engine: `exact=True` + `predicate` now runs the
        filtered exact oracle (the old code silently ignored `exact`
        and let the optimizer answer approximately)."""
        if batch_mqo is not None:
            warnings.warn(
                "MicroNN.search(batch_mqo=...) is deprecated and has no "
                "effect: a batched ANN QuerySpec is the MQO shared scan; "
                "use MicroNN.query(vecs, Q.knn(...))",
                DeprecationWarning, stacklevel=2)
        spec = Q.exact(k=k) if exact else Q.knn(k=k, n_probe=n_probe)
        if predicate is not None:
            spec = spec.where(predicate)
        if backend is not None:
            spec = spec.backend(backend)
        return self.query(queries, spec)

    def _gather_attrs(self, ids: np.ndarray) -> np.ndarray:
        """[Q, k] result ids -> [Q, k, n_attr] attribute rows from the
        durable tier (zeros where INVALID)."""
        Qn, k = ids.shape
        flat = ids.reshape(-1)
        got = flat != INVALID_ID
        out = np.zeros((Qn * k, self.store.n_attr), np.float32)
        if got.any():
            out[got] = self.store.attributes_for(flat[got])
        return out.reshape(Qn, k, self.store.n_attr)

    # -- observability --------------------------------------------------------
    def stats(self) -> dict:
        """Operational counters with UNIFORM keys in both modes: pager
        hits/misses/evictions, resident scan-tier bytes, and the query
        executor's compile-cache counters (`trace_count`,
        `compile_cache_size` -- pinned against QuerySpecs, so a stable
        trace_count across a query stream proves the spec cache is
        hitting). In resident mode the pager counters are zero and
        `resident_bytes` is what search must keep in memory (f32 tier +
        codes when quantized); in paged mode it is the preallocated frame
        pool (<= the byte budget by construction). Benchmarks and tests
        assert on these counters instead of re-deriving them.

        PR 7 adds the serving/maintenance-concurrency counters, uniform
        in both modes: `scheduler_depth` (pending maintenance work
        items), `daemon_alive`/`daemon_steps` (the background scheduler
        thread's liveness and executed quanta), and `frontdoor` (the
        attached serving front door's admission/coalescing/latency
        counters -- queued, coalesced, batches, p50/p99 queue-wait and
        execute times; zeroed when no front door is attached).

        PR 8 makes every value here a derived view of the ONE process
        metrics registry (obs.metrics) -- same keys, same plain-int
        values -- and adds `scheduler`: the maintenance scheduler's
        wakeup / backoff / rows-moved / per-action telemetry."""
        from ..serving import frontdoor as frontdoor_mod
        sched = self.scheduler
        fd = self._frontdoor
        out = {"paged": self.paged, "hits": 0, "misses": 0, "evictions": 0,
               "resident_bytes": 0, "budget_bytes": None,
               "trace_count": executor.trace_count(),
               "compile_cache_size": executor.compile_cache_size(),
               "scheduler_depth": sched.queue_depth(),
               "daemon_alive": sched.daemon_alive,
               "daemon_steps": sched.daemon_steps,
               "scheduler": sched.stats(),
               "frontdoor": fd.stats() if fd is not None
               else frontdoor_mod.empty_stats()}
        idx = self.index
        if idx is None:
            return out
        if self.paged:
            out.update(idx.cache.stats())
            return out
        # same components the paged pool counts: payload(s) + ids + valid
        # + attrs, so the two modes' resident_bytes are comparable
        resident = int(idx.vectors.nbytes + idx.ids.nbytes
                       + idx.valid.nbytes + idx.attrs.nbytes)
        if idx.codes is not None:
            resident += int(idx.codes.nbytes)
        out["resident_bytes"] = resident
        return out

    # -- paged lifecycle (memory_budget_mb mode) ------------------------------
    def _build_paged(self):
        """Cluster + persist durably, then attach a paged view -- fully
        STREAMED from SQLite, so host memory stays O(batch + ids), never
        O(collection): the quantizer trains via train_from_store, codes
        encode batch-by-batch, mini-batch k-means samples from disk, the
        final assignment streams the clustered scan, and the generation
        swap moves partition ids with keyed UPDATEs instead of
        re-materialising the blobs. Same crash ordering as build():
        codes + qstats land before the clustering swap. The split pass
        reads one oversized partition at a time back from SQLite and
        folds into the same swap. Returns the split pass's stats."""
        cfg = self.config
        store = self.store
        batch = max(cfg.minibatch_size, 4096)
        ids = store.iter_asset_ids()
        if cfg.quantize == "int8":
            qstats = quantize.train_from_store(store, cfg.metric, batch)

            def _code_chunks():
                off = 0
                for b in store.iter_batches(batch):
                    bn = np.asarray(normalize_if_cosine(
                        jnp.asarray(b, jnp.float32), cfg.metric))
                    yield (ids[off:off + len(bn)],
                           quantize.encode_np(qstats, bn))
                    off += len(bn)
            # one transaction for the whole stream: a crash never leaves
            # old codes paired with the retrained stats
            store.set_code_tier_streaming(
                _code_chunks(), *quantize.stats_to_arrays(qstats))
        km = kmeans.MiniBatchKMeans(cfg)
        km.fit(lambda size, rng: store.sample(size, rng), len(ids))
        assign = km.assign(store.iter_batches(batch))
        cents, csz, assign, split = maintenance.split_to_bar(
            km.centroids, km.counts, ids, assign,
            lambda pos: store.vectors_for(ids[pos])[0], cfg.metric)
        store.reassign_partitions(ids, assign, cents, csz)
        self._attach_paged()
        # a fresh clustering resets the maintenance signals -- write them
        # so a later recover() does not restore a stale pre-build state
        self._persist_maintenance_state()
        return split

    def _attach_paged(self):
        """Build the PagedIndex view from durable metadata only: centroids,
        per-partition counts, quantizer stats, and an empty frame pool
        sized to the byte budget."""
        cfg = self.config
        cents, csizes = self.store.centroids()
        if len(cents) == 0:
            self.index = None
            self.optimizer = None
            return
        counts = self.store.partition_counts(len(cents))
        qstats, payload = None, "f32"
        if cfg.quantize == "int8":
            qs = self.store.qstats()
            if qs is not None:
                qstats = quantize.stats_from_arrays(*qs)
                payload = "int8"
        pad = effective_pad_to(cfg)
        p_max = int(max(counts.max() if len(counts) else 0, 1))
        p_max = max(pad, -(-p_max // pad) * pad)
        old_cache = self.index.cache \
            if isinstance(self.index, PagedIndex) else None
        cache = pager.PartitionCache(
            self.store, p_max=p_max,
            budget_bytes=int(self.memory_budget_mb * 2 ** 20),
            payload=payload, metric=cfg.metric, qstats=qstats,
            with_attrs=self.store.n_attr > 0,
            metrics=self.metrics.scope(component="pager"),
            pool=self._frame_pool, tenant=self.tenant)
        if old_cache is not None:   # counters are cumulative across rebuilds
            cache.hits, cache.misses, cache.evictions = \
                old_cache.hits, old_cache.misses, old_cache.evictions
        nonempty = counts[counts > 0]
        self.index = PagedIndex(
            centroids=jnp.asarray(cents),
            csizes=jnp.asarray(csizes, jnp.float32),
            counts=counts,
            delta=DeltaStore.empty(cfg.delta_capacity, self.store.dim,
                                   self.store.n_attr,
                                   quantized=payload == "int8"),
            cache=cache,
            base_mean_size=float(nonempty.mean()) if nonempty.size else 1.0,
            qstats=qstats,
            drift=np.zeros((len(cents),), np.float32),
            config=cfg)
        self.optimizer = None

    def _recover_paged(self):
        """Paged recovery restores only metadata + centroids (plus the
        pending delta rows); partitions fault in lazily on first probe."""
        self._attach_paged()
        if self.index is None:
            return
        mstate = self.store.maintenance_state()
        if mstate is not None:
            base, drift = mstate
            if drift.shape[0] == self.index.k:
                self.index.drift = np.asarray(drift, np.float32)
                self.index.base_mean_size = float(base)
        pids, pvecs = self.store.scan_partition(-1)
        if not len(pids):
            return
        attrs = self.store.attributes_for(pids)
        cap = self.config.delta_capacity
        for s in range(0, len(pids), cap):
            e = min(s + cap, len(pids))
            free = self.index.delta.capacity - int(self.index.delta.count)
            if free < e - s:
                self.maintain(force="flush")
            self.index.delta = delta_ops.delta_only_upsert(
                self.index.delta, jnp.asarray(pvecs[s:e], jnp.float32),
                jnp.asarray(pids[s:e].astype(np.int32)),
                jnp.asarray(attrs[s:e], jnp.float32),
                self.config.metric, self.index.qstats)

    def _maintain_paged(self, force: Optional[str]) -> Optional[str]:
        idx = self.index
        mcfg = self.monitor.cfg
        action = force
        if action is None:
            counts = np.asarray(idx.counts)
            nonempty = counts[counts > 0]
            mean_size = float(nonempty.mean()) if nonempty.size else 0.0
            growth = mean_size / max(idx.base_mean_size, 1.0) - 1.0
            if growth >= mcfg.growth_rebuild_threshold:
                action = "rebuild"
            elif int(idx.delta.count) >= \
                    mcfg.delta_flush_fraction * idx.delta.capacity:
                action = "flush"
        if action == "flush":
            self._paged_flush()
            return "flush"
        if action == "rebuild":
            # full re-cluster straight from the durable tier (pending rows
            # included); _attach_paged re-sizes the pool and drops every
            # frame, which IS the rebuild's cache invalidation
            n_rows = self.store.count()
            self._build_paged()
            row_b = 4 * self.store.dim + 4 + 4 * self.store.n_attr + 1 \
                + (self.store.dim if self.config.quantize == "int8" else 0)
            self.maintenance_log.append(maintenance.MaintenanceStats(
                kind="full", rows_moved=n_rows,
                partitions_touched=self.index.k,
                # a paged rebuild rewrites every row's partition id, its
                # codes, and the centroid generation -- same flash-wear
                # accounting as the resident full_rebuild
                bytes_written=n_rows * row_b
                + self.index.k * self.store.dim * 4,
                p_max_before=idx.cache.p_max,
                p_max_after=self.index.cache.p_max))
            return "rebuild"
        return None

    def _paged_flush(self, max_rows: Optional[int] = None):
        """Incremental paged flush: move live delta rows into their nearest
        partitions *durably* (the clustered SQLite table is the scan tier
        here, so unlike resident flush the partition ids must move on
        disk), write their codes, update centroids by the running-mean
        rule, and invalidate the touched partitions' frames. `max_rows`
        bounds the work quantum: the rest stays searchable in the delta.
        Returns the MaintenanceStats of the flush (None if no live rows)."""
        idx = self.index
        d = idx.delta
        quantized = idx.quantized
        live = np.nonzero(np.asarray(d.valid))[0]
        deferred = np.zeros((0,), np.int64)
        if max_rows is not None and live.size > max_rows:
            live, deferred = live[:max_rows], live[max_rows:]
        p_before = idx.cache.p_max
        stats = None
        if live.size:
            dx = np.asarray(d.vectors)[live]          # metric-normalised
            dids = np.asarray(d.ids)[live]
            assign = maintenance.assign_nearest_centroid(dx, idx.centroids)
            touched = np.unique(assign)
            if quantized:
                # move the insert-time codes verbatim (same contract as
                # resident flush_delta); re-encode only as a fallback
                dcod = (np.asarray(d.codes)[live] if d.codes is not None
                        else quantize.encode_np(idx.qstats, dx))
                self.store.set_code_tier(
                    dids, dcod, *quantize.stats_to_arrays(idx.qstats))
            idx.cache.invalidate(touched)
            idx.counts = idx.counts + np.bincount(assign, minlength=idx.k)
            cent = np.array(idx.centroids)
            csz = np.array(idx.csizes)
            if idx.drift is None:
                idx.drift = np.zeros((idx.k,), np.float32)
            maintenance.running_mean_update(cent, csz, dx, assign, touched,
                                            drift=idx.drift)
            idx.centroids = jnp.asarray(cent)
            idx.csizes = jnp.asarray(csz)
            # row moves + TOUCHED centroid rewrites in one transaction --
            # durable I/O matches the stats accounting (never O(k))
            self.store.apply_repair(dids, assign, touched,
                                    cent[touched], csz[touched])
            self._persist_maintenance_state()
            pad = effective_pad_to(self.config)
            new_p_max = int(idx.counts.max())
            new_p_max = max(idx.cache.p_max, -(-new_p_max // pad) * pad)
            if new_p_max > idx.cache.p_max:   # a partition outgrew a frame
                idx.cache.resize(new_p_max)
            stats = maintenance.MaintenanceStats(
                kind="incremental", rows_moved=int(live.size),
                partitions_touched=int(len(touched)),
                bytes_written=int(live.size
                                  * (4 * idx.dim + 4 + 4 * idx.n_attr + 1
                                     + (idx.dim if quantized else 0))
                                  + len(touched) * idx.dim * 4),
                p_max_before=p_before, p_max_after=idx.cache.p_max)
            self.maintenance_log.append(stats)
        # partial flush: deferred live rows compact to the front of a
        # fresh delta (the same compaction the resident path uses)
        idx.delta = maintenance.compact_delta(d, deferred, idx.n_attr,
                                              quantized, idx.qstats)
        return stats

    # -- helpers --------------------------------------------------------------
    def _refresh_stats(self):
        idx = self.index
        flat_attrs = np.asarray(idx.attrs).reshape(
            idx.k * idx.p_max, idx.n_attr)
        live = np.asarray(idx.valid).reshape(-1)
        self.optimizer = HybridOptimizer(AttributeStats(flat_attrs[live]))

    def _persist_maintenance_state(self):
        """Mirror the monitor's maintenance signals (per-partition drift
        accumulators + the rebuild baseline mean size) into the store's
        meta table, so recover() resumes maintenance timing instead of
        resetting drift to zero. Called at every point that durably
        changes the clustering or the signals themselves."""
        idx = self.index
        if idx is None:
            return
        drift = np.asarray(idx.drift, np.float32) if idx.drift is not None \
            else np.zeros((idx.k,), np.float32)
        self.store.set_maintenance_state(float(idx.base_mean_size), drift)

    def _persist_codes(self):
        """Mirror the resident code tier (+ quantizer stats) durably --
        one transaction, so codes and stats can never diverge -- letting
        recover() restore the tier without re-encoding."""
        idx = self.index
        if idx is None or idx.codes is None:
            return
        val = np.asarray(idx.valid)
        self.store.set_code_tier(np.asarray(idx.ids)[val],
                                 np.asarray(idx.codes)[val],
                                 *quantize.stats_to_arrays(idx.qstats))

    def _current_assignment(self) -> np.ndarray:
        """asset id -> partition id for every live main-tier row, as one
        numpy scatter from the packed ids/valid arrays (no per-partition
        host round-trips)."""
        idx = self.index
        vid = np.asarray(idx.ids)
        val = np.asarray(idx.valid)
        out = np.full(int(vid.max()) + 1 if vid.size else 1, -1, np.int64)
        rows = vid[val]
        parts = np.broadcast_to(
            np.arange(idx.k, dtype=np.int64)[:, None], vid.shape)[val]
        out[rows] = parts
        return out

    def _centroid_state(self) -> Tuple[np.ndarray, np.ndarray]:
        return (np.asarray(self.index.centroids),
                np.asarray(self.index.csizes))
