"""Disk-resident partition pager: a memory-budgeted buffer pool of
partition frames (the paper's "~10 MB resident at million scale" made
literal -- cf. Faiss's on-disk inverted lists).

Frame layout. The pool is a fixed set of F frames preallocated up front
from the byte budget; each frame seats one partition in the same padded
layout the resident tier uses:

    payload  [F, p_max, d]   int8 codes (quantized index) or f32 vectors
    ids      [F, p_max]      asset ids, INVALID_ID marks padding
    valid    [F, p_max]      live-row mask
    attrs    [F, p_max, a]   optional, for fused attribute predicates

so the existing fused-scan kernels run over the pool unchanged: the
scalar-prefetched `part_ids` input simply carries *frame* indices instead
of partition indices (the frame -> partition indirection lives in the
pool's host-side frame table). F = budget_bytes // frame_bytes; the
pool never grows, so resident bytes are <= the budget by construction.

PR 9 splits ownership: the pool mechanics -- preallocated frames, CLOCK
eviction, scan-resistant admission ring, pins, read-ahead staging, the
donated batched scatter -- live in `fleet.pool.FramePool`, keyed by
`(tenant, pid)` so MANY engines can share ONE pool under one global
budget (fleet mode). `PartitionCache` here is the per-tenant VIEW an
engine holds: it owns the tenant-specific fetch path (its VectorStore,
metric normalisation, quantizer stats) and the tenant's cumulative
counters, and delegates frames/eviction/pins to the pool. A solo engine
(no fleet) constructs a private single-tenant pool, so its behavior --
eviction order, hit/miss accounting, donation rules, budget errors --
is exactly the PR 6 pager's (pinned by tests/test_pager.py).

Eviction is CLOCK (second chance): a fault sweeps the hand past pinned
frames and frames whose reference bit is set (clearing it), and reclaims
the first cold unpinned frame. Frames are *pinned* for the duration of a
scan chunk (fault() pins, the executor unpins after the scan), so a
concurrent fault can never steal a frame mid-scan; faulting more
partitions than the pool seats raises, which is what forces the
executor's streaming chunked scan.

Admission policy (scan resistance): `fault(pids, admit=False)` marks a
one-off stream -- a paged *exact* search reads every partition exactly
once, and admitting that stream would flush the hot ANN working set.
Non-admitted faults cycle through a small reusable *scan ring* of at
most `scan_frames` frames (a fraction of the pool; same byte budget),
never touching admitted frames' reference bits; ring frames are the
preferred eviction victims for admitted traffic, and a later admitted
hit on a ring frame promotes it out of the ring. Probes already resident
still hit (and stay hot), so a full scan reuses the warm set for free.

Fault path: all missing partitions of a probe set are fetched in ONE SQL
round-trip (VectorStore.scan_partitions -- the clustered primary key
makes each partition a sequential range read) and scattered into the
pool in one batched device write.

Invalidation contract: any write that changes a partition's durable rows
(delta flush into it, a split/merge moving rows, upsert/delete of one of
its rows, a rebuild) must call invalidate(pids) / invalidate_all(); the
next fault re-reads the partition from SQLite. Invalidating a partition
whose frame is pinned by an in-flight scan defers the release to the
last unpin -- the scan keeps its (pre-invalidation snapshot) frame, and
the mapping is dropped immediately so the next fault refetches. Counters
(hits / misses / evictions) are cumulative and surface through
MicroNN.stats().

Thread safety: every public method takes the POOL's RLock, so the
background maintenance scheduler (storage/scheduler.py), query threads,
and -- in fleet mode -- every co-tenant engine may interleave
fault/invalidate/unpin safely. Scans themselves run outside the lock:
pinned frames cannot be evicted, and the pool arrays are functionally
rebound -- a scan always reads a consistent snapshot.

Fault scatter: when no scan (of ANY tenant) holds pins, the batched
fault scatters fetched frames into the pool through a jitted donated
update (`donate_argnums`) -- XLA aliases the output to the input buffer
and updates the touched frames in place, so a fault never allocates a
second pool-sized buffer (asserted by tests/test_pager.py via the
compiled memory analysis). With foreign pins outstanding the fault
falls back to a copying scatter: donation would invalidate the buffer a
concurrent scan may still be reading.

Read-ahead staging (PR 6 double-buffering): `stage(pids)` runs the SQL
round-trip + host-side block packing for a future chunk WITHOUT taking
frames, pins, or rebinding any pool -- the processed per-partition
blocks land in a host-side staging dict that the next fault() consumes
under the lock, paying only the frame scatter. The executor's paged
loop submits stage(chunk N+1) to a worker thread while the fused scan
chews on chunk N, overlapping the disk latency with compute at
UNCHANGED chunking (so results are trivially bit-identical with
staging off). Staging is purely advisory: entries are dropped by
invalidate()/resize() (a generation counter discards in-flight stages
that raced a writer), fault() falls back to SQLite for anything not
staged, and the buffer holds at most one scan chunk of host blocks --
the classic double-buffer cost, bounded by scan_frames * frame_bytes.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..core import quantize
from ..core.types import normalize_if_cosine
from ..fleet.pool import (FramePool, _scatter_frames, _scatter_one,  # noqa: F401 -- re-exported; tests compile _scatter_frames directly
                          compute_frame_bytes)
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace


class PartitionCache:
    """Per-tenant view over a FramePool of partition frames.

    Solo mode (pool=None): constructs a private single-tenant pool from
    `budget_bytes` -- the PR 6 pager, verbatim. Fleet mode: pass the
    shared `pool` and a stable `tenant` name; frames then compete under
    the fleet-wide budget via the pool's global CLOCK, and
    `budget_bytes` reflects the POOL's (fleet) budget."""

    def __init__(self, store, *, p_max: int, budget_bytes: int,
                 payload: str = "f32", metric: str = "l2",
                 qstats=None, with_attrs: bool = False,
                 metrics=None, pool: Optional[FramePool] = None,
                 tenant: Optional[str] = None):
        assert payload in ("f32", "int8"), payload
        if payload == "int8":
            assert qstats is not None, "int8 frames need quantizer stats"
        self.store = store
        self.metric = metric
        self.payload = payload
        self.qstats = qstats
        self.with_attrs = bool(with_attrs and store.n_attr)
        # counters live in the process metrics registry (PR 8). The engine
        # passes its own scope so counts survive re-attachment (the scope's
        # get-or-create hands back the SAME counter objects); standalone
        # caches get a fresh uniquely-labeled scope, so they start at zero.
        if metrics is None:
            metrics = obs_metrics.default_registry().scope(
                component="pager", inst=str(obs_metrics.next_instance()))
        self._metrics = metrics
        self._c_hits = metrics.counter("hits")
        self._c_misses = metrics.counter("misses")
        self._c_evictions = metrics.counter("evictions")
        self._c_bytes_read = metrics.counter("bytes_read")
        self._c_bytes_staged = metrics.counter("bytes_staged")
        self._c_staged_consumed = metrics.counter("staged_consumed")
        # per-fault work breakdown, for the active trace's fault span:
        # (hits, misses, staged frames consumed, bytes synchronously read)
        self._last_fault = (0, 0, 0, 0)
        self._private_pool = pool is None
        if pool is None:
            pool = FramePool(
                dim=store.dim, p_max=p_max, budget_bytes=budget_bytes,
                payload=payload,
                n_attr=store.n_attr if self.with_attrs else 0)
            tenant = "solo" if tenant is None else tenant
        else:
            assert tenant is not None, \
                "a shared FramePool view needs a stable tenant name"
        self._pool = pool
        self.tenant = str(tenant)
        self._tid = pool.register(self, self.tenant, p_max=p_max)

    # -- cumulative counters (registry-backed; plain ints out) ---------------
    @property
    def hits(self) -> int:
        return self._c_hits.value

    @hits.setter
    def hits(self, v: int):
        self._c_hits.set(int(v))

    @property
    def misses(self) -> int:
        return self._c_misses.value

    @misses.setter
    def misses(self, v: int):
        self._c_misses.set(int(v))

    @property
    def evictions(self) -> int:
        return self._c_evictions.value

    @evictions.setter
    def evictions(self, v: int):
        self._c_evictions.set(int(v))

    # -- pool geometry (delegated) -------------------------------------------
    compute_frame_bytes = staticmethod(compute_frame_bytes)

    @property
    def pool(self) -> FramePool:
        return self._pool

    @property
    def budget_bytes(self) -> int:
        return self._pool.budget_bytes

    @property
    def p_max(self) -> int:
        return self._pool.p_max

    @property
    def frame_bytes(self) -> int:
        return self._pool.frame_bytes

    @property
    def capacity(self) -> int:
        return self._pool.capacity

    @property
    def scan_frames(self) -> int:
        return self._pool.scan_frames

    @property
    def payload_pool(self):
        return self._pool.payload_pool

    @property
    def ids_pool(self):
        return self._pool.ids_pool

    @property
    def valid_pool(self):
        return self._pool.valid_pool

    @property
    def attrs_pool(self):
        return self._pool.attrs_pool

    @property
    def resident_bytes(self) -> int:
        return self._pool.resident_bytes

    # -- frame-table views (tests + introspection; pool holds the truth) ----
    @property
    def _lock(self):
        return self._pool._lock

    @property
    def _pid_frame(self) -> dict:
        return self._pool.tenant_frames(self._tid)

    @property
    def _staged(self) -> dict:
        return self._pool.tenant_staged(self._tid)

    @property
    def _frame_pid(self) -> np.ndarray:
        return self._pool._frame_pid

    @property
    def _pins(self) -> np.ndarray:
        return self._pool._pins

    @property
    def _ref(self) -> np.ndarray:
        return self._pool._ref

    @property
    def _stale(self) -> np.ndarray:
        return self._pool._stale

    @property
    def _transient(self) -> np.ndarray:
        return self._pool._transient

    @property
    def _ring(self) -> list:
        return self._pool._ring

    def resize(self, p_max: int):
        """Reallocate the pool for a larger partition size (after a flush
        or merge grows some partition past p_max). Drops every frame --
        the caller already invalidated the moved partitions -- but keeps
        the cumulative counters and the byte budget. A SHARED pool only
        ever grows: co-tenants' partitions may still need the current
        p_max."""
        if not self._private_pool:
            p_max = max(int(p_max), self._pool.p_max)
        self._pool.resize(p_max)

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "bytes_read": self._c_bytes_read.value,
                "bytes_staged": self._c_bytes_staged.value,
                "staged_consumed": self._c_staged_consumed.value,
                "resident_bytes": self.resident_bytes,
                "budget_bytes": self.budget_bytes,
                "capacity_frames": self.capacity,
                "frame_bytes": self.frame_bytes,
                "resident_partitions":
                    self._pool.resident_count(self._tid)}

    # -- fetch ---------------------------------------------------------------
    def _fetch_blocks(self, pids: Sequence[int]):
        """One batched SQL round-trip for the listed partitions, packed to
        pool layout on the host: (payload, ids, valid, attrs) numpy blocks
        of shape [len(pids), p_max, ...] (attrs is None without an attrs
        pool). int8 pools skip the f32 blobs entirely -- the fetch moves
        4x fewer bytes off disk (the point of the code tier) -- and
        backfill the rare code-less row from the f32 tier with the same
        deterministic encode the build used. Pure read: no pool, frame
        table, or counter is touched, so stage() may run it off-lock."""
        sq = self.payload == "int8"
        blocks = self.store.scan_partitions(
            list(pids), self.p_max,
            with_codes=sq, with_attrs=self.with_attrs, with_vecs=not sq)
        if sq:
            codes = blocks.codes
            stale = blocks.valid & ~blocks.code_ok
            if stale.any():
                # rare: rows without a durable code (written by a
                # pre-quantized engine) -- backfill just those rows
                # from the f32 tier and re-encode deterministically
                rows, _ = self.store.vectors_for(blocks.ids[stale])
                rows = np.asarray(normalize_if_cosine(
                    jnp.asarray(rows, jnp.float32), self.metric))
                codes[stale] = quantize.encode_np(self.qstats, rows)
            payload = codes
        else:
            payload = np.asarray(normalize_if_cosine(
                jnp.asarray(blocks.vecs, jnp.float32), self.metric))
        attrs = blocks.attrs if self.with_attrs else None
        return payload, blocks.ids, blocks.valid, attrs

    def stage(self, pids: Sequence[int]):
        """Read ahead: fetch + pack the listed partitions' blocks into the
        pool's host-side staging dict so the next fault() skips its SQL
        round trip. Takes no frames and no pins -- safe on a prefetch
        thread concurrently with any tenant's scan. Advisory only: a
        concurrent invalidate() bumps the generation and the whole
        in-flight stage is discarded (the next fault re-reads)."""
        self._pool.stage(self._tid, pids)

    # -- fault / pin / invalidate -------------------------------------------
    def fault(self, pids: Sequence[int], admit: bool = True) -> np.ndarray:
        """Ensure every listed partition is resident; returns the frame
        index per pid (aligned to input order), with each frame PINNED --
        the caller must unpin() after its scan. All missing partitions are
        fetched in one batched SQL round-trip.

        `admit=False` flags a one-off stream (paged exact scan): misses
        land in the reusable scan ring instead of the admitted set, and
        hits do not touch reference bits -- so the stream cannot evict or
        artificially refresh the hot working set."""
        tr = obs_trace.current()
        with obs_trace.stage(obs_trace.STAGE_FAULT, tr) as st:
            if tr is None:
                return self._pool.fault(self._tid, pids, admit)
            with self._pool._lock:
                frames = self._pool.fault(self._tid, pids, admit)
                h, m, staged, nb = self._last_fault
            st.set(hits=h, misses=m, staged=staged, bytes_read=nb,
                   admitted=bool(admit))
        return frames

    def unpin(self, frames: np.ndarray):
        self._pool.unpin(frames)

    def invalidate(self, pids: Sequence[int]):
        """Drop the listed partitions' frames (durable rows changed); the
        next fault re-reads them from SQLite. A frame pinned by an
        in-flight scan is released lazily at its last unpin -- the scan
        keeps its pre-invalidation snapshot, the mapping is gone at once."""
        self._pool.invalidate(self._tid, pids)

    def invalidate_all(self):
        self._pool.invalidate_tenant(self._tid)
