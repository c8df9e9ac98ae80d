"""Index maintenance: incremental delta flush, LIRE-style local repair
(split / merge / recluster), and the legacy full rebuild (paper §3.6).

Incremental flush ([1]-style, as the paper implements): each live delta
vector is assigned to the partition with the nearest centroid; centroids
update by the running-mean rule  c' = (v*c + sum x) / (v + m)  (the same
telescoped form as Alg. 1's eta=1/v update, see core/kmeans.py).

Local repair (the paper's Fig. 10d updatability claim, made incremental):
instead of retraining the world when partitions drift out of shape, a
repair touches only a *neighbourhood* of partitions -- an oversized
partition is 2-means-split, underfull siblings are merged, and only rows
in the touched centroid neighbourhood are reassigned. Quantized codes are
re-encoded with the *existing* quantizer (deterministic, so codes stay
byte-stable everywhere; in practice no code bytes change at all). The
planning half (`plan_split` / `plan_merge` / `plan_local_recluster`) is a
pure host computation over a `RowBlock` fetch callback, shared by the
resident and paged engines so both modes make bit-identical decisions;
`apply_plan` rewrites the resident packed layout, while both engines
persist the plan durably through one atomic repair transaction
(VectorStore.apply_repair) -- the paged engine additionally invalidates
exactly the touched pager frames.

A flush/repair only rewrites the partitions it touches -- the I/O win
over a full rebuild that Fig. 10d quantifies. We account bytes for every
path (`MaintenanceStats`) so benchmarks/bench_updates.py can reproduce
the figure.

The flush itself is a host-side repack (it changes row placement --
the 'SSD reorganisation' tier); the nearest-centroid assignment runs on
device.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace as obs_trace
from . import ivf, kmeans, quantize
from .types import (DeltaStore, FRAME_ROWS_MAX, INVALID_ID, IVFConfig,
                    IVFIndex, effective_pad_to, pairwise_scores)


@dataclasses.dataclass
class MaintenanceStats:
    kind: str                 # "incremental" | "full" | "split" | "merge"
    #                            | "recluster"
    rows_moved: int
    partitions_touched: int
    bytes_written: int        # host-tier write I/O (flash-wear metric)
    p_max_before: int
    p_max_after: int


def assign_nearest_centroid(dx: np.ndarray, centroids) -> np.ndarray:
    """Nearest-centroid assignment for a flush batch (device matmul) --
    shared by the resident and paged flush so both agree on placement.
    Always l2 over the (metric-normalised) rows: for cosine data rows and
    centroids are unit-norm, so l2 order == cosine order."""
    return np.asarray(jnp.argmin(
        pairwise_scores(jnp.asarray(dx), centroids, "l2"), axis=-1))


def running_mean_update(cent: np.ndarray, csizes: np.ndarray,
                        dx: np.ndarray, assign: np.ndarray,
                        touched: np.ndarray,
                        drift: Optional[np.ndarray] = None):
    """The paper's telescoped running-mean rule c' = (v*c + sum x)/(v+m)
    per touched partition (in place) -- shared by both flush paths so the
    resident and paged centroid trajectories stay numerically identical.

    Vectorized as one np.add.at scatter over the whole batch: bitwise
    identical to the per-partition loop it replaced, because an axis-0
    float32 sum accumulates rows sequentially in row order exactly like
    the scatter does (pinned by tests/test_maintenance.py).

    When `drift` is given, each touched partition's centroid displacement
    accumulates into it (in place) -- the monitor's recluster signal.
    """
    sums = np.zeros_like(cent)
    np.add.at(sums, assign, dx)
    m = np.bincount(assign, minlength=cent.shape[0]).astype(csizes.dtype)
    t = np.asarray(touched)
    old = cent[t].copy() if drift is not None else None
    v = csizes[t]
    cent[t] = (v[:, None] * cent[t] + sums[t]) \
        / np.maximum(v + m[t], 1.0)[:, None]
    csizes[t] = v + m[t]
    if drift is not None:
        drift[t] += np.linalg.norm(cent[t] - old, axis=-1)


def _row_bytes(index: IVFIndex) -> int:
    d = index.dim
    n_attr = index.n_attr
    # vector + id + attrs + valid (+ the int8 code tier when quantized)
    codes = d if index.codes is not None else 0
    return 4 * d + 4 + 4 * n_attr + 1 + codes


def compact_delta(d: DeltaStore, keep: np.ndarray, n_attr: int,
                  quantized: bool, qstats=None) -> DeltaStore:
    """Compact the delta rows listed in `keep` into a fresh DeltaStore --
    the tail of a *partial* flush (the scheduler's bounded work quantum
    flushes only `max_rows` rows per step and must not drop the rest).
    Shared by the resident and paged flush paths."""
    cap, dim = d.capacity, d.vectors.shape[1]
    out = DeltaStore.empty(cap, dim, n_attr, quantized=quantized)
    if keep.size == 0:
        return out
    r = keep.size
    vec = np.zeros((cap, dim), np.float32)
    vec[:r] = np.asarray(d.vectors)[keep]
    ids = np.full((cap,), INVALID_ID, np.int32)
    ids[:r] = np.asarray(d.ids)[keep]
    attrs = np.zeros((cap, n_attr), np.float32)
    attrs[:r] = np.asarray(d.attrs)[keep]
    valid = np.zeros((cap,), bool)
    valid[:r] = True
    codes = None
    if quantized:
        codes = np.zeros((cap, dim), np.int8)
        if d.codes is not None:
            codes[:r] = np.asarray(d.codes)[keep]
        else:           # hand-assembled code-less delta: re-encode
            codes[:r] = quantize.encode_np(qstats, vec[:r])
        codes = jnp.asarray(codes)
    return DeltaStore(vectors=jnp.asarray(vec), ids=jnp.asarray(ids),
                      attrs=jnp.asarray(attrs), valid=jnp.asarray(valid),
                      count=jnp.asarray(r, jnp.int32), codes=codes)


def flush_delta(index: IVFIndex, max_rows: Optional[int] = None,
                assign: Optional[np.ndarray] = None
                ) -> Tuple[IVFIndex, MaintenanceStats]:
    """Incrementally fold live delta rows into the IVF partitions.

    `max_rows` bounds the work quantum (storage/scheduler.py): only the
    first `max_rows` live rows (slot order) are flushed; the rest stay in
    the delta, compacted to the front, and remain searchable. A caller
    that already computed the flushed rows' nearest-centroid assignment
    (the engine's durable flush step mirrors the moves to SQLite) passes
    it via `assign` to skip the second identical device computation."""
    cfg = index.config
    k, p_max, d = index.vectors.shape

    quantized = index.codes is not None
    dvalid = np.asarray(index.delta.valid)
    live = np.nonzero(dvalid)[0]
    deferred = np.zeros((0,), np.int64)
    if max_rows is not None and live.size > max_rows:
        live, deferred = live[:max_rows], live[max_rows:]
    if live.size == 0:
        new = dataclasses.replace(
            index, delta=compact_delta(index.delta, deferred, index.n_attr,
                                       quantized, index.qstats))
        return new, MaintenanceStats("incremental", 0, 0, 0, p_max, p_max)

    dx = np.asarray(index.delta.vectors)[live]
    dids = np.asarray(index.delta.ids)[live]
    dattrs = np.asarray(index.delta.attrs)[live]
    if quantized:
        # Delta rows were encoded on insert; re-encode only as a fallback
        # (e.g. an index assembled by hand without a code-backed delta).
        dcod = (np.asarray(index.delta.codes)[live]
                if index.delta.codes is not None
                else quantize.encode_np(index.qstats, dx))

    # nearest-centroid assignment on device (unless the caller already
    # computed it for the durable mirror of these moves)
    if assign is None:
        assign = assign_nearest_centroid(dx, index.centroids)
    assert len(assign) == live.size

    vec = np.array(index.vectors)
    vid = np.array(index.ids)
    vat = np.array(index.attrs)
    val = np.array(index.valid)
    counts = np.array(index.counts)
    csizes = np.array(index.csizes)
    cent = np.array(index.centroids)
    cod = np.array(index.codes) if quantized else None

    # grow p_max if some partition would overflow (compaction first: reuse
    # tombstoned slots)
    add = np.bincount(assign, minlength=k)
    need = val.sum(-1) + add
    new_p_max = int(need.max())
    pad = effective_pad_to(cfg)   # int8-on-TPU pads to the (32,128) tile
    new_p_max = max(p_max, -(-new_p_max // pad) * pad)
    if new_p_max > p_max:
        grow = new_p_max - p_max
        vec = np.pad(vec, [(0, 0), (0, grow), (0, 0)])
        vid = np.pad(vid, [(0, 0), (0, grow)], constant_values=INVALID_ID)
        vat = np.pad(vat, [(0, 0), (0, grow), (0, 0)])
        val = np.pad(val, [(0, 0), (0, grow)])
        if quantized:
            cod = np.pad(cod, [(0, 0), (0, grow), (0, 0)])

    touched = np.unique(assign)
    for p in touched:
        keep = np.nonzero(val[p])[0]
        newv = np.concatenate([vec[p][keep], dx[assign == p]])
        newi = np.concatenate([vid[p][keep], dids[assign == p]])
        newa = np.concatenate([vat[p][keep], dattrs[assign == p]])
        m = len(newv)
        vec[p, :m] = newv; vec[p, m:] = 0.0
        vid[p, :m] = newi; vid[p, m:] = INVALID_ID
        vat[p, :m] = newa; vat[p, m:] = 0.0
        val[p, :m] = True; val[p, m:] = False
        if quantized:
            newc = np.concatenate([cod[p][keep], dcod[assign == p]])
            cod[p, :m] = newc; cod[p, m:] = 0
        counts[p] = m
    drift = np.asarray(index.drift, np.float32).copy() \
        if index.drift is not None else np.zeros((k,), np.float32)
    running_mean_update(cent, csizes, dx, assign, touched, drift=drift)

    stats = MaintenanceStats(
        kind="incremental",
        rows_moved=int(live.size),
        partitions_touched=int(len(touched)),
        # host-tier write I/O: a clustered B-tree append touches only the
        # pages of the inserted rows (not the whole partition) -- count
        # moved rows + the touched partitions' centroid rewrites. This is
        # the paper's "<2% of full rebuild" metric (Fig. 10d).
        bytes_written=int(live.size * _row_bytes(index)
                          + len(touched) * d * 4),
        p_max_before=p_max, p_max_after=new_p_max)

    codes = jnp.asarray(cod) if quantized else None
    new_index = IVFIndex(
        centroids=jnp.asarray(cent),
        csizes=jnp.asarray(csizes),
        vectors=jnp.asarray(vec), ids=jnp.asarray(vid),
        attrs=jnp.asarray(vat), valid=jnp.asarray(val),
        counts=jnp.asarray(counts),
        delta=compact_delta(index.delta, deferred, index.n_attr, quantized,
                            index.qstats),
        base_mean_size=index.base_mean_size,
        codes=codes,
        qstats=index.qstats,
        code_norms=quantize.row_norms(index.qstats, codes)
        if quantized else None,
        drift=jnp.asarray(drift),
        config=cfg)
    return new_index, stats


# ---------------------------------------------------------------------------
# LIRE-style local repair: split / merge / recluster over a partition
# neighbourhood. Planning is a pure host computation shared by the resident
# and paged engines (both feed it the same row bytes, sorted by asset id,
# so the two modes produce bit-identical repairs); application is
# mode-specific (apply_plan rewrites the packed layout; the paged engine
# applies durably + invalidates frames).
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RowBlock:
    """Live rows of one partition, sorted ascending by asset id (the order
    both the packed resident layout after repack and SQLite's clustered
    scan agree on). `attrs`/`codes` ride along only where the fetcher has
    them resident (the paged apply re-reads them from SQLite instead)."""

    ids: np.ndarray                       # [m] int32
    vecs: np.ndarray                      # [m, d] f32, metric-normalised
    attrs: Optional[np.ndarray] = None    # [m, n_attr] f32
    codes: Optional[np.ndarray] = None    # [m, d] int8

# fetch callback: pids -> {pid: RowBlock} (one batched read per repair)
RowFetch = Callable[[Sequence[int]], Dict[int, "RowBlock"]]


@dataclasses.dataclass
class RepairPlan:
    """One planned local repair: which partitions are touched, where every
    affected row lands, and the neighbourhood's new centroid state. The
    plan is pure data -- the engine persists it durably (codes first, then
    one generation-swap transaction) and applies it to device state."""

    kind: str                 # "split" | "merge" | "recluster"
    pids: np.ndarray          # [L] int64 -- touched partitions (split: the
    #                           new slot is last)
    new_pid: Optional[int]    # slot a split allocated (reused empty slot,
    #                           or == k_before when appending)
    k_after: int              # partition count after the repair
    row_ids: np.ndarray       # [m] int32 -- every live row in the
    #                           neighbourhood (block order per pids)
    row_vecs: np.ndarray      # [m, d] f32 metric-normalised
    row_attrs: Optional[np.ndarray]   # [m, n_attr] (resident fetch only)
    row_codes: Optional[np.ndarray]   # [m, d] int8 (resident fetch only)
    src: np.ndarray           # [m] int64 -- current partition per row
    assign: np.ndarray        # [m] int64 -- new partition per row
    centroids: np.ndarray     # [L, d] f32 -- new centroids for `pids`
    csizes: np.ndarray        # [L] f32 -- restarted running counts

    @property
    def rows(self) -> int:
        return int(self.row_ids.size)

    @property
    def moved(self) -> np.ndarray:
        return self.assign != self.src


def two_means(rows: np.ndarray, iters: int = 8
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Deterministic 2-means over [m, d] float32 rows: farthest-point init
    from the partition mean, fixed Lloyd iterations, ties to side 0. No
    RNG and no order sensitivity beyond the caller's (sorted-by-id) row
    order, so the resident and paged planners split identically."""
    mu = rows.mean(0)
    c1 = rows[int(((rows - mu) ** 2).sum(-1).argmax())]
    c2 = rows[int(((rows - c1) ** 2).sum(-1).argmax())]
    assign = np.zeros((rows.shape[0],), np.int64)
    for _ in range(iters):
        d1 = ((rows - c1) ** 2).sum(-1)
        d2 = ((rows - c2) ** 2).sum(-1)
        new = (d2 < d1).astype(np.int64)
        if (new == 0).all() or (new == 1).all():
            assign = new
            break
        c1n, c2n = rows[new == 0].mean(0), rows[new == 1].mean(0)
        done = np.array_equal(new, assign)
        assign = new
        if done:
            break
        c1, c2 = c1n, c2n
    return np.stack([c1, c2]), assign


def neighborhood(centroids: np.ndarray, counts: np.ndarray,
                 seeds: Sequence[int], row_budget: Optional[int],
                 n_extra: int) -> List[int]:
    """The touched centroid neighbourhood of a repair: the seed partitions
    plus up to `n_extra` nearest non-empty partitions whose rows still fit
    the row budget (the scheduler's work quantum). Deterministic: ordered
    by centroid distance to the first seed, ties by partition id."""
    base = [int(p) for p in seeds]
    used = int(counts[base].sum())
    if n_extra <= 0:
        return base
    ref = centroids[base[0]]
    dist = ((centroids - ref) ** 2).sum(-1)
    order = np.lexsort((np.arange(len(centroids)), dist))
    out = list(base)
    for q in order:
        if len(out) - len(base) >= n_extra:
            break
        q = int(q)
        if q in base or counts[q] <= 0:
            continue
        if row_budget is not None and used + int(counts[q]) > row_budget:
            continue
        out.append(q)
        used += int(counts[q])
    return out


def _gather_blocks(blocks: Dict[int, RowBlock], pids: Sequence[int]):
    """Concatenate the neighbourhood's RowBlocks in pid-list order."""
    ids = [blocks[p].ids for p in pids if p in blocks]
    if not ids:
        d = 0
        return (np.zeros((0,), np.int32), np.zeros((0, d), np.float32),
                None, None, np.zeros((0,), np.int64))
    vecs = np.concatenate([blocks[p].vecs for p in pids if p in blocks])
    src = np.concatenate([np.full((len(blocks[p].ids),), p, np.int64)
                          for p in pids if p in blocks])
    have_attrs = all(blocks[p].attrs is not None
                     for p in pids if p in blocks)
    have_codes = all(blocks[p].codes is not None
                     for p in pids if p in blocks)
    attrs = np.concatenate([blocks[p].attrs for p in pids if p in blocks]) \
        if have_attrs else None
    codes = np.concatenate([blocks[p].codes for p in pids if p in blocks]) \
        if have_codes else None
    return np.concatenate(ids), vecs, attrs, codes, src


def _finalize_plan(kind, local, new_pid, k_after, row_ids, row_vecs,
                   row_attrs, row_codes, src, local_cents
                   ) -> Optional[RepairPlan]:
    """Shared tail of every planner: reassign the neighbourhood's rows to
    their nearest local centroid, then restate each touched partition's
    centroid as the mean of its new members (running-mean restart).
    Partitions left empty keep their (masked-by-count) old centroid."""
    d2 = ((row_vecs[:, None, :] - local_cents[None, :, :]) ** 2).sum(-1)
    pick = d2.argmin(axis=1)                      # ties -> lowest index
    assign = np.asarray(local, np.int64)[pick]
    cents = local_cents.copy().astype(np.float32)
    csz = np.zeros((len(local),), np.float32)
    for j in range(len(local)):
        sel = pick == j
        m = int(sel.sum())
        csz[j] = m
        if m:
            cents[j] = row_vecs[sel].mean(0)
    return RepairPlan(
        kind=kind, pids=np.asarray(local, np.int64), new_pid=new_pid,
        k_after=k_after, row_ids=row_ids, row_vecs=row_vecs,
        row_attrs=row_attrs, row_codes=row_codes, src=src, assign=assign,
        centroids=cents, csizes=csz)


def plan_split(centroids: np.ndarray, csizes: np.ndarray,
               counts: np.ndarray, pid: int, fetch: RowFetch, *,
               row_budget: Optional[int] = None, n_local: int = 2
               ) -> Optional[RepairPlan]:
    """2-means split of an oversized partition + local reassignment of the
    touched neighbourhood. The freed half lands in a reused empty slot
    when one exists (keeping k stable under churn), else in a new slot k.
    Returns None when the partition is degenerate (all rows identical)."""
    k = centroids.shape[0]
    pid = int(pid)
    nbrs = neighborhood(centroids, counts, [pid], row_budget, n_local)
    blocks = fetch(nbrs)
    seed = blocks.get(pid)
    if seed is None or len(seed.ids) < 2:
        return None
    (c1, c2), halves = two_means(seed.vecs)
    if (halves == 0).all() or (halves == 1).all():
        return None                      # degenerate: nothing to split
    if (halves == 1).sum() > (halves == 0).sum():
        # the larger half stays in place (fewer durable row moves)
        c1, c2 = c2, c1
    empty = [int(p) for p in np.nonzero(counts == 0)[0] if p not in nbrs]
    new_pid = empty[0] if empty else k
    k_after = max(k, new_pid + 1)
    local = nbrs + [new_pid]
    row_ids, row_vecs, row_attrs, row_codes, src = _gather_blocks(
        blocks, nbrs)
    local_cents = np.concatenate(
        [np.stack([c1]), centroids[nbrs[1:]], np.stack([c2])]) \
        .astype(np.float32)
    plan = _finalize_plan("split", local, new_pid, k_after, row_ids,
                          row_vecs, row_attrs, row_codes, src, local_cents)
    if plan is None or not plan.moved.any():
        return None
    return plan


@dataclasses.dataclass
class BuildSplitStats:
    """What the build's split pass did (`split_to_bar`)."""

    splits: int = 0           # 2-means splits applied
    rows_split: int = 0       # rows of each split partition, summed over
    #                           every split (a row split twice counts twice)
    unsplittable: int = 0     # partitions left above the bar (rows all equal)
    max_rows: int = 0         # largest partition after the pass
    ms: float = 0.0           # host time of the pass


def _unit_rows(rows: np.ndarray, metric: str) -> np.ndarray:
    """Host metric normalisation, row by row (NumPy: no device op and no
    compile per partition size)."""
    rows = np.asarray(rows, np.float32)
    if metric != "cosine":
        return rows
    n = np.linalg.norm(rows, axis=-1, keepdims=True)
    return rows / np.maximum(n, np.float32(1e-12))


def split_to_bar(centroids: np.ndarray, csizes: np.ndarray,
                 ids: np.ndarray, assign: np.ndarray,
                 rows_for: Callable[[np.ndarray], np.ndarray], metric: str
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                            BuildSplitStats]:
    """The build's split pass: 2-means-split (`plan_split`, the monitor's
    own split) every partition above `FRAME_ROWS_MAX` rows, and each
    piece again, until none is above it. Runs on a fresh clustering
    before it is persisted, so p_max stays where the paged frame scan
    compiles.

    `ids` and `assign` are aligned [n] arrays over the build's rows;
    `rows_for(pos)` returns the raw float32 rows at those positions. One
    oversized partition is read at a time, its rows sorted by asset id,
    so host memory is O(largest partition) and the resident and paged
    builds, fed the same rows, split identically. Returns the new
    (centroids, csizes, assign) and what was done; k grows by the splits
    that found no empty slot to reuse."""
    t0 = time.perf_counter()
    tr = obs_trace.current()
    with obs_trace.stage(obs_trace.STAGE_BUILD_SPLIT, tr) as st:
        assign = np.array(assign, np.int64)
        k = centroids.shape[0]
        counts = np.bincount(assign, minlength=k).astype(np.int64)
        order = np.argsort(assign, kind="stable")
        starts = np.concatenate([[0], np.cumsum(counts)])
        # grown by doubling: a split appends at most one slot
        cents = np.array(centroids, np.float32)
        csz = np.array(csizes, np.float32)
        stats = BuildSplitStats()
        for p0 in np.nonzero(counts > FRAME_ROWS_MAX)[0]:
            pos = order[starts[p0]:starts[p0 + 1]]
            pos = pos[np.argsort(ids[pos], kind="stable")]
            vecs = _unit_rows(rows_for(pos), metric)
            members = {int(p0): np.arange(len(pos))}
            todo = [int(p0)]
            while todo:
                p = todo.pop()
                sel = members[p]
                block = RowBlock(ids=ids[pos[sel]].astype(np.int32),
                                 vecs=vecs[sel])
                plan = plan_split(cents[:k], csz[:k], counts[:k], p,
                                  lambda pids, p=p, b=block: {p: b},
                                  n_local=0)
                if plan is None:
                    stats.unsplittable += 1
                    continue
                new = int(plan.new_pid)
                if new >= len(cents):
                    grow = len(cents)
                    cents = np.pad(cents, [(0, grow), (0, 0)])
                    csz = np.pad(csz, (0, grow))
                    counts = np.pad(counts, (0, grow))
                k = max(k, plan.k_after)
                cents[plan.pids] = plan.centroids
                csz[plan.pids] = plan.csizes
                assign[pos[sel]] = plan.assign
                stats.splits += 1
                stats.rows_split += len(sel)
                for q in (p, new):
                    members[q] = sel[plan.assign == q]
                    counts[q] = len(members[q])
                    if counts[q] > FRAME_ROWS_MAX:
                        todo.append(q)
        stats.max_rows = int(counts[:k].max()) if k else 0
        stats.ms = (time.perf_counter() - t0) * 1e3
        st.set(**dataclasses.asdict(stats))
    return cents[:k], csz[:k], assign, stats


def fit_to_bar(X: np.ndarray, ids: np.ndarray, cfg: IVFConfig,
               k: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                          BuildSplitStats]:
    """The in-memory build's clustering (ivf.build_index): Alg. 1 over the
    raw rows, then `split_to_bar` to `FRAME_ROWS_MAX`. The paged build
    streams the same two steps from SQLite and gets the same result."""
    cents, csz, assign = kmeans.fit_in_memory(X, cfg, k=k)
    return split_to_bar(cents, csz, ids, assign, lambda pos: X[pos],
                        cfg.metric)


def choose_merge_partner(centroids: np.ndarray, counts: np.ndarray,
                         victim: int, split_bar: float,
                         exclude: Sequence[int] = ()) -> Optional[int]:
    """Bin-packing partner selection for a merge: among the non-empty
    partitions whose post-merge size still fits under the split bar, pick
    the one that *minimizes the post-merge slack* (best-fit decreasing --
    the classic bin-packing heuristic), NOT merely the nearest centroid.
    Nearest-centroid partnering tends to pour small partitions into other
    small partitions, leaving many half-empty bins that each trigger a
    later merge; best-fit packs the victim into the fullest partition it
    still fits, retiring a bin per merge. Ties on slack break by centroid
    distance to the victim (locality still matters for recall), then by
    partition id (determinism). Returns None when nothing fits."""
    victim = int(victim)
    counts = np.asarray(counts)
    k = centroids.shape[0]
    merged = counts + counts[victim]
    dist = ((centroids - centroids[victim]) ** 2).sum(-1)
    ok = (counts > 0) & (merged <= split_bar)
    ok[victim] = False
    for p in exclude:
        if 0 <= int(p) < k:
            ok[int(p)] = False
    if not ok.any():
        return None
    slack = np.where(ok, split_bar - merged, np.inf)
    # lexsort: last key is primary -> (slack, distance, pid)
    order = np.lexsort((np.arange(k), dist, slack))
    return int(order[0])


def plan_merge(centroids: np.ndarray, csizes: np.ndarray,
               counts: np.ndarray, into: int, victim: int, fetch: RowFetch
               ) -> Optional[RepairPlan]:
    """Merge an underfull partition into a sibling: every row of `victim`
    moves to `into`, whose centroid restarts at the merged rows' mean.
    The victim keeps its (masked-by-count) centroid slot -- reusable by a
    later split, so k never needs global renumbering."""
    into, victim = int(into), int(victim)
    local = [into, victim]
    blocks = fetch(local)
    row_ids, row_vecs, row_attrs, row_codes, src = _gather_blocks(
        blocks, local)
    if row_ids.size == 0:
        return None
    assign = np.full((row_ids.size,), into, np.int64)
    cents = np.stack([row_vecs.mean(0),
                      centroids[victim]]).astype(np.float32)
    csz = np.asarray([row_ids.size, 0.0], np.float32)
    plan = RepairPlan(
        kind="merge", pids=np.asarray(local, np.int64), new_pid=None,
        k_after=centroids.shape[0], row_ids=row_ids, row_vecs=row_vecs,
        row_attrs=row_attrs, row_codes=row_codes, src=src, assign=assign,
        centroids=cents, csizes=csz)
    return plan


def plan_local_recluster(centroids: np.ndarray, csizes: np.ndarray,
                         counts: np.ndarray, pid: int, fetch: RowFetch, *,
                         row_budget: Optional[int] = None, n_local: int = 2
                         ) -> Optional[RepairPlan]:
    """Local repair of a drifted (or tombstone-heavy) partition: reassign
    only the rows in its centroid neighbourhood to their nearest local
    centroid and restart those centroids at their members' means. Always
    returns a plan (even a no-move one: the repack drops tombstones and
    the apply resets the drift signal)."""
    nbrs = neighborhood(centroids, counts, [int(pid)], row_budget, n_local)
    blocks = fetch(nbrs)
    row_ids, row_vecs, row_attrs, row_codes, src = _gather_blocks(
        blocks, nbrs)
    if row_ids.size == 0:
        return None
    return _finalize_plan("recluster", nbrs, None, centroids.shape[0],
                          row_ids, row_vecs, row_attrs, row_codes, src,
                          centroids[nbrs].astype(np.float32))


def apply_plan(index: IVFIndex, plan: RepairPlan) -> IVFIndex:
    """Rewrite the resident packed layout per a RepairPlan: only the
    touched partitions' slots change (rows packed ascending by asset id,
    matching what a recover() from the repaired durable state would pack),
    k/p_max grow as needed, codes move byte-stable with their rows, and
    the touched partitions' drift resets."""
    cfg = index.config
    k, p_max, d = index.vectors.shape
    quantized = index.codes is not None
    assert plan.row_attrs is not None, "resident apply needs attrs"
    assert (not quantized) or plan.row_codes is not None

    vec = np.array(index.vectors)
    vid = np.array(index.ids)
    vat = np.array(index.attrs)
    val = np.array(index.valid)
    counts = np.array(index.counts)
    cent = np.array(index.centroids)
    csz = np.array(index.csizes)
    cod = np.array(index.codes) if quantized else None
    drift = np.asarray(index.drift, np.float32).copy() \
        if index.drift is not None else np.zeros((k,), np.float32)

    if plan.k_after > k:
        grow = plan.k_after - k
        vec = np.pad(vec, [(0, grow), (0, 0), (0, 0)])
        vid = np.pad(vid, [(0, grow), (0, 0)], constant_values=INVALID_ID)
        vat = np.pad(vat, [(0, grow), (0, 0), (0, 0)])
        val = np.pad(val, [(0, grow), (0, 0)])
        counts = np.pad(counts, (0, grow))
        cent = np.pad(cent, [(0, grow), (0, 0)])
        csz = np.pad(csz, (0, grow))
        drift = np.pad(drift, (0, grow))
        if quantized:
            cod = np.pad(cod, [(0, grow), (0, 0), (0, 0)])

    sizes = np.asarray([(plan.assign == p).sum() for p in plan.pids])
    pad = effective_pad_to(cfg)
    new_p_max = max(p_max, -(-int(max(sizes.max(), 1)) // pad) * pad)
    if new_p_max > p_max:
        grow = new_p_max - p_max
        vec = np.pad(vec, [(0, 0), (0, grow), (0, 0)])
        vid = np.pad(vid, [(0, 0), (0, grow)], constant_values=INVALID_ID)
        vat = np.pad(vat, [(0, 0), (0, grow), (0, 0)])
        val = np.pad(val, [(0, 0), (0, grow)])
        if quantized:
            cod = np.pad(cod, [(0, 0), (0, grow), (0, 0)])

    for j, p in enumerate(plan.pids):
        sel = plan.assign == p
        order = np.argsort(plan.row_ids[sel], kind="stable")
        m = int(sel.sum())
        vec[p] = 0.0
        vid[p] = INVALID_ID
        vat[p] = 0.0
        val[p] = False
        if quantized:
            cod[p] = 0
        if m:
            vec[p, :m] = plan.row_vecs[sel][order]
            vid[p, :m] = plan.row_ids[sel][order]
            vat[p, :m] = plan.row_attrs[sel][order]
            val[p, :m] = True
            if quantized:
                cod[p, :m] = plan.row_codes[sel][order]
        counts[p] = m
        cent[p] = plan.centroids[j]
        csz[p] = plan.csizes[j]
        drift[p] = 0.0

    codes = jnp.asarray(cod) if quantized else None
    return dataclasses.replace(
        index,
        centroids=jnp.asarray(cent), csizes=jnp.asarray(csz),
        vectors=jnp.asarray(vec), ids=jnp.asarray(vid),
        attrs=jnp.asarray(vat), valid=jnp.asarray(val),
        counts=jnp.asarray(counts),
        codes=codes,
        code_norms=quantize.row_norms(index.qstats, codes)
        if quantized else None,
        drift=jnp.asarray(drift))


def repack_partition(index: IVFIndex, pid: int) -> IVFIndex:
    """Device-only tombstone repack of one partition: live rows re-pack
    ascending by asset id (the order paged frames and recover() use) and
    dead slots clear. No centroid, drift, or durable change -- the paged
    engine has no tombstones, so the two modes' durable states stay
    identical; write I/O is zero (the flash never sees it)."""
    pid = int(pid)
    vec = np.array(index.vectors[pid])
    vid = np.array(index.ids[pid])
    vat = np.array(index.attrs[pid])
    val = np.array(index.valid[pid])
    cod = np.array(index.codes[pid]) if index.codes is not None else None
    sel = np.nonzero(val)[0]
    order = np.argsort(vid[sel], kind="stable")
    m = len(sel)
    rows = sel[order]

    def repacked(buf, live, fill):
        out = np.full_like(buf, fill)
        out[:m] = live
        return out

    new = dataclasses.replace(
        index,
        vectors=index.vectors.at[pid].set(repacked(vec, vec[rows], 0.0)),
        ids=index.ids.at[pid].set(repacked(vid, vid[rows], INVALID_ID)),
        attrs=index.attrs.at[pid].set(repacked(vat, vat[rows], 0.0)),
        valid=index.valid.at[pid].set(
            np.concatenate([np.ones(m, bool),
                            np.zeros(len(val) - m, bool)])),
    )
    if cod is not None:
        new_codes = index.codes.at[pid].set(repacked(cod, cod[rows], 0))
        norms = index.code_norms if index.code_norms is not None \
            else quantize.row_norms(index.qstats, index.codes)
        new = dataclasses.replace(
            new, codes=new_codes,
            code_norms=norms.at[pid].set(
                quantize.row_norms(index.qstats, new_codes[pid])))
    return new


def live_rows(index: IVFIndex):
    """Extract all live rows (main + delta) back to host arrays."""
    val = np.asarray(index.valid)
    vec = np.asarray(index.vectors)[val]
    vid = np.asarray(index.ids)[val]
    vat = np.asarray(index.attrs)[val]
    dval = np.asarray(index.delta.valid)
    if dval.any():
        vec = np.concatenate([vec, np.asarray(index.delta.vectors)[dval]])
        vid = np.concatenate([vid, np.asarray(index.delta.ids)[dval]])
        vat = np.concatenate([vat, np.asarray(index.delta.attrs)[dval]])
    return vec, vid, vat


def full_rebuild(index: IVFIndex,
                 cfg: Optional[IVFConfig] = None
                 ) -> Tuple[IVFIndex, MaintenanceStats]:
    """Re-cluster everything from scratch (the paper's fallback when
    average partition growth crosses the threshold)."""
    cfg = cfg or index.config
    vec, vid, vat = live_rows(index)
    p_max_before = index.p_max
    new = ivf.build_index(vec, vid, vat, cfg=cfg)
    stats = MaintenanceStats(
        kind="full",
        rows_moved=int(len(vec)),
        partitions_touched=int(new.k),
        bytes_written=int(len(vec) * _row_bytes(index) + new.k * new.dim * 4),
        p_max_before=p_max_before, p_max_after=new.p_max)
    return new, stats
