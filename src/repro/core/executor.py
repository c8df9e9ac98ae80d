"""Unified query-execution layer: every search is a QueryPlan run by one
fused scan primitive (the repo's single implementation of paper Alg. 2).

Module map -- who builds specs, who runs them:

    core/query.py       the public object model: QuerySpec (frozen,
                        hashable -- the jit cache key) and ResultSet
    core/search.py      kwarg shims: ann_search / exact_search /
                        prefilter_search -> QuerySpec (public API kept)
    core/mqo.py         kwarg shim: mqo_search -> spec with a union cap
    core/optimizer.py   hybrid pre/post spec choice (paper Eqs. 1-3),
                        both arms issued through this executor
    core/rag.py         kNN-LM retrieval -> ANN specs
    storage/engine.py   MicroNN.query(vecs, QuerySpec) -> run()
    distributed/        sharded_index phase 3 calls fused_scan directly
                        on each device's local partition shard
    kernels/ivf_scan.py the Pallas TPU backend of fused_scan
    kernels/sq_scan.py  the Pallas backend of fused_sq_scan (int8 codes,
                        dequantize fused into the distance accumulation)
    storage/pager.py    the partition frame pool behind paged_search
                        (PR 3: disk-resident mode on a memory budget)
    benchmarks/bench_executor.py   backend + plan-cache latency
    benchmarks/bench_quantized.py  int8-vs-f32 recall / memory / latency
    benchmarks/bench_paged.py      resident bytes / recall / latency vs
                                   memory budget; cache hit rates

Quantized two-stage execution (core/quantize.py): on an index carrying
int8 codes, ann/exact plans scan the code tier for k' = rerank_factor * k
candidate rows, then _rerank_float32 rescores exactly those rows at full
precision before the final top-k; prefilter plans and the delta epilogue
stay float32.

Plan model (paper Alg. 2 generalised):
    probe set         part_ids [n]  -- shared partition scan list
    selection mask    qsel [Q, n]   -- which query wants which partition
                                       (MQO §3.4; ANN is the batch union)
    fused predicate   attr_filter   -- compiled hybrid predicate, masked
                                       *before* top-k (§3.5)
    k                 running top-k width (§3.3)
Exact = probe everything; pre-filter = compact qualifying rows into
virtual partitions and probe those (§3.5, cost ~ the gather cap).

Two interchangeable backends execute the same plan shape-identically:
    "pallas"  fused kernel (kernels/ivf_scan.py); interpret mode is
              auto-selected off-TPU
    "xla"     reference path for CPU/GPU -- one shared [n*p_max] matmul
Neither materialises the seed's per-query [Q, n_probe, p_max, d] gather:
the probe union is scanned once and queries mask into it.

Plan/compile cache: the `run` facade buckets the query count to the next
power of two and routes through one jitted entry point whose static
cache key IS the QuerySpec (core/query.py) -- a frozen, structurally
hashable dataclass, so two equal specs (including structurally-equal
predicate trees) provably share one compile-cache entry and a stream of
variable-size batches compiles once per (Q_bucket, spec).
`trace_count()` is the retrace counter; `compile_cache_size()` the
number of live entries -- both surface through MicroNN.stats().
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import quantize
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from .hybrid import compile_filter
from .query import QuerySpec, ResultSet
from .topk import (dedup_by_id, mask_scores, merge_topk,
                   running_topk_init, topk_smallest)
from .types import (EXACT, INVALID_ID, MASKED_SCORE, IVFIndex, PagedIndex,
                    SearchResult, normalize_if_cosine, pairwise_scores,
                    register_dataclass, static_field)

# attr_filter: [..., n_attr] float32 -> [...] bool  (hybrid.compile_filter;
# memoized there so equal predicates are identical objects / cache keys)
AttrFilter = Callable[[jax.Array], jax.Array]

# Retrace counter: incremented each time the jitted entry point actually
# traces. Stable counter == plan-cache hit.
_TRACE_COUNT = 0


def trace_count() -> int:
    return _TRACE_COUNT


def default_backend() -> str:
    """Pallas kernel on real TPU, shape-identical XLA path elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _centroid_scores(centroids, counts, metric, q):
    """[Q, d] -> [Q, k] centroid distances with empty partitions pushed
    out of any probe set (they can never contribute)."""
    cd = pairwise_scores(q, centroids, metric)
    return jnp.where(counts[None, :] > 0, cd, jnp.finfo(cd.dtype).max)


def find_nearest_centroids(index: IVFIndex, q: jax.Array, n_probe: int):
    """[Q, d] -> [Q, n_probe] partition ids (line 3 of Alg. 2)."""
    cd = _centroid_scores(index.centroids, index.counts,
                          index.config.metric, q)
    n_probe = min(n_probe, index.k)
    _, parts = jax.lax.top_k(-cd, n_probe)
    return parts


def _probe_union(centroids, counts, metric, q, n_probe,
                 u_max: Optional[int] = None,
                 qmask: Optional[jax.Array] = None):
    """Shared probe-set + vote/union construction (paper §3.4): the union
    is the u_max most-voted partitions (default covers the batch exactly)
    and `qsel` masks each query back onto its own probes. Used by BOTH
    plan_ann and the paged planner, so the resident and paged scans visit
    partitions in the same order -- the structural half of the paged
    bit-parity contract."""
    kp = centroids.shape[0]
    Q = q.shape[0]
    n_probe = min(n_probe, kp)
    if u_max is None:
        u_max = min(kp, Q * n_probe)
    cd = _centroid_scores(centroids, counts, metric, q)
    _, parts = jax.lax.top_k(-cd, n_probe)                     # [Q, n]
    sel = jnp.zeros((Q, kp), bool).at[
        jnp.arange(Q)[:, None], parts].set(True)               # [Q, kp]
    if qmask is not None:
        sel = sel & qmask[:, None]
    votes = sel.sum(axis=0)                                    # [kp]
    vote_top, upart = jax.lax.top_k(votes, u_max)              # [u_max]
    qsel = jnp.take_along_axis(sel, upart[None, :], axis=1)    # [Q, u_max]
    qsel = qsel & (vote_top > 0)[None, :]
    return upart.astype(jnp.int32), qsel


# ---------------------------------------------------------------------------
# QueryPlan + builders
# ---------------------------------------------------------------------------


@register_dataclass
@dataclasses.dataclass
class QueryPlan:
    """One compiled search: probe set + per-query mask + predicate + k.

    `queries` are already metric-normalised. For kind "prefilter" the probe
    set is replaced by `rows`, a fixed-cap compaction of qualifying row
    indices that execute_plan repacks into virtual partitions.
    """

    queries: jax.Array                    # [Q, d] f32
    part_ids: Optional[jax.Array]         # [n] int32 (None for prefilter)
    qsel: Optional[jax.Array]             # [Q, n] bool (None: all queries)
    rows: Optional[jax.Array]             # [cap] int32 (prefilter only)
    parts_pq: Optional[jax.Array] = None  # [Q, n] int32 (ann_gather only)
    k: int = static_field(default=10)
    kind: str = static_field(default="ann")  # ann | exact | prefilter
    #                                          | ann_gather
    attr_filter: Optional[AttrFilter] = static_field(default=None)


def plan_ann(index: IVFIndex, queries: jax.Array, k: int, n_probe: int,
             attr_filter: Optional[AttrFilter] = None,
             u_max: Optional[int] = None,
             qmask: Optional[jax.Array] = None) -> QueryPlan:
    """ANN / batched-MQO plan: per-query probe sets, shared scan union.

    The union is the u_max most-voted partitions (default covers the whole
    batch exactly: u_max = min(k_parts, Q * n_probe)); `qsel` masks each
    query back onto its own probes -- paper §3.4's partition-major shared
    scan, which is also how single-query ANN avoids a per-query gather.
    `qmask` marks which query rows are real (False rows = bucket padding:
    they cast no votes and select nothing).
    """
    cfg = index.config
    q = normalize_if_cosine(queries.astype(jnp.float32), cfg.metric)
    upart, qsel = _probe_union(index.centroids, index.counts, cfg.metric,
                               q, n_probe, u_max=u_max, qmask=qmask)
    return QueryPlan(queries=q, part_ids=upart, qsel=qsel,
                     rows=None, k=k, kind="ann", attr_filter=attr_filter)


# Largest (bucketed) query count routed to the per-query gather variant.
# Small batches pay more for the shared union's vote/top-k plumbing and
# its n_union = Q * n_probe scan width than a direct [Q, n_probe] gather
# costs (the PR 1 regression on CPU); past ~8 queries probe overlap makes
# the shared union the winner again. The selection is static per
# (spec, Q-bucket), i.e. it lives inside the existing jit cache key.
SMALL_Q_GATHER_MAX = 8


def plan_ann_gather(index: IVFIndex, queries: jax.Array, k: int,
                    n_probe: int,
                    attr_filter: Optional[AttrFilter] = None) -> QueryPlan:
    """Small-Q ANN plan: per-query probe lists, NO shared union.

    Execution gathers each query's own [n_probe, p_max] probe block and
    scores it directly -- the seed's formulation, which beats the shared
    union below SMALL_Q_GATHER_MAX queries on CPU (no vote/top-k union
    plumbing, no scan over other queries' partitions). Same candidate
    set as plan_ann at equal n_probe, so recall is identical, and the
    reported float32 scores are the union scan's bits (`_topk_rescored`)."""
    cfg = index.config
    q = normalize_if_cosine(queries.astype(jnp.float32), cfg.metric)
    parts = find_nearest_centroids(index, q, n_probe)      # [Q, n]
    return QueryPlan(queries=q, part_ids=None, qsel=None, rows=None,
                     parts_pq=parts.astype(jnp.int32), k=k,
                     kind="ann_gather", attr_filter=attr_filter)


def plan_exact(index: IVFIndex, queries: jax.Array, k: int,
               attr_filter: Optional[AttrFilter] = None) -> QueryPlan:
    """Exact plan: probe set = every partition, no selection mask."""
    q = normalize_if_cosine(queries.astype(jnp.float32), index.config.metric)
    return QueryPlan(queries=q,
                     part_ids=jnp.arange(index.k, dtype=jnp.int32),
                     qsel=None, rows=None, k=k, kind="exact",
                     attr_filter=attr_filter)


def plan_prefilter(index: IVFIndex, queries: jax.Array, k: int,
                   attr_filter: AttrFilter, cap: int) -> QueryPlan:
    """Pre-filtering plan (paper §3.5): evaluate the predicate first and
    compact qualifying row indices into a static `cap` budget (the device
    analogue of the SQLite b-tree row-id fetch); execution brute-forces
    over just those rows, so cost scales with predicate selectivity."""
    cfg = index.config
    q = normalize_if_cosine(queries.astype(jnp.float32), cfg.metric)
    kp, p_max, _ = index.vectors.shape
    n_attr = index.attrs.shape[-1]
    ok = index.valid.reshape(-1) & attr_filter(
        index.attrs.reshape(kp * p_max, n_attr))
    (rows,) = jnp.nonzero(ok, size=cap, fill_value=kp * p_max)
    return QueryPlan(queries=q, part_ids=None, qsel=None,
                     rows=rows.astype(jnp.int32), k=k, kind="prefilter",
                     attr_filter=attr_filter)


# ---------------------------------------------------------------------------
# The fused scan primitive (two backends, one shape)
# ---------------------------------------------------------------------------


def fused_scan(
    queries: jax.Array,          # [Q, d] f32 (normalised)
    vectors: jax.Array,          # [kp, p_max, d]
    valid: jax.Array,            # [kp, p_max] bool
    ids: jax.Array,              # [kp, p_max] int32
    part_ids: jax.Array,         # [n] int32 probe list
    k_out: int,
    *,
    metric: str = "l2",
    qsel: Optional[jax.Array] = None,      # [Q, n] bool
    attrs: Optional[jax.Array] = None,     # [kp, p_max, n_attr]
    attr_filter: Optional[AttrFilter] = None,
    backend: Optional[str] = None,         # "pallas" | "xla" | None=auto
) -> Tuple[jax.Array, jax.Array]:
    """Alg. 2 hot loop: stream probed partitions, batched distances,
    running top-k, with the attribute predicate fused before top-k.

    Returns (scores [Q, k_out], ids [Q, k_out]) ascending, rank
    convention (l2 drops the per-query ||q||^2 constant).
    """
    if backend is None:
        backend = default_backend()
    if backend == "pallas":
        from ..kernels import ivf_scan
        return ivf_scan.ivf_scan_topk(
            queries, vectors, valid, ids, part_ids, k_out, metric=metric,
            qsel=qsel, attrs=attrs, attr_filter=attr_filter, interpret=None)
    assert backend == "xla", backend
    return _xla_scan(queries, vectors, valid, ids, part_ids, k_out,
                     metric=metric, qsel=qsel, attrs=attrs,
                     attr_filter=attr_filter)


def _topk_rescored(q, scores, rows, ids, k, metric):
    """Top-k of a scan's [Q, N] scores, each winner then scored again
    from its own (query, row) pair: a multiply and a reduction over d.
    The scan's matmul picks the winners, but its bits depend on how many
    queries and rows share the call; these do not, so the resident union
    scan, the small-Q gather and the paged frame chunks report the same
    float32 score for the same pair. `rows` [N, d] and `ids` [N] are the
    scanned rows (or [Q, N, d] and [Q, N], each query's own)."""
    pos = jnp.broadcast_to(jnp.arange(scores.shape[-1], dtype=jnp.int32),
                           scores.shape)
    s, pos = topk_smallest(scores, pos, k)
    at = jnp.maximum(pos, 0)
    if rows.ndim == 2:
        x, ids = rows[at], ids[at]
    else:
        x = jnp.take_along_axis(rows, at[..., None], axis=1)
        ids = jnp.take_along_axis(ids, at, axis=1)
    dots = jnp.sum(q[:, None, :] * x, axis=-1)
    if metric in ("ip", "cosine"):
        exact = -dots
    else:
        exact = jnp.sum(x * x, axis=-1) - 2.0 * dots
    s = jnp.where(pos == INVALID_ID, s, exact)
    return topk_smallest(s, jnp.where(pos == INVALID_ID, INVALID_ID, ids), k)


def _xla_scan_gathered(queries, pv, pok, pid, k_out, *, metric, qsel=None,
                       pattrs=None, attr_filter=None):
    """Shared core of the XLA reference backends, over the already-
    gathered probe union ([n, p_max, d]): one [Q, d] x [d, n*p_max]
    matmul, predicate + selection masking, top-k, the winners rescored
    pair by pair (`_topk_rescored`)."""
    if attr_filter is not None:
        pok = pok & attr_filter(pattrs)
    n, p_max, d = pv.shape
    flat_v = pv.reshape(n * p_max, d)
    dots = jnp.matmul(queries, flat_v.T, precision=EXACT)   # [Q, n*p_max]
    if metric in ("ip", "cosine"):
        scores = -dots
    else:
        v2 = jnp.sum(flat_v * flat_v, axis=-1)
        scores = v2[None, :] - 2.0 * dots
    ok = jnp.broadcast_to(pok.reshape(1, n * p_max), scores.shape)
    if qsel is not None:
        ok = ok & jnp.repeat(qsel, p_max, axis=1)
    scores = mask_scores(scores, ok)
    return _topk_rescored(queries, scores, flat_v, pid.reshape(-1), k_out,
                          metric)


def _xla_scan(queries, vectors, valid, ids, part_ids, k_out, *, metric,
              qsel=None, attrs=None, attr_filter=None):
    """Shape-identical XLA reference backend: gather the probe union once
    ([n, p_max, d] -- NOT per query), then the shared scan core."""
    return _xla_scan_gathered(
        queries, vectors[part_ids], valid[part_ids], ids[part_ids], k_out,
        metric=metric, qsel=qsel,
        pattrs=None if attr_filter is None else attrs[part_ids],
        attr_filter=attr_filter)


def fused_sq_scan(
    queries: jax.Array,          # [Q, d] f32 (normalised)
    codes: jax.Array,            # [kp, p_max, d] int8
    qstats,                      # quantize.QuantStats
    valid: jax.Array,            # [kp, p_max] bool
    ids: jax.Array,              # [kp, p_max] int32 (flat row ids here)
    part_ids: jax.Array,         # [n] int32 probe list
    k_out: int,
    *,
    metric: str = "l2",
    qsel: Optional[jax.Array] = None,
    attrs: Optional[jax.Array] = None,
    attr_filter: Optional[AttrFilter] = None,
    norms: Optional[jax.Array] = None,   # [kp, p_max] precomputed norms
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Candidate stage of the quantized two-stage search: the fused scan
    over the int8 code tier, with the distance accumulation in the
    INTEGER domain (quantize.fold_queries + int8 x int8 -> int32 matmul
    + rank-1 affine epilogue; see kernels/sq_scan.py). Same plan shape
    as fused_scan; scores are approximate (quantized reconstruction plus
    the query-side fold) and only used to *select* the k_out candidates
    that _rerank_float32 rescores exactly. `norms` is the precomputed
    IVFIndex.code_norms tier; when None (paged frame scans) both
    backends fall back to decode-and-reduce in-scan."""
    if backend is None:
        backend = default_backend()
    if backend == "pallas":
        from ..kernels import sq_scan
        return sq_scan.sq_scan_topk(
            queries, codes, qstats.lo, qstats.scale, valid, ids, part_ids,
            k_out, metric=metric, qsel=qsel, attrs=attrs,
            attr_filter=attr_filter, norms=norms, interpret=None)
    assert backend == "xla", backend
    return _xla_sq_scan(queries, codes, qstats, valid, ids, part_ids, k_out,
                        metric=metric, qsel=qsel, attrs=attrs,
                        attr_filter=attr_filter, norms=norms)


def _int_domain_dots(q_i8, alpha, beta, flat_c):
    """Two-term affine epilogue over [2Q, d] x [m, d] int8 operands:
    (alpha * (q_i8 . c))[:Q] + (alpha * (q_i8 . c))[Q:] + beta, with
    q_i8/alpha in quantize.fold_queries' stacked [q1; q2] form.

    For d <= 1024 the accumulation runs as an f32 gemm over the *cast*
    integer operands: every product (|q_i8| <= 127, |c| <= 128) and every
    partial sum (< 127 * 128 * 1024 < 2^24) is exactly representable in
    f32, so this is bitwise-identical to int32 accumulation -- and much
    faster than XLA's int8 gemm on CPU backends, where int32 matmul units
    don't exist. Wider vectors keep the exact int32 path. The Pallas
    kernel always accumulates in int32 (preferred_element_type) -- the
    actual MXU int8 path -- and holds accumulator values identical to
    this reference; its f32 epilogue agrees to ~1 ulp (the compiler may
    fma-fuse the affine correction differently per program), so candidate
    selection is identical and post-rerank results are bitwise."""
    d = q_i8.shape[-1]
    if d <= 1024:
        acc = jax.lax.dot_general(
            q_i8.astype(jnp.float32), flat_c.astype(jnp.float32),
            (((1,), (1,)), ((), ())), precision=EXACT)
    else:
        acc = jax.lax.dot_general(
            q_i8, flat_c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
    terms = alpha[:, None] * acc                     # [2Q, m]
    q_n = beta.shape[0]
    return terms[:q_n] + terms[q_n:] + beta[:, None]


def _xla_sq_scan(queries, codes, qstats, valid, ids, part_ids, k_out, *,
                 metric, qsel=None, attrs=None, attr_filter=None,
                 norms=None):
    """Shape-identical XLA reference for the int8-domain SQ scan: gather
    the probe union's codes, integer-domain matmul + affine epilogue
    (same fold, same op order as the Pallas kernel -- bitwise parity),
    then the same masking + top-k tail as the float32 reference."""
    q_i8, alpha, beta = quantize.fold_queries(qstats, queries)
    pc = codes[part_ids]                             # [n, p_max, d] int8
    n, p_max, d = pc.shape
    pok = valid[part_ids]
    if attr_filter is not None:
        pok = pok & attr_filter(attrs[part_ids])
    dots = _int_domain_dots(q_i8, alpha, beta, pc.reshape(n * p_max, d))
    if metric in ("ip", "cosine"):
        scores = -dots
    else:
        if norms is not None:
            v2 = norms[part_ids].reshape(n * p_max)
        else:   # paged/hand-built fallback: decode-and-reduce in-scan
            v2 = quantize.row_norms(qstats, pc).reshape(n * p_max)
        scores = v2[None, :] - 2.0 * dots
    ok = jnp.broadcast_to(pok.reshape(1, n * p_max), scores.shape)
    if qsel is not None:
        ok = ok & jnp.repeat(qsel, p_max, axis=1)
    scores = mask_scores(scores, ok)
    pid = ids[part_ids]
    return topk_smallest(
        scores, jnp.broadcast_to(pid.reshape(1, -1), scores.shape), k_out)


def _xla_sq_scan_dequant(queries, codes, qstats, valid, ids, part_ids,
                         k_out, *, metric, qsel=None, attrs=None,
                         attr_filter=None):
    """The pre-int8-domain reference (gather, dequantize to f32, f32
    matmul) -- kept as the recall/latency baseline the int8-domain scan
    is pinned against (tests + benchmarks/bench_quantized.py)."""
    return _xla_scan_gathered(
        queries, quantize.decode(qstats, codes[part_ids]),
        valid[part_ids], ids[part_ids], k_out,
        metric=metric, qsel=qsel,
        pattrs=None if attr_filter is None else attrs[part_ids],
        attr_filter=attr_filter)


# ---------------------------------------------------------------------------
# Plan execution (scan + delta merge + dedup epilogue)
# ---------------------------------------------------------------------------


def _delta_candidates_from(delta, metric: str, q: jax.Array,
                           attr_filter: Optional[AttrFilter]):
    """Delta partition, always scanned (§3.6), in rank convention. Shared
    by the resident and the paged epilogue (the delta stays resident in
    both modes -- it is small and write-hot)."""
    dots = jnp.matmul(q, delta.vectors.T, precision=EXACT)  # [Q, cap]
    if metric in ("ip", "cosine"):
        scores = -dots
    else:
        scores = jnp.sum(delta.vectors * delta.vectors,
                         axis=-1)[None, :] - 2.0 * dots
    ok = delta.valid
    if attr_filter is not None:
        ok = ok & attr_filter(delta.attrs)
    return mask_scores(scores, ok[None, :]), jnp.broadcast_to(
        delta.ids[None, :], scores.shape)


def _delta_candidates(index: IVFIndex, q: jax.Array,
                      attr_filter: Optional[AttrFilter]):
    return _delta_candidates_from(index.delta, index.config.metric, q,
                                  attr_filter)


def _merge_epilogue(delta, metric: str, q, s, i, k: int, k_scan: int,
                    attr_filter: Optional[AttrFilter],
                    qmask: Optional[jax.Array] = None):
    """Shared tail of every search: delta merge + dedup + l2 restore --
    one op sequence for the resident and paged paths (bit-parity)."""
    ds, di = _delta_candidates_from(delta, metric, q, attr_filter)
    if qmask is not None:
        ds = mask_scores(ds, qmask[:, None])
    k_final = min(k, k_scan + ds.shape[-1])
    s, i = merge_topk(s, i, ds, di, k_final)
    s, i = dedup_by_id(s, i)
    if metric == "l2":
        # restore full squared distances (the scan drops the rank-invariant
        # per-query ||q||^2); masked slots stay at the sentinel
        q2 = jnp.sum(q * q, axis=-1, keepdims=True)
        s = jnp.where(i == INVALID_ID, MASKED_SCORE, s + q2)
    return s, i


def _rescore_exact(q, v, got, ids, k_out: int, metric: str):
    """Shared exact-rescore stage of both rerank paths (resident device
    gather and paged disk gather): one op sequence, so XLA emits the same
    floats for both -- the other structural half of paged bit-parity."""
    dots = jnp.einsum("qd,qcd->qc", q, v, precision=EXACT)
    if metric in ("ip", "cosine"):
        s = -dots
    else:
        s = jnp.sum(v * v, axis=-1) - 2.0 * dots
    s = mask_scores(s, got)
    ids = jnp.where(got, ids, INVALID_ID)
    return topk_smallest(s, ids, k_out)


def _rerank_float32(index: IVFIndex, q: jax.Array, rows: jax.Array,
                    k_out: int):
    """Stage 2 of the quantized path: gather the candidate rows' float32
    vectors (the durable-precision tier) and recompute exact distances.

    `rows` are flat row indices (partition * p_max + slot) emitted by the
    SQ scan, INVALID_ID where the scan found fewer than k' candidates.
    Gather cost is O(Q * k' * d) -- independent of the scan width, which
    is the point of scanning codes.
    """
    kp, p_max, d = index.vectors.shape
    total = kp * p_max
    got = rows != INVALID_ID
    r = jnp.clip(rows, 0, total - 1)
    v = index.vectors.reshape(total, d)[r]           # [Q, k', d]
    ids = index.ids.reshape(total)[r]                # [Q, k']
    return _rescore_exact(q, v, got, ids, k_out, index.config.metric)


def execute_plan(index: IVFIndex, plan: QueryPlan,
                 backend: Optional[str] = None,
                 quantized: Optional[bool] = None) -> SearchResult:
    """Run a QueryPlan through the fused scan primitive + delta epilogue.

    `quantized` selects the scan tier on an index carrying int8 codes:
    None (default) auto-uses the codes when present; False forces the
    float32 scan (parity tests / benchmarks); True asserts codes exist.
    The quantized path is two-stage: the SQ scan over-fetches
    k' = rerank_factor * k candidate *rows*, then _rerank_float32
    rescores exactly before the final top-k. Only "ann" plans use the
    code tier: prefilter plans already gather float32 rows, and "exact"
    plans keep their 100%-recall oracle contract (brute force over the
    float32 tier) even on a quantized index.
    """
    cfg = index.config
    q = plan.queries
    kp, p_max, d = index.vectors.shape
    f = plan.attr_filter
    if quantized is None:
        quantized = index.codes is not None
    elif quantized:
        assert index.codes is not None, "quantized=True needs index codes"
    use_sq = quantized and plan.kind in ("ann", "ann_gather")

    if plan.kind == "prefilter":
        # Repack the qualifying rows into virtual partitions so the same
        # primitive scans them; predicate already applied at compaction.
        total = kp * p_max
        got = plan.rows < total
        rows = jnp.minimum(plan.rows, total - 1)
        cap = rows.shape[0]
        vparts = -(-cap // p_max)
        pad = vparts * p_max - cap
        sub_v = jnp.pad(index.vectors.reshape(total, d)[rows],
                        ((0, pad), (0, 0)))
        sub_i = jnp.pad(jnp.where(got, index.ids.reshape(-1)[rows],
                                  INVALID_ID), (0, pad),
                        constant_values=INVALID_ID)
        sub_ok = jnp.pad(got, (0, pad))
        k_scan = min(plan.k, vparts * p_max)
        s, i = fused_scan(
            q, sub_v.reshape(vparts, p_max, d), sub_ok.reshape(vparts, p_max),
            sub_i.reshape(vparts, p_max),
            jnp.arange(vparts, dtype=jnp.int32), k_scan,
            metric=cfg.metric, backend=backend)
    elif plan.kind == "ann_gather":
        # Small-Q specialization: per-query [n_probe, p_max] gather, no
        # shared union (see plan_ann_gather). Quantized indexes still run
        # the two-stage contract: int8-domain gathered scan -> f32 rerank.
        parts = plan.parts_pq                         # [Q, n]
        npb = parts.shape[1]
        pok = index.valid[parts]                      # [Q, n, p_max]
        if f is not None:
            pok = pok & f(index.attrs[parts])
        if use_sq:
            k_cand = min(max(plan.k, plan.k * cfg.rerank_factor),
                         npb * p_max)
            q_i8, alpha, beta = quantize.fold_queries(index.qstats, q)
            # stacked two-term fold ([q1; q2], see fold_queries): expose
            # the term axis so ONE contraction pass over the gathered
            # codes computes both integer products per query
            q_n = q.shape[0]
            qt = q_i8.reshape(2, q_n, d)
            at = alpha.reshape(2, q_n)
            pc = index.codes[parts]                   # [Q, n, p_max, d]
            if d <= 1024:
                acc = jnp.einsum("tqd,qnpd->tqnp", qt.astype(jnp.float32),
                                 pc.astype(jnp.float32),
                                 precision=EXACT)
            else:
                acc = jnp.einsum("tqd,qnpd->tqnp", qt, pc,
                                 preferred_element_type=jnp.int32
                                 ).astype(jnp.float32)
            terms = at[:, :, None, None] * acc        # [2, Q, n, p_max]
            dots = terms[0] + terms[1] + beta[:, None, None]
            if cfg.metric in ("ip", "cosine"):
                scores = -dots
            else:
                v2 = index.code_norms[parts] if index.code_norms is not None \
                    else quantize.row_norms(index.qstats, pc)
                scores = v2 - 2.0 * dots
            scores = mask_scores(scores.reshape(q.shape[0], npb * p_max),
                                 pok.reshape(q.shape[0], npb * p_max))
            # flat row ids (partition * p_max + slot) feed the f32 rerank
            rid = (parts[:, :, None] * p_max
                   + jnp.arange(p_max, dtype=jnp.int32)[None, None, :])
            cand_s, cand_rows = topk_smallest(
                scores, rid.reshape(q.shape[0], npb * p_max), k_cand)
            cand_rows = jnp.where(cand_s >= MASKED_SCORE, INVALID_ID,
                                  cand_rows)
            k_scan = min(plan.k, k_cand)
            s, i = _rerank_float32(index, q, cand_rows, k_scan)
        else:
            pv = index.vectors[parts]                 # [Q, n, p_max, d]
            dots = jnp.einsum("qd,qnpd->qnp", q, pv, precision=EXACT)
            if cfg.metric in ("ip", "cosine"):
                scores = -dots
            else:
                scores = jnp.sum(pv * pv, axis=-1) - 2.0 * dots
            scores = mask_scores(scores.reshape(q.shape[0], npb * p_max),
                                 pok.reshape(q.shape[0], npb * p_max))
            k_scan = min(plan.k, npb * p_max)
            s, i = _topk_rescored(
                q, scores, pv.reshape(q.shape[0], npb * p_max, d),
                index.ids[parts].reshape(q.shape[0], npb * p_max), k_scan,
                cfg.metric)
    elif use_sq:
        # Two-stage quantized search: (1) fused SQ scan over int8 codes
        # selects k' = rerank_factor * k candidate rows; (2) exact f32
        # rerank over just those rows.
        n = plan.part_ids.shape[0]
        k_cand = min(max(plan.k, plan.k * cfg.rerank_factor), n * p_max)
        row_ids = jnp.arange(kp * p_max, dtype=jnp.int32).reshape(kp, p_max)
        cand_s, cand_rows = fused_sq_scan(
            q, index.codes, index.qstats, index.valid, row_ids,
            plan.part_ids, k_cand, metric=cfg.metric, qsel=plan.qsel,
            attrs=index.attrs if f is not None else None,
            attr_filter=f, norms=index.code_norms, backend=backend)
        # fewer than k' qualifying rows: the Pallas running-merge re-emits
        # an already-extracted row id (argmin over an all-MASKED buffer)
        # for the exhausted rounds. The f32 path neutralises those via
        # topk_smallest's score-based invalidation; here the rows feed the
        # rerank directly, so invalidate by score first or the rerank
        # would resurrect them as real (duplicate) candidates.
        cand_rows = jnp.where(cand_s >= MASKED_SCORE, INVALID_ID, cand_rows)
        k_scan = min(plan.k, k_cand)
        s, i = _rerank_float32(index, q, cand_rows, k_scan)
    else:
        n = plan.part_ids.shape[0]
        k_scan = min(plan.k, n * p_max)
        s, i = fused_scan(
            q, index.vectors, index.valid, index.ids, plan.part_ids, k_scan,
            metric=cfg.metric, qsel=plan.qsel,
            attrs=index.attrs if f is not None else None,
            attr_filter=f, backend=backend)

    s, i = _merge_epilogue(index.delta, cfg.metric, q, s, i, plan.k, k_scan,
                           f)
    return SearchResult(ids=i, scores=s)


# ---------------------------------------------------------------------------
# Cached entry point (the engine-facing facade): the QuerySpec IS the key
# ---------------------------------------------------------------------------


def _spec_filter(spec: QuerySpec) -> Optional[AttrFilter]:
    """Spec predicate -> fused filter callable. Predicate trees compile
    through the memoized hybrid.compile_filter (structurally-equal trees
    share one callable); pre-compiled callables pass through."""
    if spec.predicate is None:
        return None
    if callable(spec.predicate):
        return spec.predicate
    return compile_filter(spec.predicate)


@partial(jax.jit, static_argnames=("spec",))
def _run_spec(index, queries, qmask, spec: QuerySpec):
    """THE jitted entry point: its only static argument is the QuerySpec,
    so the spec (plus the query-count bucket and the index pytree
    structure) is the entire compile-cache key -- equal specs share one
    trace by construction."""
    global _TRACE_COUNT
    _TRACE_COUNT += 1          # executes only while tracing
    f = _spec_filter(spec)
    if spec.kind == "exact":
        plan = plan_exact(index, queries, spec.k, f)
    elif f is not None and spec.hybrid == "pre":
        assert spec.cap is not None, \
            "pre-filtering needs a static gather cap: use " \
            "spec.prefilter(cap) or let MicroNN.query size it from the " \
            "selectivity estimate"
        plan = plan_prefilter(index, queries, spec.k, f, spec.cap)
    elif (queries.shape[0] <= SMALL_Q_GATHER_MAX and spec.u_max is None
          and (spec.on_backend or default_backend()) != "pallas"):
        # small (bucketed) batches skip the shared union: the per-query
        # gather variant wins on CPU below ~8 queries (the PR 1
        # regression). Static per (spec, Q-bucket) -- no new cache key
        # dimension, no retrace beyond the existing bucket one.
        plan = plan_ann_gather(index, queries, spec.k, spec.n_probe, f)
    else:
        plan = plan_ann(index, queries, spec.k, spec.n_probe, f,
                        u_max=spec.u_max, qmask=qmask)
    return execute_plan(index, plan, backend=spec.on_backend,
                        quantized=spec.use_quantized)


def compile_cache_size() -> int:
    """Live jit cache entries of the spec entry point (observability:
    MicroNN.stats() reports it next to trace_count())."""
    return int(_run_spec._cache_size())


def _bucket(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def as_query_batch(queries):
    """The [Q, d] float32 query batch, kept where the caller holds it: a
    jax.Array stays on the device, anything else is cast on the host by
    NumPy (the same float32 rounding as a device cast) and crosses to
    the device inside the jitted call, with no eager device op of its
    own."""
    if isinstance(queries, jax.Array):
        return jnp.atleast_2d(jnp.asarray(queries, jnp.float32))
    return np.atleast_2d(np.asarray(queries, np.float32))


def _stage_batch(queries, bucket: bool = True):
    """The query batch padded to its bucket and the mask of its real rows:
    (q [b, d] float32, qmask [b] bool, Q, on_device). A host query is
    padded and masked with NumPy and crosses to the device inside the
    jitted call that takes it; a caller's jax.Array is padded and masked
    on the device (`as_query_batch`). Both give the same float32 bits."""
    q = as_query_batch(queries)
    Q = q.shape[0]
    b = _bucket(Q) if bucket else Q
    on_device = isinstance(q, jax.Array)
    xp = jnp if on_device else np
    if b != Q:
        q = xp.concatenate([q, xp.zeros((b - Q, q.shape[1]), q.dtype)])
    return q, xp.arange(b) < Q, Q, on_device


def _record_resident_spans(tr, index, spec: QuerySpec, Q: int, b: int,
                           compiled: int):
    """Probe/scan/rerank/merge spans for a traced resident query. The one
    jitted call does all four stages on the device, so each is a fused
    marker: counters only, no time (that is in `dispatch` and
    `device_wait`) and no device work of its own. The probe's
    `partitions` is the union bound min(Q*n_probe, k), capped by the
    spec's union cap: exact for Q=1, an upper bound for a batch."""
    kp, p_max, _ = index.vectors.shape
    if spec.kind == "exact":
        n_parts, probe = kp, {"n_probe": kp, "kind": "exact"}
    elif spec.predicate is not None and spec.hybrid == "pre":
        n_parts = 0
        probe = {"rows_cap": int(spec.cap or 0), "kind": "prefilter"}
    else:
        n_probe = min(spec.n_probe, kp)
        n_parts = min(Q * n_probe, kp,
                      kp if spec.u_max is None else spec.u_max)
        probe = {"n_probe": n_probe, "kind": "ann"}
    tr.record(obs_trace.STAGE_PROBE, 0.0, partitions=n_parts, fused=1,
              **probe)
    backend = spec.on_backend or default_backend()
    quantized = spec.use_quantized
    if quantized is None:
        quantized = index.codes is not None
    use_sq = bool(quantized) and spec.kind == "ann" and \
        spec.hybrid != "pre"
    tr.record(obs_trace.STAGE_SCAN, 0.0,
              partitions=n_parts, rows=n_parts * p_max, chunks=1,
              backend=backend, q_bucket=b, quantized=use_sq,
              compiled=compiled, cache_hit=(compiled == 0), fused=1)
    if use_sq:
        rf = index.config.rerank_factor
        tr.record(obs_trace.STAGE_RERANK, 0.0, fused=1, rf=int(rf),
                  candidates=b * min(max(spec.k, spec.k * rf),
                                     n_parts * p_max))
    tr.record(obs_trace.STAGE_MERGE, 0.0, fused=1)


def run(index, queries: jax.Array, spec: QuerySpec, *,
        bucket: bool = True) -> ResultSet:
    """Execute a QuerySpec against a resident IVFIndex or a PagedIndex --
    the single query entry point every public path routes through.

    Resident execution buckets the query count to the next power of two
    (padding queries are masked out of the plan and sliced off the
    result), so the jit cache is keyed on (Q_bucket, spec): a stream of
    variable-size batches compiles once per bucket, and equal specs
    share one entry. `spec.use_quantized` is the scan-tier dimension of
    the key (the index pytree structure -- codes present or not -- is
    itself part of jit's implicit key). Paged execution streams the
    probe set through the frame pool (paged_search).

    Host queries (NumPy, lists) are padded and masked with NumPy and
    cross to the device inside the jitted call, so no eager device op
    runs before it; a caller's jax.Array stays on the device and is
    padded and masked there (`as_query_batch`). Both give the same
    float32 bits, the same avals and so the same trace.

    A traced resident run does exactly the device work of an untraced
    one: the host stages `stage_in` and `dispatch` are timed around the
    same calls, and the fused stages get counter-only spans.
    """
    if isinstance(index, PagedIndex):
        if spec.predicate is not None and spec.hybrid == "pre":
            raise ValueError(
                "paged mode fuses predicates into the frame scan "
                "(post-filtering); pre-filtering needs the resident "
                "float32 tier")
        if spec.u_max is not None:
            # refuse rather than silently diverge: a capped union changes
            # which partitions are scanned, and the paged probe union is
            # pinned to the resident plan_ann ordering (bit-parity)
            raise ValueError(
                "union_cap is not supported in paged mode (the paged "
                "probe union mirrors the resident plan exactly)")
        return paged_search(
            index, queries, k=spec.k, kind=spec.kind,
            n_probe=spec.n_probe, attr_filter=_spec_filter(spec),
            backend=spec.on_backend, quantized=spec.use_quantized,
            spec=spec)
    tr = obs_trace.current()
    with obs_trace.stage(obs_trace.STAGE_STAGE_IN, tr) as st:
        q, qmask, Q, on_device = _stage_batch(queries, bucket)
        b = q.shape[0]
        (_C_STAGED_DEVICE if on_device else _C_STAGED_HOST).inc()
        st.set(staged="device" if on_device else "host")
    tc0 = _TRACE_COUNT
    with obs_trace.stage(obs_trace.STAGE_DISPATCH, tr):
        res = _run_spec(index, q, qmask, spec)
    if tr is not None:
        _record_resident_spans(tr, index, spec, Q, b, _TRACE_COUNT - tc0)
    if b != Q:
        res = SearchResult(ids=res.ids[:Q], scores=res.scores[:Q])
    return ResultSet.of(res, spec)


def run_coalesced(index, chunks, spec: QuerySpec):
    """Batch-split entry point for cross-request micro-batching (the
    serving front door): concatenate per-caller query chunks that share
    one spec, execute a SINGLE bucketed `run()` -- one fused scan, one
    jit cache entry per (Q_bucket, spec) -- and split the ResultSet back
    into per-caller slices.

    Bit-parity contract: per-query scores are elementwise (each query
    masks onto its OWN probe set inside the shared union), so a caller's
    slice of the coalesced result carries exactly the ids + scores its
    solo `run()` would have returned -- pinned by tests/test_frontdoor
    and the gather-vs-union parity tests."""
    assert len(chunks) >= 1, "run_coalesced needs at least one chunk"
    qs = [as_query_batch(c) for c in chunks]
    sizes = [int(q.shape[0]) for q in qs]
    if len(qs) == 1:
        return [run(index, qs[0], spec)]
    on_device = any(isinstance(q, jax.Array) for q in qs)
    rs = run(index, (jnp if on_device else np).concatenate(qs, axis=0),
             spec)
    return rs.split(sizes)


def search(
    index: IVFIndex,
    queries: jax.Array,
    *,
    k: int,
    kind: str = "ann",                 # ann | exact | prefilter
    n_probe: int = 8,
    u_max: Optional[int] = None,       # MQO union cap (None: exact union)
    cap: Optional[int] = None,         # prefilter gather budget
    attr_filter: Optional[AttrFilter] = None,
    backend: Optional[str] = None,
    quantized: Optional[bool] = None,  # None: auto (codes present)
    bucket: bool = True,
) -> ResultSet:
    """Kwarg-style shim over the QuerySpec entry point (API compat).

    Builds the equivalent spec and routes through `run`, so repeated
    calls with equal kwargs -- or a hand-built equal spec -- share the
    same compile-cache entry.
    """
    if kind == "prefilter":
        assert cap is not None, "kind='prefilter' needs a static cap " \
            "(the optimizer sizes it from the selectivity estimate)"
        assert attr_filter is not None, "kind='prefilter' needs attr_filter"
    pred = None if attr_filter is None else \
        getattr(attr_filter, "predicate", attr_filter)
    spec = QuerySpec(
        kind="exact" if kind == "exact" else "ann", k=k, n_probe=n_probe,
        u_max=u_max, cap=cap, predicate=pred,
        hybrid="pre" if kind == "prefilter" else
        ("post" if pred is not None else "auto"),
        use_quantized=quantized, on_backend=backend)
    return run(index, queries, spec, bucket=bucket)


# ---------------------------------------------------------------------------
# Paged execution (PR 3): scan the memory-budgeted frame pool instead of a
# full-resident tier; the rerank gathers f32 rows from the durable store.
# ---------------------------------------------------------------------------
#
# A PagedIndex (core/types.py) keeps only metadata resident; the scan tier
# is faulted on demand into a storage/pager.PartitionCache. Execution is
# host-driven: (1) pick the probe set from the resident centroids with the
# SAME vote/union ordering as plan_ann -- this is what pins paged-vs-
# resident parity bit-for-bit -- in one compiled call (_paged_plan) that
# also stages the query and the running top-k; (2) fault each probe chunk (<= pool
# capacity) and run the fused scan over the pool with *frame* indices as
# the scalar-prefetched probe list (the frame -> partition indirection --
# both kernels are layout-agnostic, they just stream whichever blocks the
# probe list names); (3) merge chunk top-k's associatively (streaming scan:
# an exact search over a 1 GB tier runs in a 10 MB pool); (4) on a
# quantized index, gather the k' = rerank_factor * k candidate rows from
# SQLite (_rerank_from_store) and rescore at exact f32 -- the float32 tier
# is never materialised; (5) the resident-delta merge + dedup epilogue.


@partial(jax.jit, static_argnames=("k_out", "metric"))
def _paged_rerank(q, v, got, cand, *, k_out, metric):
    """Jitted rescore stage of the paged rerank: literally _rescore_exact
    (the resident rerank's core), so the reported scores are bit-identical
    to the resident path's -- XLA compiles the identical-shape expression
    the same way in both programs."""
    return _rescore_exact(q, v, got, cand, k_out, metric)


def _rerank_from_store(store, q: jax.Array, cand_ids: jax.Array,
                       k_out: int, metric: str):
    """Sibling of _rerank_float32 for the paged path: gather exactly the
    candidate rows' float32 vectors from the durable SQLite tier (batched
    IN (...) -- the disk analogue of the device gather) and recompute
    exact distances. `cand_ids` are *asset* ids ([Q, k'], INVALID_ID
    holes) -- paged frames carry asset ids, and the durable tier is keyed
    by them. Disk-gather cost is O(unique candidates), independent of the
    scan width, which is the point of scanning codes."""
    tr = obs_trace.current()
    with obs_trace.stage(obs_trace.STAGE_RERANK, tr) as st:
        cand = np.asarray(cand_ids)
        got = cand != INVALID_ID
        Q, kc = cand.shape
        d = store.dim
        v = np.zeros((Q, kc, d), np.float32)
        n_uniq = 0
        if got.any():
            uniq = np.unique(cand[got])
            n_uniq = int(uniq.size)
            rows, found = store.vectors_for(uniq)
            rows = np.asarray(normalize_if_cosine(
                jnp.asarray(rows, jnp.float32), metric))
            idx = np.searchsorted(uniq, np.where(got, cand, uniq[0]))
            idx = np.clip(idx, 0, len(uniq) - 1)
            got = got & (uniq[idx] == cand) & found[idx]
            v[got] = rows[idx[got]]
        out = _paged_rerank(q, jnp.asarray(v), jnp.asarray(got),
                            jnp.asarray(cand), k_out=k_out, metric=metric)
        if tr is not None:
            jax.block_until_ready(out[0])
            st.set(candidates=Q * kc, rows_gathered=n_uniq, k_out=k_out)
    return out


def _run_width(k_want: int, n_parts: int, p_max: int) -> int:
    """Width of the paged running top-k: the k' the scan is asked for,
    capped by the rows the probe list can hold."""
    return min(k_want, max(n_parts * p_max, 1))


# Retrace counter of the paged planner (`_paged_plan`), kept apart from
# _TRACE_COUNT, which counts `_run_spec` compiles only.
_PAGED_PLAN_TRACE_COUNT = 0


def paged_plan_trace_count() -> int:
    return _PAGED_PLAN_TRACE_COUNT


@partial(jax.jit, static_argnames=("n_probe", "metric", "k_want", "p_max"))
def _paged_plan(queries, qmask, centroids, counts, *, n_probe, metric,
                k_want, p_max):
    """The paged planner as one compiled call, from the staged query batch
    to the probe list: the metric-normalised query, the query mask,
    plan_ann's probe union over the resident centroids -- literally
    _probe_union, shared with plan_ann, so paged and resident searches
    agree on the probe order -- and the empty running top-k buffers.
    `counts` are the live rows per partition as the host holds them, an
    argument of every call: no device copy can go stale under a write."""
    global _PAGED_PLAN_TRACE_COUNT
    _PAGED_PLAN_TRACE_COUNT += 1      # executes only while tracing
    q = normalize_if_cosine(queries, metric)
    upart, qsel = _probe_union(centroids, counts, metric, q, n_probe,
                               qmask=qmask)
    run_s, run_i = running_topk_init(
        q.shape[:1], _run_width(k_want, upart.shape[0], p_max))
    return q, qmask, upart, qsel, run_s, run_i


@partial(jax.jit, static_argnames=("k", "k_scan", "metric", "attr_filter"))
def _paged_epilogue(q, s_m, i_m, delta, qmask, *, k, k_scan, metric,
                    attr_filter):
    """Jitted wrapper over _merge_epilogue (execute_plan's shared tail):
    bit-parity with the resident path by construction."""
    return _merge_epilogue(delta, metric, q, s_m, i_m, k, k_scan,
                           attr_filter, qmask=qmask)


# Double-buffered fault pipeline (PR 6): while the fused scan chews on
# chunk N, a single worker thread STAGES chunk N+1 -- the SQLite fetch +
# host-side block packing (PartitionCache.stage) -- so the disk latency
# overlaps the scan and the next fault() only pays the frame scatter.
# Staging takes no frames, no pins, and never rebinds a pool, so the
# chunking is identical to the serial loop and results are bit-identical
# by construction (same probe order, same per-chunk top-k merge). Set
# False to force the serial fetch->scan loop (the before/after axis of
# bench_paged.py).
PAGED_PREFETCH = True

_PREFETCHER = None


def _prefetcher():
    global _PREFETCHER
    if _PREFETCHER is None:
        from concurrent.futures import ThreadPoolExecutor
        _PREFETCHER = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="micronn-prefetch")
    return _PREFETCHER


@partial(jax.jit, static_argnames=("k_out", "metric", "backend",
                                   "attr_filter"))
def _scan_frames(q, payload, valid, ids, frame_ids, qsel, attrs, *,
                 k_out, metric, backend, attr_filter):
    """Jitted frame-pool scan chunk (f32 payload): the fused kernel runs
    over the pool with frame indices as its probe list."""
    return fused_scan(q, payload, valid, ids, frame_ids, k_out,
                      metric=metric, qsel=qsel, attrs=attrs,
                      attr_filter=attr_filter, backend=backend)


@partial(jax.jit, static_argnames=("k_out", "metric", "backend",
                                   "attr_filter"))
def _scan_frames_sq(q, payload, qstats, valid, ids, frame_ids, qsel, attrs,
                    *, k_out, metric, backend, attr_filter):
    """Jitted frame-pool scan chunk (int8 payload + fused dequantize)."""
    return fused_sq_scan(q, payload, qstats, valid, ids, frame_ids, k_out,
                         metric=metric, qsel=qsel, attrs=attrs,
                         attr_filter=attr_filter, backend=backend)


def paged_search(
    pindex,
    queries: jax.Array,
    *,
    k: int,
    kind: str = "ann",                 # ann | exact
    n_probe: int = 8,
    attr_filter: Optional[AttrFilter] = None,
    backend: Optional[str] = None,
    quantized: Optional[bool] = None,
    spec: Optional[QuerySpec] = None,  # carried onto the ResultSet
) -> ResultSet:
    """Run a search against a PagedIndex through the budgeted frame pool.

    The probe union is processed in chunks of at most the pool's frame
    capacity: each chunk is faulted (pinned), scanned by the fused kernel
    over the pool, unpinned, and its top-k merged into the running result
    -- so resident scan-tier bytes never exceed the budget even for an
    exact scan of the whole collection. Hybrid predicates are fused into
    the frame scan (the cache carries attrs frames); a quantized index
    scans int8 frames and reranks candidates straight from SQLite.
    """
    cfg = pindex.config
    cache = pindex.cache
    p_max = cache.p_max
    # the pool payload dictates the scan: an int8 pool can only run the SQ
    # scan (there are no f32 frames to brute-force -- paged "exact" on a
    # quantized index scans every partition's codes and reranks, a
    # full-probe near-oracle rather than the resident f32 oracle)
    use_sq = pindex.cache.payload == "int8"
    if quantized is not None:
        assert quantized == use_sq, \
            f"paged scan tier is fixed by the frame pool payload " \
            f"({pindex.cache.payload}); cannot force quantized={quantized}"
    k_want = max(k, k * cfg.rerank_factor) if use_sq else k

    tr = obs_trace.current()
    with obs_trace.stage(obs_trace.STAGE_PROBE, tr) as st:
        q, qmask, Q, on_device = _stage_batch(queries)
        b = q.shape[0]
        if kind == "exact":
            q = normalize_if_cosine(jnp.asarray(q), cfg.metric)
            qmask = jnp.asarray(qmask)
            counts = np.asarray(pindex.counts)
            upart = np.nonzero(counts > 0)[0]
            qsel = jnp.broadcast_to(qmask[:, None], (b, len(upart)))
            k_run = _run_width(k_want, len(upart), p_max)
            run_s, run_i = running_topk_init((b,), k_run)
        else:
            assert kind == "ann", kind
            # one compiled call from the staged query to the probe list;
            # the host fetches only the partition ids it must fault
            q, qmask, upart, qsel, run_s, run_i = _paged_plan(
                q, qmask, pindex.centroids,
                np.asarray(pindex.counts, np.int32), n_probe=n_probe,
                metric=cfg.metric, k_want=k_want, p_max=p_max)
            upart = np.asarray(upart, np.int64)
            k_run = run_s.shape[1]
            _C_PLANS_FUSED.inc()
        n = len(upart)
        if tr is not None:
            st.set(partitions=int(n), n_probe=int(n_probe), kind=kind,
                   fused=int(kind != "exact"),
                   staged="device" if on_device else "host")

    if attr_filter is not None:
        assert cache.attrs_pool is not None, \
            "attribute predicate needs an attr-backed frame pool " \
            "(store built with n_attr > 0)"
    # Scan-resistant admission (ROADMAP open item): a paged exact search
    # reads every partition exactly once, so admitting its stream would
    # flush the hot ANN working set out of the pool. Exact faults run
    # with admit=False -- they cycle through a small reusable scan ring
    # inside the pool (budget unchanged) -- and chunk to the ring size.
    admit = kind != "exact"
    ring = cache.capacity if admit else cache.scan_frames
    chunk = ring
    # Double-buffering: while the fused scan chews on chunk N, the worker
    # thread STAGES chunk N+1 -- the SQLite fetch + host block packing
    # land in the pager's staging dict (PartitionCache.stage), so the
    # next fault() only pays the frame scatter. Staging takes no frames
    # and no pins, so chunking is unchanged (results trivially
    # bit-identical with prefetch off) and the fault keeps its donated
    # in-place scatter (no foreign pins outstanding). Single-chunk probe
    # lists keep the serial path -- nothing to overlap.
    prefetch = PAGED_PREFETCH and n > chunk
    starts = list(range(0, n, chunk))
    pending = None          # in-flight stage future for the next chunk
    try:
        for ci_, s in enumerate(starts):
            cpids = upart[s:s + chunk]
            if pending is not None:
                try:
                    pending.result()    # staged blocks ready to consume
                except Exception:
                    pass                # advisory: fault() re-reads SQLite
                pending = None
            frames = cache.fault(cpids, admit=admit)
            if prefetch and ci_ + 1 < len(starts):
                s2 = starts[ci_ + 1]
                pending = _prefetcher().submit(
                    cache.stage, upart[s2:s2 + chunk])
            try:
                # read the pools AFTER fault(): the batched scatter rebinds
                # them (functional .at[].set), so a reference captured
                # before the fault would scan stale frame contents. A
                # concurrent prefetch fault may rebind them again, but the
                # current chunk's frames are pinned, so every binding holds
                # identical contents for them (copy-on-write scatter).
                attrs_pool = cache.attrs_pool if attr_filter is not None \
                    else None
                fidx = jnp.asarray(frames.astype(np.int32))
                cq = qsel[:, s:s + chunk]
                k_chunk = min(k_run, len(cpids) * p_max)
                with obs_trace.stage(obs_trace.STAGE_SCAN, tr) as st:
                    if use_sq:
                        cs, ci = _scan_frames_sq(
                            q, cache.payload_pool, pindex.qstats,
                            cache.valid_pool, cache.ids_pool, fidx, cq,
                            attrs_pool, k_out=k_chunk, metric=cfg.metric,
                            backend=backend, attr_filter=attr_filter)
                    else:
                        cs, ci = _scan_frames(
                            q, cache.payload_pool, cache.valid_pool,
                            cache.ids_pool, fidx, cq, attrs_pool,
                            k_out=k_chunk, metric=cfg.metric,
                            backend=backend, attr_filter=attr_filter)
                    if tr is not None:
                        jax.block_until_ready(cs)
                        st.set(chunks=1, partitions=len(cpids),
                               rows=len(cpids) * p_max,
                               backend=backend or default_backend(),
                               quantized=use_sq, q_bucket=b)
            finally:
                cache.unpin(frames)
            run_s, run_i = merge_topk(run_s, run_i, cs, ci, k_run)
    finally:
        if pending is not None:     # scan raised: let the stage land (it
            try:                    # holds no pins; entries age out)
                pending.result()
            except Exception:
                pass

    if use_sq:
        # the frame scan emits asset ids; invalidate re-emitted rows from
        # exhausted merge rounds by score (as execute_plan does), then
        # gather + rescore the survivors from the durable tier
        cand = jnp.where(run_s >= MASKED_SCORE, INVALID_ID, run_i)
        k_scan = min(k, k_run)
        s_m, i_m = _rerank_from_store(cache.store, q, cand, k_scan,
                                      cfg.metric)
    else:
        k_scan = k_run if n else 0
        s_m, i_m = (run_s, run_i) if n else (
            jnp.zeros((b, 0), jnp.float32), jnp.zeros((b, 0), jnp.int32))

    with obs_trace.stage(obs_trace.STAGE_MERGE, tr) as st:
        s_f, i_f = _paged_epilogue(q, s_m, i_m, pindex.delta, qmask,
                                   k=k, k_scan=k_scan, metric=cfg.metric,
                                   attr_filter=attr_filter)
        if tr is not None:
            jax.block_until_ready(s_f)
            st.set(k=int(k), k_scan=int(k_scan), fused=0)
    if b != Q:
        s_f, i_f = s_f[:Q], i_f[:Q]
    return ResultSet(ids=i_f, scores=s_f, spec=spec)


# -- registry wiring (PR 8): the compile-cache instruments surface through
# the process metrics registry next to the pager / front door / scheduler,
# so one snapshot carries the whole telemetry state.
_OBS = obs_metrics.default_registry().scope(component="executor")
_OBS.gauge("trace_count", fn=trace_count)
_OBS.gauge("compile_cache_size", fn=compile_cache_size)
# resident `run` calls, by where the query batch was padded and masked
_C_STAGED_HOST = _OBS.counter("queries_staged_host")
_C_STAGED_DEVICE = _OBS.counter("queries_staged_device")
# paged ANN runs planned by the one compiled call, and its compiles
_C_PLANS_FUSED = _OBS.counter("paged_plans_fused")
_OBS.gauge("paged_plan_trace_count", fn=paged_plan_trace_count)
