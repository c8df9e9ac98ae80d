"""Fused IVF partition scan + running top-k Pallas TPU kernel.

The paper's hot loop (Alg. 2 lines 4-10): stream the n probed partitions,
compute query-to-vector distances, keep a running top-k. The TPU-native
realisation (DESIGN.md §2):

  * HBM -> VMEM streaming via *scalar-prefetched* partition ids: the
    vectors' BlockSpec index_map reads `part_ids[i]` so only the probed
    partitions ever leave HBM -- the analogue of "only read probed pages
    from disk";
  * distances on the MXU: scores = ||v||^2 - 2 q.v as one [Q,d]x[d,p_max]
    matmul per grid step (the paper's SIMD batch, on a systolic array) at
    f32 contract precision, so reported scores are f32-exact;
  * the per-thread heap becomes a VMEM running top-k scratch, merged with
    each tile via K rounds of masked min-extraction (a heap has no
    vector-unit analogue; K-round selection keeps everything in VREGs --
    a production kernel could swap in a bitonic partial sort, same
    semantics). The rounds use iota-compare masks only (no gather or
    scatter, which Mosaic does not lower), and a tile none of whose rows
    beats any query's current k-th score skips the merge;
  * the MQO variant takes a per-(query, partition) selection mask, giving
    the batch path (paper §3.4) the same single-pass-over-HBM property;
  * attribute-filter fusion (paper §3.5): a compiled predicate is
    evaluated over the probed partitions' attrs and folded, with the
    live-row mask, into the per-slot id stream the kernel reads next to
    each partition -- rows are "filtered before being considered in the
    top-K computation", never materialised as a separate candidate set.

Per-slot metadata (ids, selection, norms) is gathered for the probe list
in the wrapper and handed to the kernel probe-major as [n, 1, p_max] and
[n, tiles, 1, qt] blocks, which satisfy the TPU's (8, 128) block tiling
rule by spanning the array's two minor dimensions; only the vectors (and
int8 codes) stream through the scalar-prefetched index map.

Grid: (query tile, probed partition). A query tile holds at most
QUERY_TILE rows, so the per-step VMEM -- the double-buffered p_max x d
block plus the running top-k and merge temporaries of one tile -- stays
inside the 16 MiB scoped limit at the d=960 gist width
(tests/test_tpu_compile.py); larger batches re-stream the probed
partitions once per tile.

`interpret` is auto-selected from the runtime backend (interpret mode
everywhere except real TPU); callers can still force it either way.
This module is the Pallas backend of core/executor.py -- the engine
never calls it directly.

Frame-indirect entry (storage/pager.py): the paged executor passes the
pager's frame *pool* [F, p_max, d] as `vectors` and frame indices as
`part_ids` -- the scalar-prefetched index_map streams whichever blocks
the probe list names, so a 10 MB pool serves the same kernel that a
full-resident tier does (HBM traffic stays "probed frames only").
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASKED = jnp.finfo(jnp.float32).max
# f32 tiles are 8 sublanes deep: compiled kernels pad the query block to it
F32_SUBLANE_MIN = 8
# Largest query tile a grid step holds: at 32 rows the running top-k and
# the merge temporaries stay inside the 16 MiB scoped VMEM limit up to
# p_max=512 at d=960 (tests/test_tpu_compile.py); larger batches add a
# query-tile grid axis that re-streams the probed partitions per tile.
QUERY_TILE = 32


def _merge_topk(run_s, run_i, cand_s, cand_i, k_out: int):
    """K rounds of masked min-extraction merging candidates into the
    running buffer. run_*: [Q, K]; cand_*: [Q, C].

    Equivalent to argmin-extraction over concat([run, cand]) with
    argmin's lowest-index tie rule (run slots before candidate slots,
    lower slots first), written with iota-compare masks so it lowers
    without gather, scatter or an unaligned lane concatenation."""
    q_n = run_s.shape[0]
    big = jnp.int32(2 ** 30)
    col_r = jax.lax.broadcasted_iota(jnp.int32, run_s.shape, 1)
    col_c = jax.lax.broadcasted_iota(jnp.int32, cand_s.shape, 1)
    col_o = jax.lax.broadcasted_iota(jnp.int32, (q_n, k_out), 1)

    def body(j, carry):
        rs, cs, out_s, out_i = carry
        m = jnp.minimum(jnp.min(rs, axis=1, keepdims=True),
                        jnp.min(cs, axis=1, keepdims=True))      # [Q, 1]
        am_r = jnp.min(jnp.where(rs == m, col_r, big), axis=1, keepdims=True)
        am_c = jnp.min(jnp.where(cs == m, col_c, big), axis=1, keepdims=True)
        in_run = am_r < big
        hit_r = (col_r == am_r) & in_run
        hit_c = (col_c == am_c) & jnp.logical_not(in_run)
        mid = jnp.where(
            in_run,
            jnp.sum(jnp.where(hit_r, run_i, 0), axis=1, keepdims=True),
            jnp.sum(jnp.where(hit_c, cand_i, 0), axis=1, keepdims=True))
        slot = col_o == j
        out_s = jnp.where(slot, m, out_s)
        out_i = jnp.where(slot, mid, out_i)
        return (jnp.where(hit_r, MASKED, rs), jnp.where(hit_c, MASKED, cs),
                out_s, out_i)

    out_s = jnp.full((q_n, k_out), MASKED, jnp.float32)
    out_i = jnp.full((q_n, k_out), -1, jnp.int32)
    _, _, out_s, out_i = jax.lax.fori_loop(
        0, k_out, body, (run_s, cand_s, out_s, out_i))
    return out_s, out_i


def merge_tile(run_s, run_i, scores, ok, ids_row, k_out: int):
    """Shared per-step tail of both scan kernels: mask the tile, then fold
    it into the running top-k scratch unless no row of it beats any
    query's current k-th score (the merge would return the buffer
    unchanged: a candidate equal to the k-th loses argmin's tie rule)."""
    scores = jnp.where(ok, scores, MASKED)
    cand_i = jnp.where(scores >= MASKED, -1,
                       jnp.broadcast_to(ids_row, scores.shape))
    kth = run_s[:, k_out - 1:k_out]                      # [Q, 1]
    beats = jnp.max(jnp.where(scores < kth, 1, 0))

    @pl.when(beats > 0)
    def _merge():
        new_s, new_i = _merge_topk(run_s[...], run_i[...], scores, cand_i,
                                   k_out)
        run_s[...] = new_s
        run_i[...] = new_i


def probe_ids(valid, ids, part_ids, attrs=None, attr_filter=None):
    """[n, 1, p_max] int32 id stream of the probed partitions: the row id
    where the row is live and passes the fused predicate, -1 elsewhere
    (ids of live rows are non-negative: -1 is the INVALID_ID sentinel)."""
    ok = valid[part_ids] != 0
    if attr_filter is not None:
        assert attrs is not None, "attr_filter needs the attrs tensor"
        ok = ok & attr_filter(attrs[part_ids].astype(jnp.float32))
    return jnp.where(ok, ids[part_ids], -1).astype(jnp.int32)[:, None, :]


def qsel_rows(qsel, q_pad: int, qt: int):
    """[Q, n] selection mask -> [n, q_pad // qt, 1, qt] int32: one lane row
    per (probed partition, query tile); padding queries select nothing."""
    q_n, n = qsel.shape
    rows = jnp.pad(qsel.astype(jnp.int32), [(0, q_pad - q_n), (0, 0)])
    return rows.T.reshape(n, q_pad // qt, 1, qt)


def selected(qsel_row):
    """[1, qt] selection row -> [qt, 1] bool column, by an iota-diagonal
    lane reduction (no transpose or dynamic lane slice)."""
    qt = qsel_row.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (qt, qt), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (qt, qt), 1))
    return jnp.sum(jnp.where(eye, qsel_row, 0), axis=1, keepdims=True) != 0


def query_tiling(q_n: int, sublanes: int):
    """(q_pad, qt): pad the query count to the dtype's sublane tile and
    split it into tiles of at most QUERY_TILE rows, which bounds the
    per-step VMEM (running top-k, merge temporaries) whatever the batch
    size. Interpret mode runs the same grid as the chip."""
    q_pad = -(-q_n // sublanes) * sublanes
    qt = min(q_pad, QUERY_TILE)
    return -(-q_pad // qt) * qt, qt


def _scan_kernel(part_ids_ref,               # scalar prefetch [n]
                 *refs, k_out: int, metric: str, mqo: bool):
    del part_ids_ref                 # consumed by the vectors' index_map
    if mqo:
        q_ref, v_ref, ids_ref, qsel_ref, out_s_ref, out_i_ref, \
            run_s, run_i = refs
    else:
        q_ref, v_ref, ids_ref, out_s_ref, out_i_ref, run_s, run_i = refs
    i = pl.program_id(1)
    n = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        run_s[...] = jnp.full_like(run_s, MASKED)
        run_i[...] = jnp.full_like(run_i, -1)

    q = q_ref[...].astype(jnp.float32)               # [qt, d]
    v = v_ref[0].astype(jnp.float32)                 # [p_max, d]
    dots = jax.lax.dot_general(q, v, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    if metric == "l2":
        v2 = jnp.sum(v * v, axis=-1)
        scores = v2[None, :] - 2.0 * dots
    else:
        scores = -dots
    ids_row = ids_ref[0]                             # [1, p_max]
    ok = ids_row != -1
    if mqo:
        ok = ok & selected(qsel_ref[0, 0])           # [qt, 1]
    merge_tile(run_s, run_i, scores, ok, ids_row, k_out)

    @pl.when(i == n - 1)
    def _out():
        out_s_ref[...] = run_s[...]
        out_i_ref[...] = run_i[...]


def default_interpret() -> bool:
    """Interpret everywhere except a real TPU backend (auto-selection)."""
    return jax.default_backend() != "tpu"


def ivf_scan_topk(
    queries: jax.Array,          # [Q, d]
    vectors: jax.Array,          # [k, p_max, d]
    valid: jax.Array,            # [k, p_max] bool/int8
    ids: jax.Array,              # [k, p_max] int32
    part_ids: jax.Array,         # [n] int32 -- partitions to stream
    k_out: int,
    metric: str = "l2",
    qsel: Optional[jax.Array] = None,   # [Q, n] bool (MQO mask)
    attrs: Optional[jax.Array] = None,  # [k, p_max, n_attr] f32
    attr_filter=None,                   # compiled predicate (hybrid.py)
    interpret: Optional[bool] = None,   # None: auto by backend
) -> Tuple[jax.Array, jax.Array]:
    if interpret is None:
        interpret = default_interpret()
    kp, p_max, d = vectors.shape
    q_n = queries.shape[0]
    n = part_ids.shape[0]
    part_ids = part_ids.astype(jnp.int32)
    mqo = qsel is not None
    q_pad, qt = query_tiling(q_n, F32_SUBLANE_MIN)
    if q_pad != q_n:
        queries = jnp.pad(queries, [(0, q_pad - q_n), (0, 0)])

    in_specs = [
        pl.BlockSpec((qt, d), lambda b, i, pids: (b, 0)),
        pl.BlockSpec((1, p_max, d), lambda b, i, pids: (pids[i], 0, 0)),
        pl.BlockSpec((1, 1, p_max), lambda b, i, pids: (i, 0, 0)),
    ]
    inputs = [queries, vectors,
              probe_ids(valid, ids, part_ids, attrs, attr_filter)]
    if mqo:
        in_specs.append(pl.BlockSpec((1, 1, 1, qt),
                                     lambda b, i, pids: (i, b, 0, 0)))
        inputs.append(qsel_rows(qsel, q_pad, qt))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q_pad // qt, n),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((qt, k_out), lambda b, i, pids: (b, 0)),
            pl.BlockSpec((qt, k_out), lambda b, i, pids: (b, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((qt, k_out), jnp.float32),
            pltpu.VMEM((qt, k_out), jnp.int32),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(_scan_kernel, k_out=k_out, metric=metric, mqo=mqo),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((q_pad, k_out), jnp.float32),
            jax.ShapeDtypeStruct((q_pad, k_out), jnp.int32),
        ],
        interpret=interpret,
        name="ivf_scan_topk",
    )
    out_s, out_i = kernel(part_ids, *inputs)
    if q_pad != q_n:
        out_s, out_i = out_s[:q_n], out_i[:q_n]
    return out_s, out_i
