"""Distributed MicroNN: the paper's ANN search at pod scale.

Index layout on the production mesh (DESIGN.md §6):
  * centroids        replicated (small -- the paper scans them anyway)
  * partitions       [k, p_max, d] sharded on k over the `model` axis
  * queries          sharded over the data axes, replicated over `model`

Search is Alg. 2 run as 4 SPMD phases inside one `shard_map`:
  1. local centroid scoring        [Q, k/m] matmul per device
  2. global top-n probe selection  log-depth tournament over `model`
     (exact: the union of per-device candidates contains the global top-n)
  3. owned-partition scan          each device issues a local plan to the
     unified executor's fused scan primitive (core/executor.fused_scan)
     over the probed partitions it owns (fixed-cap probe list,
     selection-masked) -- the same primitive as single-device search
  4. global top-k result merge     hypercube tournament over `model`
     (the paper's parallel heap merge, on ICI)

Collective bytes per query batch: phase 2 moves n ids+scores per device,
phase 4 moves k results per device -- both O(log m) rounds; partition data
never crosses devices. That locality is the paper's disk-efficiency
argument transplanted to ICI.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import executor
from ..core import topk as topk_lib
from ..core.query import Q, QuerySpec, ResultSet
from ..core.types import (EXACT, IVFIndex, SearchResult,
                          normalize_if_cosine)


def index_shardings(index: IVFIndex, mesh: Mesh, model_axis: str = "model"):
    """NamedShardings for an IVFIndex pytree: partitions over `model`."""
    m = model_axis

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    from ..core.types import DeltaStore

    # The template must mirror the index's pytree structure: the quantized
    # tier (codes on the model axis next to the vectors, small qstats
    # replicated) is present iff the index carries it.
    quantized = isinstance(index, IVFIndex) and index.codes is not None
    qstats_ns = None
    if quantized:
        qstats_ns = jax.tree.map(lambda _: ns(None), index.qstats)
    return IVFIndex(
        centroids=ns(m, None),
        csizes=ns(m),
        vectors=ns(m, None, None),
        ids=ns(m, None),
        attrs=ns(m, None, None),
        valid=ns(m, None),
        counts=ns(m),
        delta=DeltaStore(
            vectors=ns(None, None), ids=ns(None), attrs=ns(None, None),
            valid=ns(None), count=ns(),
            codes=ns(None, None) if quantized else None),
        base_mean_size=ns(),
        codes=ns(m, None, None) if quantized else None,
        qstats=qstats_ns,
        code_norms=ns(m, None) if quantized else None,
        drift=None if index.drift is None else ns(m),
        config=index.config if not isinstance(index, IVFIndex) else
        index.config,
    )


def distributed_query(
    index: IVFIndex,
    queries: jax.Array,              # [Q, d] sharded over data axes
    spec: QuerySpec,
    mesh: Mesh,
    *,
    data_axes: Tuple[str, ...] = ("data",),
    model_axis: str = "model",
    local_cap: Optional[int] = None,
    merge: str = "tournament",       # tournament | allgather
) -> ResultSet:
    """Exact-distributed Alg. 2 driven by a QuerySpec (bitwise same
    results as single-device ann_search up to float association,
    validated in tests) -- the sharded route of the declarative query
    API. The per-device phase-3 scan merges into a global top-k, which
    is exactly ResultSet.merge()'s reduction run on ICI instead of host.
    """
    assert spec.kind == "ann", "sharded execution serves ANN specs " \
        "(exact = n_probe >= k_partitions)"
    assert spec.predicate is None, \
        "sharded hybrid predicates are not wired yet (ROADMAP)"
    # refuse what the sharded path cannot honor rather than silently
    # diverging from the same spec run through executor.run
    assert spec.u_max is None and spec.cap is None, \
        "union_cap/prefilter are not supported in sharded execution"
    assert spec.use_quantized in (None, False), \
        "sharded scan is float32 (no sharded code tier yet)"
    assert spec.on_backend in (None, "xla"), \
        "shard_map bodies run the XLA backend"
    cfg = index.config
    k, n_probe = spec.k, spec.n_probe

    def local(centroids, csizes, vectors, ids, attrs, valid, counts,
              dvec, dids, dattrs, dvalid, dcount, base, q):
        del csizes, attrs, dattrs, base
        me = jax.lax.axis_index(model_axis)
        k_local = vectors.shape[0]
        # worst case: every probe of the local batch lands on this shard
        cap = local_cap or min(k_local, q.shape[0] * n_probe)
        q = normalize_if_cosine(q.astype(jnp.float32), cfg.metric)

        # -- phase 1: local centroid scores --------------------------------
        from ..core.types import pairwise_scores
        cd = pairwise_scores(q, centroids, cfg.metric)       # [Q, k_local]
        cd = jnp.where(counts[None, :] > 0, cd, jnp.finfo(jnp.float32).max)
        n_local = min(n_probe, k_local)
        local_s, local_i = jax.lax.top_k(-cd, n_local)
        local_s = -local_s
        gids = (local_i + me * k_local).astype(jnp.int32)

        # -- phase 2: global top-n probe ids --------------------------------
        if merge == "tournament":
            gs, gi = topk_lib.tournament_merge(local_s, gids, n_probe,
                                               model_axis)
        else:
            gs, gi = topk_lib.allgather_merge(local_s, gids, n_probe,
                                              model_axis)

        # -- phase 3: scan owned probed partitions --------------------------
        mine = (gi // k_local) == me                          # [Q, n]
        # other shards' probes point past the end and are dropped (mapped
        # to local partition 0 they raced its own probe in the scatter)
        lid = jnp.where(mine, gi % k_local, k_local)
        # fixed-cap compaction of this device's probe list over the batch
        want = jnp.zeros((k_local,), bool).at[lid.reshape(-1)].set(
            True, mode="drop")
        (plist,) = jnp.nonzero(want, size=cap, fill_value=0)
        # the fill repeats partition 0: only the first want.sum() are real
        pvalid_probe = jnp.arange(cap) < want.sum()

        # per-query selection: query q wants local partition plist[j]?
        sel = (gi[:, None, :] == (plist[None, :, None] + me * k_local)
               ).any(-1) & mine.any(-1, keepdims=True)        # [Q, cap]

        # local plan -> the unified fused scan primitive (XLA backend:
        # shard_map bodies are already device-local XLA; scores stay in
        # the executor's rank convention, which is rank-equal)
        k_scan = min(k, cap * vectors.shape[1])
        ls0, li0 = executor.fused_scan(
            q, vectors, valid, ids, plist, k_scan, metric=cfg.metric,
            qsel=sel & pvalid_probe[None, :], backend="xla")

        # delta partition: replicated, scanned once on shard 0 of the axis
        ddots = jnp.matmul(q, dvec.T, precision=EXACT)
        dsc = -ddots if cfg.metric in ("ip", "cosine") else \
            jnp.sum(dvec * dvec, -1)[None] - 2.0 * ddots
        dok = dvalid[None, :] & (me == 0)
        dsc = jnp.where(dok, dsc, jnp.finfo(jnp.float32).max)

        ls, li = topk_lib.merge_topk(
            ls0, li0, dsc, jnp.broadcast_to(dids[None], dsc.shape),
            min(k, k_scan + dsc.shape[-1]))
        ls = jnp.where(li < 0, jnp.finfo(jnp.float32).max, ls)

        # -- phase 4: global result merge ------------------------------------
        if merge == "tournament":
            fs, fi = topk_lib.tournament_merge(ls, li, k, model_axis)
        else:
            fs, fi = topk_lib.allgather_merge(ls, li, k, model_axis)
        return fs, fi

    dp = P(data_axes if len(data_axes) > 1 else data_axes[0], None)
    mp = model_axis
    in_specs = (
        P(mp, None), P(mp), P(mp, None, None), P(mp, None),
        P(mp, None, None), P(mp, None), P(mp),
        P(None, None), P(None), P(None, None), P(None), P(),
        P(), dp,
    )
    fs, fi = jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=(dp, dp),
        check_vma=False,
    )(index.centroids, index.csizes, index.vectors, index.ids, index.attrs,
      index.valid, index.counts, index.delta.vectors, index.delta.ids,
      index.delta.attrs, index.delta.valid, index.delta.count,
      index.base_mean_size, queries)
    return ResultSet(ids=fi, scores=fs, spec=spec)


def distributed_search(
    index: IVFIndex,
    queries: jax.Array,
    k: int,
    n_probe: int,
    mesh: Mesh,
    **kwargs,
) -> ResultSet:
    """Kwarg shim over distributed_query (API compat)."""
    return distributed_query(index, queries, Q.knn(k=k, n_probe=n_probe),
                             mesh, **kwargs)
