"""ANN / exact KNN search (paper Alg. 2): kwarg shims over QuerySpecs.

The public object model lives in core/query.py: a frozen `QuerySpec`
built with the fluent `Q` builder is THE query representation (and the
executor's jit cache key), and `ResultSet` is the typed result every
path returns. The entry points here survive as thin shims that compile
their arguments into a spec and hand it to `executor.run`, which builds
the QueryPlan (probe set + per-query selection mask + optional fused
attribute predicate + k) and runs one fused scan primitive on either the
Pallas TPU kernel or the shape-identical XLA reference backend.

Faithful structure (now encoded as specs -> plans):
  1. scan centroids, pick the n nearest partitions          (FindNearestCentroids)
  2. always include the delta partition                     (§3.6)
  3. scan chosen partitions, batched distance via matmul    (SIMD -> MXU)
  4. maintain top-k (heaps -> masked running top-k buffers) (§3.3)
  5. merge + sort final results

Attribute post-filtering is fused *before* the top-k, reproducing the
paper's optimization: "vectors in the requested partitions that don't
satisfy the predicate filter are filtered before being considered in the
top-K computation" (§3.5) -- folded into the scan's per-slot id stream
on the Pallas backend.
"""
from __future__ import annotations

from typing import Optional

import jax

from . import executor
from .executor import AttrFilter, find_nearest_centroids  # noqa: F401 (re-export)
from .query import Q, QuerySpec, ResultSet  # noqa: F401 (re-export)
from .types import INVALID_ID, IVFIndex

import jax.numpy as jnp


def ann_search(
    index: IVFIndex,
    queries: jax.Array,            # [Q, d]
    k: int,
    n_probe: int,
    attr_filter: Optional[AttrFilter] = None,
    backend: Optional[str] = None,
) -> ResultSet:
    """Alg. 2 as an ANN spec: per-query probe sets scanned as one shared
    union with a selection mask (no per-query partition gather)."""
    spec = Q.knn(k=k, n_probe=n_probe).backend(backend)
    if attr_filter is not None:
        spec = spec.where(attr_filter).postfilter()
    return executor.run(index, queries, spec)


def exact_search(
    index: IVFIndex,
    queries: jax.Array,
    k: int,
    attr_filter: Optional[AttrFilter] = None,
    backend: Optional[str] = None,
) -> ResultSet:
    """Brute-force KNN over every live row (paper: 'trivial but resource
    intensive'); also the 100%-recall oracle for tests/benchmarks.
    Spec: kind "exact" -- probe set = all partitions, no selection mask."""
    spec = Q.exact(k=k).backend(backend)
    if attr_filter is not None:
        spec = spec.where(attr_filter)
    return executor.run(index, queries, spec)


def prefilter_search(
    index: IVFIndex,
    queries: jax.Array,
    k: int,
    attr_filter: AttrFilter,
    cap: int,
    backend: Optional[str] = None,
) -> ResultSet:
    """Pre-filtering spec (paper §3.5): evaluate the predicate first, fetch
    only qualifying rows, brute-force over that subset (100% recall).

    `cap` is the static gather budget; the optimizer sizes it from the
    selectivity estimate (x safety margin). Cost scales with `cap`, i.e.
    with predicate selectivity -- matching the paper's latency behaviour.
    """
    spec = Q.knn(k=k).where(attr_filter).prefilter(cap).backend(backend)
    return executor.run(index, queries, spec)


def recall_at_k(approx, exact, k: int) -> jax.Array:
    """recall@k: |approx top-k  ∩  exact top-k| / k (paper's metric)."""
    a = approx.ids[:, :k]
    e = exact.ids[:, :k]
    hits = (a[:, :, None] == e[:, None, :]) & (a[:, :, None] != INVALID_ID)
    # denominator: number of real results in the exact set (handles tiny dbs)
    denom = jnp.maximum((e != INVALID_ID).sum(-1), 1)
    return (hits.any(-1).sum(-1) / denom).mean()
