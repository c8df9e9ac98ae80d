"""Jit'd public wrappers around the Pallas kernels.

`interpret=None` auto-selects by backend (interpret everywhere except a
real TPU -- this container is CPU-only; interpret mode executes the
kernel bodies exactly). On TPU hardware the BlockSpecs/grids are written
for real VMEM tiling and compile natively.

These wrappers are the Pallas backend of core/executor.py's fused scan;
engine code routes through the executor, not through this module.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ivf_scan as _ivf
from . import kmeans_assign as _km
from ..core.types import IVFIndex


@partial(jax.jit, static_argnames=("k_out", "metric", "attr_filter",
                                   "interpret"))
def scan_topk(queries, vectors, valid, ids, part_ids, k_out: int,
              metric: str = "l2", attrs=None, attr_filter=None,
              interpret: Optional[bool] = None):
    """Fused partition-scan + top-k (Alg. 2 hot loop), optional fused
    attribute predicate (paper §3.5)."""
    return _ivf.ivf_scan_topk(queries, vectors, valid, ids, part_ids,
                              k_out, metric=metric, attrs=attrs,
                              attr_filter=attr_filter, interpret=interpret)


@partial(jax.jit, static_argnames=("k_out", "metric", "attr_filter",
                                   "interpret"))
def scan_topk_mqo(queries, vectors, valid, ids, part_ids, qsel,
                  k_out: int, metric: str = "l2", attrs=None,
                  attr_filter=None, interpret: Optional[bool] = None):
    """MQO variant: qsel [Q, n] masks which query wants which partition."""
    return _ivf.ivf_scan_topk(queries, vectors, valid, ids, part_ids,
                              k_out, metric=metric, qsel=qsel, attrs=attrs,
                              attr_filter=attr_filter, interpret=interpret)


@partial(jax.jit, static_argnames=("balance_weight", "target_size",
                                   "tile_k", "interpret"))
def assign_nearest(batch, centroids, counts, *, balance_weight: float = 0.0,
                   target_size: int = 100, scale: float = 1.0,
                   tile_k: int = 256, interpret: Optional[bool] = None):
    """Penalised nearest-centroid assignment (Alg. 1 NEAREST, batch form)."""
    return _km.kmeans_assign(batch, centroids, counts,
                             balance_weight=balance_weight,
                             target_size=target_size, scale=scale,
                             tile_k=tile_k, interpret=interpret)


def index_scan_topk(index: IVFIndex, queries: jax.Array, k_out: int,
                    n_probe: int, interpret: Optional[bool] = None):
    """Kernel-backed Alg. 2 over an IVFIndex (no delta / no filters --
    the full integration lives in core.executor which handles those)."""
    from ..core.executor import find_nearest_centroids
    parts = find_nearest_centroids(index, queries, n_probe)
    # kernel scans one shared probe list; per-query probe sets use the MQO
    # mask over the union
    uniq = parts.reshape(-1)
    return scan_topk(queries, index.vectors, index.valid, index.ids,
                     uniq, k_out, metric=index.config.metric,
                     interpret=interpret)
