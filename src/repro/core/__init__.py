"""MicroNN core: the paper's contributions C1-C6 as composable JAX modules.

  kmeans       -- Alg. 1: mini-batch balanced k-means (C1)
  ivf          -- index build + padded partition-major device layout (C2)
  query        -- the declarative API: QuerySpec/Q builder + ResultSet
  search       -- Alg. 2: ANN / exact / pre-filter search (C3)
  mqo          -- batch multi-query optimization (C4)
  hybrid       -- predicates, histograms, selectivity estimation (C5)
  optimizer    -- pre/post-filter plan chooser (C5)
  delta        -- streaming upsert / delete via delta-store (C6)
  maintenance  -- incremental flush + full rebuild (C6)
  monitor      -- index-quality tracking + maintenance triggers (C6)
  quantize     -- int8 scalar-quantization tier (codes + rerank contract)
  topk         -- running top-k + cross-device tournament merge
"""
from . import (delta, hybrid, ivf, kmeans, maintenance, monitor, mqo,
               optimizer, quantize, query, search, topk)
from .query import Q, QuerySpec, ResultSet
from .types import (DeltaStore, IVFConfig, IVFIndex, SearchResult,
                    INVALID_ID, pairwise_scores, normalize_if_cosine)

__all__ = [
    "delta", "hybrid", "ivf", "kmeans", "maintenance", "monitor", "mqo",
    "optimizer", "quantize", "query", "search", "topk",
    "Q", "QuerySpec", "ResultSet",
    "DeltaStore", "IVFConfig", "IVFIndex", "SearchResult", "INVALID_ID",
    "pairwise_scores", "normalize_if_cosine",
]
