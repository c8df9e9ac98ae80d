"""Core datatypes for the MicroNN index.

The device-resident index is a pytree of fixed-shape arrays (TPU requires
static shapes). The paper's disk-resident layout (SQLite rows clustered by
partition id) maps to a partition-major padded tensor layout:

    vectors [k, p_max, d]   -- partition-major, padded to p_max per partition
    ids     [k, p_max]      -- asset ids, -1 marks padding / tombstones
    valid   [k, p_max]      -- live-row mask (False = padding or deleted)
    counts  [k]             -- live rows per partition

The delta-store (paper §3.6: "a reserved partition identifier") is carried
as a separate fixed-capacity block scanned by every query.

Balanced clustering (Alg. 1) bounds p_max, which bounds padding waste --
on TPU the paper's balance constraint is load-bearing for the memory
roofline, not just tail latency (see DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

# Distances are "smaller is better" throughout. L2 uses squared distance;
# ip/cosine negate the dot product. Cosine vectors are L2-normalised at
# ingest so cosine == ip on the stored data.
METRICS = ("l2", "ip", "cosine")

# Sentinel id for padding / tombstoned rows.
INVALID_ID = -1
# Score assigned to masked rows so they never enter a top-k.
MASKED_SCORE = jnp.finfo(jnp.float32).max
# Contract precision of every f32 dot whose result is reported or ranks
# the exact answer. A TPU's DEFAULT is one bf16 pass (~4e-3 relative);
# HIGHEST keeps those dots f32-exact, as the CPU backend computes them.
EXACT = jax.lax.Precision.HIGHEST


def register_dataclass(cls):
    """Register a dataclass as a JAX pytree, splitting data vs meta fields."""
    data = [f.name for f in dataclasses.fields(cls) if not f.metadata.get("static")]
    meta = [f.name for f in dataclasses.fields(cls) if f.metadata.get("static")]
    jax.tree_util.register_dataclass(cls, data_fields=data, meta_fields=meta)
    return cls


def static_field(**kw):
    return dataclasses.field(metadata={"static": True}, **kw)


@register_dataclass
@dataclasses.dataclass
class IVFConfig:
    """Index construction / search configuration (paper §3.1, §3.3)."""

    dim: int = static_field(default=128)
    metric: str = static_field(default="l2")
    target_partition_size: int = static_field(default=100)  # paper default
    minibatch_size: int = static_field(default=256)
    kmeans_iters: int = static_field(default=20)
    balance_weight: float = static_field(default=1.0)  # lambda in NEAREST penalty
    balanced_final_assign: bool = static_field(default=False)  # beyond-paper knob
    delta_capacity: int = static_field(default=1024)
    # Partition padding granularity; p_max is rounded up to a multiple of
    # this so Pallas tiles stay MXU-aligned.
    pad_to: int = static_field(default=8)
    # Rebuild trigger: fraction growth of mean partition size (paper: 0.5).
    rebuild_growth_threshold: float = static_field(default=0.5)
    # Scalar-quantization tier: "none" keeps the float32-only index;
    # "int8" adds per-dimension SQ codes scanned by kernels/sq_scan.py
    # with a float32 rerank over k' = rerank_factor * k candidates
    # (core/quantize.py).
    quantize: str = static_field(default="none")  # "none" | "int8"
    rerank_factor: int = static_field(default=4)
    seed: int = static_field(default=0)


# Rows of the largest partition a build leaves (maintenance.split_to_bar):
# what the paged int8 frame scan can tile. With no resident norms it
# decodes each probed frame in-kernel, and the compiled decode takes about
# 17 KB of scoped VMEM per padded row, whatever d: a v5e's 16 MiB holds
# p_max 896 at d = 128 and overflows at 928 (16.15 MiB at k_out 1,024),
# and the cliff wanders with d (832 overflows at d = 960, 800 at 1,536).
# 768 compiles for d = 128 to 1,536 and k_out up to 1,024
# (tests/test_tpu_compile.py).
FRAME_ROWS_MAX = 768


def effective_pad_to(cfg: "IVFConfig", backend: Optional[str] = None) -> int:
    """Dtype-aware Pallas tile padding for the partition axis.

    Real TPU hardware tiles int8 at a (32, 128) minimum, so a compiled SQ
    scan needs p_max to be a multiple of 32; float32 tiles at (8, 128) and
    interpret mode has no constraint. `backend` defaults to the runtime
    backend, so CPU/GPU tests keep the configured (small) padding while a
    TPU run of a quantized index is bumped automatically."""
    if backend is None:
        backend = jax.default_backend()
    if cfg.quantize == "int8" and backend == "tpu":
        return max(cfg.pad_to, 32)
    return cfg.pad_to


@register_dataclass
@dataclasses.dataclass
class DeltaStore:
    """Fixed-capacity staging area for streaming inserts (paper §3.6)."""

    vectors: jax.Array  # [cap, d]
    ids: jax.Array      # [cap] int32, INVALID_ID where empty
    attrs: jax.Array    # [cap, n_attr] float32
    valid: jax.Array    # [cap] bool
    count: jax.Array    # [] int32 -- number of live rows
    # int8 SQ codes mirroring `vectors`, present iff the owning index is
    # quantized (encoded at insert, moved verbatim by flush_delta).
    codes: Optional[jax.Array] = None  # [cap, d] int8

    @property
    def capacity(self) -> int:
        return self.vectors.shape[0]

    @staticmethod
    def empty(cap: int, dim: int, n_attr: int,
              quantized: bool = False) -> "DeltaStore":
        return DeltaStore(
            vectors=jnp.zeros((cap, dim), jnp.float32),
            ids=jnp.full((cap,), INVALID_ID, jnp.int32),
            attrs=jnp.zeros((cap, n_attr), jnp.float32),
            valid=jnp.zeros((cap,), bool),
            count=jnp.zeros((), jnp.int32),
            codes=jnp.zeros((cap, dim), jnp.int8) if quantized else None,
        )


@register_dataclass
@dataclasses.dataclass
class IVFIndex:
    """Device-resident IVF index state (paper Fig. 2 schema, tensorised)."""

    centroids: jax.Array   # [k, d] float32
    csizes: jax.Array      # [k] int32 -- kmeans running counts (for updates)
    vectors: jax.Array     # [k, p_max, d] float32
    ids: jax.Array         # [k, p_max] int32
    attrs: jax.Array       # [k, p_max, n_attr] float32
    valid: jax.Array       # [k, p_max] bool
    counts: jax.Array      # [k] int32 live rows per partition
    delta: DeltaStore
    # Mean partition size at last (re)build -- the monitor compares the
    # current mean against this to trigger rebuilds (paper §3.6).
    base_mean_size: jax.Array  # [] float32
    # Scalar-quantization tier (config.quantize == "int8"): per-row int8
    # codes mirroring `vectors` plus the per-dimension quantizer stats
    # (core/quantize.QuantStats pytree). None on a float32-only index.
    codes: Optional[jax.Array] = None   # [k, p_max, d] int8
    qstats: Optional[Any] = None        # quantize.QuantStats
    # Precomputed ||decode(codes)||^2 per row (quantize.row_norms) -- the
    # l2 epilogue constant of the int8-domain scan. Invariant: whenever
    # `codes` is present and mutated, code_norms is recomputed alongside
    # it, so code_norms == quantize.row_norms(qstats, codes) always holds
    # (kernels read it instead of re-decoding the code tier per query).
    code_norms: Optional[jax.Array] = None  # [k, p_max] f32
    # Per-partition drift state (paper §3.6 / LIRE-style local repair):
    # cumulative centroid displacement since the partition was last
    # (re)clustered, accumulated by maintenance.running_mean_update and
    # reset by split/merge/local_recluster and rebuilds. The monitor
    # compares it against the centroid spacing to queue "recluster" work
    # for partitions whose running mean has wandered from their rows.
    # None on hand-assembled indexes (treated as zero drift).
    drift: Optional[jax.Array] = None   # [k] float32
    config: IVFConfig = static_field(default_factory=IVFConfig)

    @property
    def k(self) -> int:
        return self.centroids.shape[0]

    @property
    def p_max(self) -> int:
        return self.vectors.shape[1]

    @property
    def dim(self) -> int:
        return self.centroids.shape[1]

    @property
    def n_attr(self) -> int:
        return self.attrs.shape[-1]

    @property
    def quantized(self) -> bool:
        return self.codes is not None

    def num_live(self) -> jax.Array:
        # delta.count is the write cursor; valid tracks live rows
        return self.counts.sum() + self.delta.valid.sum()


@dataclasses.dataclass
class PagedIndex:
    """Memory-budgeted *paged* view of the index (the paper's actual
    disk-resident mode): only metadata is resident -- centroids, csizes,
    live counts, the delta store, and the quantizer stats. The scan tier
    (int8 codes when quantized, float32 vectors otherwise) stays in SQLite
    and is faulted on demand into a storage/pager.PartitionCache frame
    pool; core/executor.paged_search drives fault -> frame scan -> disk
    rerank. Deliberately NOT a jax pytree: execution is host-driven and
    the cache is a stateful host object."""

    centroids: jax.Array       # [k, d] float32
    csizes: jax.Array          # [k] float32 (kmeans running counts)
    counts: Any                # [k] int64 host array -- live rows/partition
    delta: DeltaStore          # resident staging area (small, fixed cap)
    cache: Any                 # storage.pager.PartitionCache
    base_mean_size: float
    qstats: Optional[Any] = None    # quantize.QuantStats (int8 mode)
    # Per-partition drift state (host array, same signal as IVFIndex.drift;
    # session-local -- recovery starts it at zero).
    drift: Any = None               # [k] float32 np.ndarray
    config: IVFConfig = dataclasses.field(default_factory=IVFConfig)

    @property
    def k(self) -> int:
        return self.centroids.shape[0]

    @property
    def dim(self) -> int:
        return self.centroids.shape[1]

    @property
    def p_max(self) -> int:
        return self.cache.p_max

    @property
    def n_attr(self) -> int:
        return self.delta.attrs.shape[-1]

    @property
    def quantized(self) -> bool:
        return self.qstats is not None and self.cache.payload == "int8"

    def num_live(self):
        return int(self.counts.sum()) + int(self.delta.valid.sum())


@register_dataclass
@dataclasses.dataclass
class SearchResult:
    """Top-k result batch. ids are INVALID_ID where fewer than k matches."""

    ids: jax.Array        # [Q, K] int32
    scores: jax.Array     # [Q, K] float32 (smaller is better)


def normalize_if_cosine(x: jax.Array, metric: str) -> jax.Array:
    if metric == "cosine":
        n = jnp.linalg.norm(x, axis=-1, keepdims=True)
        return x / jnp.maximum(n, 1e-12)
    return x


def pairwise_scores(q: jax.Array, v: jax.Array, metric: str) -> jax.Array:
    """[Q, d] x [N, d] -> [Q, N] scores, smaller is better.

    L2 uses the matmul expansion ||q-v||^2 = ||q||^2 + ||v||^2 - 2 q.v so the
    MXU does the heavy lifting (paper §3.3's SIMD batching, TPU-native).
    """
    dots = jnp.matmul(q, v.T, precision=EXACT)
    if metric in ("ip", "cosine"):
        return -dots
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)
    v2 = jnp.sum(v * v, axis=-1)
    return q2 + v2[None, :] - 2.0 * dots
