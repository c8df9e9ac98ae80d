"""Per-dimension int8 scalar quantization (the paper's low-memory tier).

The paper's headline memory number (top-100 @ 90% recall in <7 ms using
~10 MB on a million-scale benchmark) relies on scanning *compact codes*
and reranking a small candidate set at full precision. This module is the
code side of that design:

  * training: per-dimension min/max over the stored vectors (streamed from
    the durable tier -- never the full dataset in memory), giving an
    asymmetric affine code  c = round((x - lo) / scale) - 128  in int8;
  * `encode` / `decode` are pure jittable maps; encoding is deterministic,
    so re-encoding a row always reproduces the stored code (maintenance
    relies on this when it moves rows between tiers);
  * `QuantStats` is a pytree carried on `IVFIndex`, so the quantized index
    remains one jit-compatible value (the stats ride along with the codes
    through updates, flushes and sharding).

Distance contract (asymmetric distance computation, Faiss-style): queries
stay float32, codes are dequantized in-register inside the scan kernel
(kernels/sq_scan.py) and distances accumulate in float32. The scan
over-fetches `k' = rerank_factor * k` candidates; core/executor.py then
recomputes exact float32 distances for just those rows (the rerank stage)
before the final top-k -- recall loss from quantization is confined to
candidate *selection*, never to the reported scores.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .types import EXACT, normalize_if_cosine, register_dataclass

# Number of representable levels: codes span [-128, 127] <-> [0, 255].
LEVELS = 255
# Guard against zero-width dimensions (constant columns).
MIN_SCALE = 1e-12


@register_dataclass
@dataclasses.dataclass
class QuantStats:
    """Per-dimension affine int8 quantizer parameters (a pytree)."""

    lo: jax.Array      # [d] f32 -- per-dimension minimum
    scale: jax.Array   # [d] f32 -- (hi - lo) / LEVELS, floored at MIN_SCALE

    @property
    def dim(self) -> int:
        return self.lo.shape[0]


def train(X: jax.Array) -> QuantStats:
    """Fit per-dimension min/max stats from a [n, d] sample.

    The caller is responsible for metric normalisation (cosine indexes
    store L2-normalised rows, so stats must be trained on those).
    """
    X = jnp.asarray(X, jnp.float32)
    if X.shape[0] == 0:
        return QuantStats(lo=jnp.zeros((X.shape[1],), jnp.float32),
                          scale=jnp.ones((X.shape[1],), jnp.float32))
    lo = X.min(axis=0)
    hi = X.max(axis=0)
    scale = jnp.maximum((hi - lo) / LEVELS, MIN_SCALE)
    return QuantStats(lo=lo, scale=scale)


def train_from_store(store, metric: str = "l2",
                     batch_size: int = 4096) -> QuantStats:
    """Streaming min/max over the durable tier (storage.VectorStore) --
    one pass of `iter_batches`, never the full dataset in host memory."""
    lo: Optional[np.ndarray] = None
    hi: Optional[np.ndarray] = None
    for batch in store.iter_batches(batch_size):
        b = np.asarray(
            normalize_if_cosine(jnp.asarray(batch, jnp.float32), metric))
        blo, bhi = b.min(axis=0), b.max(axis=0)
        lo = blo if lo is None else np.minimum(lo, blo)
        hi = bhi if hi is None else np.maximum(hi, bhi)
    if lo is None:
        lo = np.zeros((store.dim,), np.float32)
        hi = lo
    scale = np.maximum((hi - lo) / LEVELS, MIN_SCALE)
    return QuantStats(lo=jnp.asarray(lo, jnp.float32),
                      scale=jnp.asarray(scale, jnp.float32))


def encode(stats: QuantStats, x: jax.Array) -> jax.Array:
    """[..., d] float32 -> [..., d] int8 codes (deterministic round)."""
    q = jnp.round((jnp.asarray(x, jnp.float32) - stats.lo) / stats.scale)
    return (jnp.clip(q, 0, LEVELS) - 128).astype(jnp.int8)


def decode(stats: QuantStats, codes: jax.Array) -> jax.Array:
    """[..., d] int8 codes -> [..., d] float32 reconstruction."""
    return (codes.astype(jnp.float32) + 128.0) * stats.scale + stats.lo


def encode_np(stats: QuantStats, x: np.ndarray) -> np.ndarray:
    """Host-side encode (used by the pack/repack maintenance paths)."""
    return np.asarray(encode(stats, jnp.asarray(x, jnp.float32)))


def fold_queries(stats: QuantStats, q: jax.Array):
    """Fold f32 queries into the int8 distance domain (the MXU scan's
    query-side preparation, done ONCE per scan).

    The dequantized dot against a code row c expands as

        q . v = q . ((c + 128) * scale + lo)
              = (q * scale) . c + 128 * sum(q * scale) + q . lo

    so with w = q * scale the whole affine correction collapses to a
    rank-1 epilogue around the integer product w~ . c. The query weights
    are encoded in TWO int8 terms (primary + residual):

        q1 = round(w * 127 / A1),  A1 = max|w|
        q2 = round(r * 127 / A2),  r = w - (A1/127) q1, A2 = max|r|
        w~ = alpha1 q1 + alpha2 q2,  alpha_i = A_i / 127

    The residual term costs one extra row per query in the (bandwidth-
    bound) int8 matmul but drops the query-side rounding error from
    ~2^-8 to ~2^-15 relative -- small enough that candidate selection
    matches the dequantize-then-f32 scan on real data (the recall pin at
    rerank_factor=1), while the arithmetic stays pure int8 x int8 on the
    MXU. The epilogue is then

        q . v ~= alpha1 (q1 . c) + alpha2 (q2 . c) + beta,
        beta  = 128 (alpha1 sum(q1) + alpha2 sum(q2)) + q . lo.

    Returns the STACKED form consumed by the scan backends:
    (q_i8 [2Q, d] int8 = [q1; q2], alpha [2Q] f32 = [alpha1; alpha2],
    beta [Q] f32). Consumers compute acc = q_i8 . c as one [2Q, m]
    integer matmul and reduce dots = (alpha * acc)[:Q] + (alpha *
    acc)[Q:] + beta. Both scan backends call this one helper, so they
    fold identical values by construction.
    """
    q = jnp.asarray(q, jnp.float32)
    w = q * stats.scale[None, :]                       # [Q, d]
    a1 = jnp.maximum(jnp.max(jnp.abs(w), axis=-1), MIN_SCALE)  # [Q]
    q1 = jnp.round(w * (127.0 / a1[:, None])).astype(jnp.int8)
    alpha1 = a1 / 127.0
    r = w - alpha1[:, None] * q1.astype(jnp.float32)   # rounding residual
    a2 = jnp.maximum(jnp.max(jnp.abs(r), axis=-1), MIN_SCALE)
    q2 = jnp.round(r * (127.0 / a2[:, None])).astype(jnp.int8)
    alpha2 = a2 / 127.0
    q_i8 = jnp.concatenate([q1, q2], axis=0)           # [2Q, d]
    alpha = jnp.concatenate([alpha1, alpha2], axis=0)  # [2Q]
    beta = 128.0 * (alpha1 * jnp.sum(q1.astype(jnp.float32), axis=-1)
                    + alpha2 * jnp.sum(q2.astype(jnp.float32), axis=-1)) \
        + jnp.matmul(q, stats.lo, precision=EXACT)
    return q_i8, alpha, beta


@jax.jit
def row_norms(stats: QuantStats, codes: jax.Array) -> jax.Array:
    """[..., p, d] int8 codes -> [..., p] f32 squared reconstruction norms
    ||decode(c)||^2 -- the l2 scan's per-row constant, precomputed once at
    (re)pack time so the int8-domain scan never re-decodes the code tier
    (IVFIndex.code_norms). The in-scan fallback (paged frames) computes
    the same decode-then-reduce expression, so the two agree bitwise.
    Jitted so the decode fuses into the reduction: a whole tier's f32
    decode would hold four times the codes' bytes in device memory."""
    v = decode(stats, codes)
    return jnp.sum(v * v, axis=-1)


def stats_to_arrays(stats: QuantStats):
    return np.asarray(stats.lo, np.float32), np.asarray(stats.scale, np.float32)


def stats_from_arrays(lo: np.ndarray, scale: np.ndarray) -> QuantStats:
    return QuantStats(lo=jnp.asarray(lo, jnp.float32),
                      scale=jnp.asarray(scale, jnp.float32))
