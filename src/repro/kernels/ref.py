"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests).

Scores follow the ranking convention of core/: smaller is better, and the
L2 path drops the per-query ||q||^2 constant (rank-invariant), i.e.
    score(q, v) = ||v||^2 - 2 q.v          (l2)
    score(q, v) = -q.v                     (ip / cosine on normalised data)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MASKED = jnp.finfo(jnp.float32).max


def scores_ref(q: jax.Array, v: jax.Array, metric: str) -> jax.Array:
    """q: [Q, d], v: [N, d] -> [Q, N]."""
    dots = jnp.matmul(q.astype(jnp.float32), v.astype(jnp.float32).T,
                      precision=jax.lax.Precision.HIGHEST)
    if metric in ("ip", "cosine"):
        return -dots
    v2 = jnp.sum(v.astype(jnp.float32) ** 2, axis=-1)
    return v2[None, :] - 2.0 * dots


def ivf_scan_ref(queries, vectors, valid, ids, part_ids, k_out,
                 metric: str = "l2", qsel=None):
    """Oracle for the fused partition scan + top-k kernel.

    queries [Q, d]; vectors [k, p_max, d]; valid [k, p_max] bool;
    ids [k, p_max] int32; part_ids [n] int32 partitions to scan;
    qsel [Q, n] bool or None (MQO: which query wants which partition).
    -> (scores [Q, k_out], ids [Q, k_out]) sorted ascending.
    """
    Q = queries.shape[0]
    pv = vectors[part_ids]                        # [n, p_max, d]
    pid = ids[part_ids]                           # [n, p_max]
    pok = valid[part_ids]                         # [n, p_max]
    n, p_max, d = pv.shape
    s = scores_ref(queries, pv.reshape(n * p_max, d), metric)
    ok = jnp.broadcast_to(pok.reshape(1, n * p_max), s.shape)
    if qsel is not None:
        ok = ok & jnp.repeat(qsel, p_max, axis=1)
    s = jnp.where(ok, s, MASKED)
    neg, idx = jax.lax.top_k(-s, k_out)
    out_ids = jnp.take_along_axis(
        jnp.broadcast_to(pid.reshape(1, -1), s.shape), idx, axis=1)
    out_ids = jnp.where(-neg >= MASKED, -1, out_ids)
    return -neg, out_ids


def kmeans_assign_ref(batch, centroids, counts, balance_weight: float,
                      target_size: int, scale: float):
    """Oracle for the penalised-nearest assignment kernel.

    batch [s, d]; centroids [k, d]; counts [k] f32.
    -> (assign [s] int32, dist [s] f32 = penalised cost of the argmin)
    """
    d2 = scores_ref(batch, centroids, "l2") \
        + jnp.sum(batch.astype(jnp.float32) ** 2, -1, keepdims=True)
    pen = d2 + balance_weight * scale * counts[None, :] / target_size
    a = jnp.argmin(pen, axis=-1).astype(jnp.int32)
    return a, jnp.min(pen, axis=-1)
