"""Mini-batch k-means with flexible balance constraints (paper Alg. 1).

Faithful reproduction of MicroNN's indexing algorithm:

  * k = |X| / target_cluster_size                       (line 1)
  * centroids seeded from random data points            (line 2)
  * per iteration: a uniform random mini-batch M        (line 6)
  * NEAREST assigns each x in M to the closest centroid *under a balance
    penalty* so large clusters repel new members        (lines 7-8, [22])
  * per-centre counts v and learning rate eta = 1/v[c]  (lines 10-13)
  * final pass assigns every x to its plain nearest centre (lines 15-16)

Vectorisation note (exactness, not approximation): Alg. 1 updates a centroid
sequentially for each assigned sample with eta = 1/v[c]. For samples
x_1..x_m joining a centroid with prior count v and position c, that
recurrence telescopes to the running mean

    c' = (v * c + sum_i x_i) / (v + m)

so the grouped update below reproduces the sequential loop bit-for-bit (up
to float associativity). The *assignment* loop, however, is order-dependent
(counts move within a batch), so we keep it as a lax.scan over the batch --
distances are precomputed with one [s, k] matmul (the paper's SIMD batching;
here the MXU), and the scan only does the penalised argmin + count bump.

Memory: only the [s, d] mini-batch, [k, d] centroids and [s, k] distance
block are live -- never the full dataset. This is the property Fig. 6b/8b
measure; `benchmarks/bench_minibatch.py` reproduces them.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .types import EXACT, IVFConfig, normalize_if_cosine, pairwise_scores


@partial(jax.jit, static_argnames=("balance_weight", "target_size"))
def assign_minibatch(
    centroids: jax.Array,     # [k, d]
    counts: jax.Array,        # [k] float32 running per-centre counts (v)
    batch: jax.Array,         # [s, d]
    *,
    balance_weight: float,
    target_size: int,
):
    """Lines 6-13 of Alg. 1 for one mini-batch.

    Returns (new_centroids, new_counts, assignments [s]).
    """
    s = batch.shape[0]
    # One matmul for the whole batch (SIMD/MXU batching, paper §3.1).
    dist = pairwise_scores(batch, centroids, "l2")  # [s, k]

    # NEAREST with balance penalty: cost = ||x - c||^2 + lambda*scale*v[c]/t.
    # `scale` (mean nearest-centroid distance in this batch) makes the
    # penalty invariant to the data's distance scale -- Liu et al. [22]
    # leave lambda a free parameter; anchoring it to the batch distance
    # scale keeps one default working across datasets (MNIST..GIST dims).
    scale = jnp.mean(jnp.min(dist, axis=-1)) + 1e-12

    # Counts advance *within* the batch (d accumulates in Alg. 1's first
    # loop), so the argmin is a sequential scan over batch elements.
    def step(carry, row):
        v = carry
        penalized = row + balance_weight * scale * v / target_size
        c = jnp.argmin(penalized)
        return v.at[c].add(1.0), c

    _, assign = jax.lax.scan(step, counts, dist)

    # Grouped running-mean update (telescoped lines 10-13).
    onehot = jax.nn.one_hot(assign, centroids.shape[0], dtype=batch.dtype)  # [s, k]
    batch_counts = onehot.sum(axis=0)                       # m_c
    batch_sums = jnp.matmul(onehot.T, batch, precision=EXACT)  # [k, d]
    new_counts = counts + batch_counts
    denom = jnp.maximum(new_counts, 1.0)[:, None]
    new_centroids = (counts[:, None] * centroids + batch_sums) / denom
    # Centres with no prior mass and no batch members stay put.
    new_centroids = jnp.where(new_counts[:, None] > 0, new_centroids, centroids)
    return new_centroids, new_counts, assign.astype(jnp.int32)


@partial(jax.jit, static_argnames=("balance_weight", "target_size", "balanced"))
def final_assign(
    centroids: jax.Array,
    counts: jax.Array,
    batch: jax.Array,
    *,
    balance_weight: float,
    target_size: int,
    balanced: bool,
):
    """Lines 15-16: P[x] <- q(C, x) (plain nearest by default).

    `balanced=True` is a beyond-paper knob: it reuses the penalised
    assignment for the final pass too, which tightens the p_max bound of the
    padded device layout (see DESIGN.md §2 item 2).
    """
    if not balanced:
        dist = pairwise_scores(batch, centroids, "l2")
        return counts, jnp.argmin(dist, axis=-1).astype(jnp.int32)
    new_c, new_v, assign = assign_minibatch(
        centroids, counts, batch,
        balance_weight=balance_weight, target_size=target_size)
    del new_c
    return new_v, assign


class MiniBatchKMeans:
    """Host-side driver. Streams mini-batches; device does the math.

    Works from an in-memory array *or* any callable yielding batches (the
    storage layer passes a SQLite cursor reader), so the full dataset is
    never required in memory -- the paper's core constraint.
    """

    def __init__(self, cfg: IVFConfig, k: Optional[int] = None):
        self.cfg = cfg
        self.k = k
        self.centroids: Optional[np.ndarray] = None
        self.counts: Optional[np.ndarray] = None
        # peak number of float32s resident at once (for Fig. 6b/8b repro)
        self.peak_live_floats = 0

    def _track(self, *arrs):
        live = sum(int(np.prod(a.shape)) for a in arrs)
        self.peak_live_floats = max(self.peak_live_floats, live)

    def fit(
        self,
        sample_batch: Callable[[int, np.random.Generator], np.ndarray],
        n_total: int,
        rng: Optional[np.random.Generator] = None,
    ) -> np.ndarray:
        """sample_batch(size, rng) -> [size, d] float32 uniform random rows."""
        cfg = self.cfg
        rng = rng or np.random.default_rng(cfg.seed)
        k = self.k or max(1, n_total // cfg.target_partition_size)
        self.k = k

        # Line 2: seed centroids with random data points.
        seed_rows = sample_batch(k, rng)
        seed_rows = np.asarray(
            normalize_if_cosine(jnp.asarray(seed_rows), cfg.metric))
        centroids = jnp.asarray(seed_rows, jnp.float32)
        counts = jnp.zeros((k,), jnp.float32)

        for _ in range(cfg.kmeans_iters):
            batch = sample_batch(cfg.minibatch_size, rng).astype(np.float32)
            batch = np.asarray(normalize_if_cosine(jnp.asarray(batch), cfg.metric))
            self._track(batch, seed_rows[:0], np.zeros((k, cfg.dim)),
                        np.zeros((cfg.minibatch_size, k)))
            centroids, counts, _ = assign_minibatch(
                centroids, counts, jnp.asarray(batch),
                balance_weight=cfg.balance_weight,
                target_size=cfg.target_partition_size)

        self.centroids = np.asarray(centroids)
        self.counts = np.asarray(counts)
        return self.centroids

    def assign(
        self,
        batch_iter: Iterator[np.ndarray],
    ) -> np.ndarray:
        """Final full-data assignment pass, streamed in batches."""
        cfg = self.cfg
        assert self.centroids is not None, "fit() first"
        centroids = jnp.asarray(self.centroids)
        counts = jnp.asarray(self.counts)
        out = []
        for batch in batch_iter:
            batch = np.asarray(
                normalize_if_cosine(jnp.asarray(batch, jnp.float32), cfg.metric))
            counts, assign = final_assign(
                centroids, counts, jnp.asarray(batch),
                balance_weight=cfg.balance_weight,
                target_size=cfg.target_partition_size,
                balanced=cfg.balanced_final_assign)
            out.append(np.asarray(assign))
        self.counts = np.asarray(counts)
        return np.concatenate(out) if out else np.zeros((0,), np.int32)


def fit_in_memory(X: np.ndarray, cfg: IVFConfig, k: Optional[int] = None):
    """Convenience wrapper: fit + assign over an in-memory array."""
    km = MiniBatchKMeans(cfg, k=k)

    def sample(size: int, rng: np.random.Generator) -> np.ndarray:
        idx = rng.integers(0, X.shape[0], size=size)
        return X[idx]

    km.fit(sample, X.shape[0])
    bs = max(cfg.minibatch_size, 4096)
    assign = km.assign(X[i:i + bs] for i in range(0, X.shape[0], bs))
    return km.centroids, km.counts, assign
