"""The benchmark's own copy of the synthetic Table-2 generator.

Copied from `repro.data.synthetic.make`, so that the yardstick cannot
move with the program: a change under `src/` leaves these arrays as
they are (a test pins a checksum). The brute-force ground truth
(`synthetic.exact_gt`) is kept, generalised to a rounding precision, in
`reference.reference_topk`. Public sets cannot be fetched, so
each is re-synthesised at its published dimension and metric as a
clustered Gaussian mixture.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

# name -> (dim, n_vectors, n_queries, metric)   [MicroNN paper, Table 2]
TABLE2 = {
    "mnist": (784, 60_000, 10_000, "l2"),
    "nytimes": (256, 290_000, 10_000, "cosine"),
    "sift": (128, 1_000_000, 10_000, "l2"),
    "glove": (200, 1_183_514, 10_000, "l2"),
    "gist": (960, 1_000_000, 1_000, "l2"),
    "deepimage": (96, 10_000_000, 10_000, "cosine"),
}


@dataclasses.dataclass
class Dataset:
    name: str
    metric: str
    X: np.ndarray          # [n, d] float32
    Q: np.ndarray          # [q, d] float32


def make(name: str, scale: float = 1.0, seed: int = 0,
         n_queries: Optional[int] = None) -> Dataset:
    """Rows and queries of `name` at `scale` of its row count.

    Rows are drawn around n/500 Gaussian centres; each query is a row
    plus 0.1-sigma noise. The first 512 queries are those of
    `synthetic.make`; `n_queries` draws a longer stream from the same
    generator."""
    dim, n, q, metric = TABLE2[name]
    n = max(1000, int(n * scale))
    q = n_queries or max(32, min(int(q * scale), 512))
    rng = np.random.default_rng(seed)
    n_clusters = max(16, n // 500)
    centers = rng.normal(size=(n_clusters, dim)).astype(np.float32) * 4.0
    asg = rng.integers(0, n_clusters, n)
    X = centers[asg] + rng.normal(size=(n, dim)).astype(np.float32)
    qi = rng.integers(0, n, q)
    Q = X[qi] + 0.1 * rng.normal(size=(q, dim)).astype(np.float32)
    return Dataset(name=name, metric=metric, X=X, Q=Q)


def attributes(n: int, seed: int) -> np.ndarray:
    """Two float attributes per row, as the smoke run loads them:
    a "location" in 0..9 and a "year" in 2000..2024."""
    rng = np.random.default_rng([seed, 1])
    return np.stack([rng.integers(0, 10, n), rng.integers(2000, 2025, n)],
                    axis=1).astype(np.float32)


def new_rows(X: np.ndarray, count: int, seed: int) -> np.ndarray:
    """Rows a writer inserts: existing rows moved by unit noise, so they
    land among the collection's clusters."""
    rng = np.random.default_rng([seed, 2])
    base = X[rng.integers(0, len(X), count)]
    return (base + rng.normal(size=base.shape)).astype(np.float32)


def normalize(X: np.ndarray) -> np.ndarray:
    return X / np.maximum(np.linalg.norm(X, axis=1, keepdims=True), 1e-12)
