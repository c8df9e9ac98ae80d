"""IVF index construction and the padded partition-major device layout.

Build path (paper §3.1-3.2): cluster with mini-batch balanced k-means, then
lay vectors out partition-major. On disk (SQLite) the layout is a clustered
primary index on (partition_id, asset_id); on device it is the padded
[k, p_max, d] tensor described in core/types.py. `p_max` is the post-build
max partition size rounded up to `cfg.pad_to` -- balanced clustering keeps
the padding overhead small (measured in benchmarks/bench_build.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import quantize
from .types import (DeltaStore, INVALID_ID, IVFConfig, IVFIndex,
                    effective_pad_to, normalize_if_cosine)


def pack_partitions(
    X: np.ndarray,            # [n, d] float32
    ids: np.ndarray,          # [n] int32
    attrs: Optional[np.ndarray],  # [n, n_attr] float32 or None
    assign: np.ndarray,       # [n] int32 partition per row
    k: int,
    pad_to: int = 8,
    p_max: Optional[int] = None,
    codes: Optional[np.ndarray] = None,  # [n, d] int8 SQ codes or None
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray,
           Optional[np.ndarray]]:
    """Repack rows into the padded partition-major layout (host-side op --
    this is the 'disk reorganisation' tier; SQLite does the same job with a
    clustered index ORDER BY partition_id). When `codes` is given the int8
    code tier is packed row-for-row with the vectors (same slots), so the
    SQ scan and the rerank gather agree on row placement."""
    n, d = X.shape
    n_attr = 0 if attrs is None else attrs.shape[1]
    attrs = np.zeros((n, 0), np.float32) if attrs is None else attrs
    counts = np.bincount(assign, minlength=k).astype(np.int32)
    if p_max is None:
        p_max = int(counts.max()) if n else pad_to
        p_max = max(pad_to, -(-p_max // pad_to) * pad_to)

    vec = np.zeros((k, p_max, d), np.float32)
    vid = np.full((k, p_max), INVALID_ID, np.int32)
    vat = np.zeros((k, p_max, n_attr), np.float32)
    val = np.zeros((k, p_max), bool)
    cod = None if codes is None else np.zeros((k, p_max, d), np.int8)

    if n and counts.max() > p_max:  # only on incremental appends
        raise ValueError(f"partition {int(np.argmax(counts))} overflows "
                         f"p_max={p_max}")
    # rows in stable partition order; a row's slot is its rank within its
    # partition, so one fancy-index scatter per tier packs the layout
    order = np.argsort(assign, kind="stable")
    part = np.asarray(assign)[order]
    starts = np.cumsum(counts) - counts
    slot = np.arange(n) - starts[part]
    vec[part, slot] = X[order]
    vid[part, slot] = ids[order]
    vat[part, slot] = attrs[order]
    val[part, slot] = True
    if cod is not None:
        cod[part, slot] = codes[order]
    return vec, vid, vat, val, counts, cod


def build_index(
    X: np.ndarray,
    ids: Optional[np.ndarray] = None,
    attrs: Optional[np.ndarray] = None,
    cfg: Optional[IVFConfig] = None,
    k: Optional[int] = None,
    qstats: Optional[quantize.QuantStats] = None,
) -> IVFIndex:
    """Full index build: Alg. 1 clustering, the split pass to
    `FRAME_ROWS_MAX` rows a partition, partition-major packing.

    With cfg.quantize == "int8" the build also trains the scalar quantizer
    (unless pre-trained stats are passed, e.g. streamed from the durable
    store) and encodes every row into the code tier.
    """
    return build_with_split(X, ids, attrs, cfg, k, qstats)[0]


def build_with_split(
    X: np.ndarray,
    ids: Optional[np.ndarray] = None,
    attrs: Optional[np.ndarray] = None,
    cfg: Optional[IVFConfig] = None,
    k: Optional[int] = None,
    qstats: Optional[quantize.QuantStats] = None,
):
    """`build_index`, also returning what its split pass did (a
    maintenance.BuildSplitStats, for the engine's build counters)."""
    from . import maintenance           # maintenance imports this module
    cfg = cfg or IVFConfig(dim=X.shape[1])
    X = np.asarray(X, np.float32)
    n = X.shape[0]
    ids = np.arange(n, dtype=np.int32) if ids is None else ids.astype(np.int32)
    # the clustering sees the raw rows, as the paged build streams them
    centroids, csizes, assign, split = maintenance.fit_to_bar(X, ids, cfg,
                                                              k=k)
    X = np.asarray(normalize_if_cosine(jnp.asarray(X), cfg.metric))

    codes = None
    if cfg.quantize == "int8":
        if qstats is None:
            qstats = quantize.train(jnp.asarray(X))
        codes = quantize.encode_np(qstats, X)
    else:
        qstats = None

    k = centroids.shape[0]
    # dtype-aware tile padding: int8 partitions on real TPU pad to the
    # (32, 128) minimum tile; f32 / interpret keep cfg.pad_to
    vec, vid, vat, val, counts, cod = pack_partitions(
        X, ids, attrs, assign, k, pad_to=effective_pad_to(cfg), codes=codes)

    n_attr = vat.shape[-1]
    code_tier = None if cod is None else jnp.asarray(cod)
    return IVFIndex(
        centroids=jnp.asarray(centroids),
        csizes=jnp.asarray(csizes, jnp.float32),
        vectors=jnp.asarray(vec),
        ids=jnp.asarray(vid),
        attrs=jnp.asarray(vat),
        valid=jnp.asarray(val),
        counts=jnp.asarray(counts),
        delta=DeltaStore.empty(cfg.delta_capacity, X.shape[1], n_attr,
                               quantized=cod is not None),
        base_mean_size=jnp.asarray(counts.mean() if n else 0.0, jnp.float32),
        codes=code_tier,
        qstats=qstats,
        code_norms=None if cod is None else quantize.row_norms(qstats,
                                                               code_tier),
        drift=jnp.zeros((k,), jnp.float32),
        config=cfg,
    ), split


def grow_layout(index: IVFIndex, new_p_max: int) -> IVFIndex:
    """Grow p_max (host-side maintenance; keeps device shapes static
    between maintenance points)."""
    k, p_max, d = index.vectors.shape
    assert new_p_max >= p_max
    pad = new_p_max - p_max

    def pad2(a, fill):
        widths = [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)
        return jnp.pad(a, widths, constant_values=fill)

    return dataclasses.replace(
        index,
        vectors=pad2(index.vectors, 0.0),
        ids=pad2(index.ids, INVALID_ID),
        attrs=pad2(index.attrs, 0.0),
        valid=pad2(index.valid, False),
        codes=None if index.codes is None else pad2(index.codes, 0),
        # recompute (not pad) so the padded slots carry decode-of-zero
        # norms, preserving code_norms == row_norms(qstats, codes)
        code_norms=None if index.codes is None else quantize.row_norms(
            index.qstats, pad2(index.codes, 0)),
    )
