"""Top-k maintenance & merging.

The paper (§3.3) keeps one max-heap per worker thread and merges heaps when
all threads finish. TPUs have no efficient random-access heap; the
semantically identical primitive is an associative *top-k merge*:

    merge(topk(A), topk(B)) == topk(A ++ B)

which lets us (a) keep a running top-k while scanning partition tiles and
(b) reduce per-device partial results across a mesh axis in log depth
(`tournament_merge`). Scores are "smaller is better" everywhere.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .types import INVALID_ID, MASKED_SCORE


def topk_smallest(scores: jax.Array, ids: jax.Array, k: int):
    """Top-k smallest scores along the last axis. Returns (scores, ids).

    Entries carrying MASKED_SCORE are no-results: their ids are
    invalidated so fewer-than-k matches never surface phantom ids."""
    neg, idx = jax.lax.top_k(-scores, k)
    out_s = -neg
    out_i = jnp.take_along_axis(ids, idx, axis=-1)
    out_i = jnp.where(out_s >= MASKED_SCORE, INVALID_ID, out_i)
    return out_s, out_i


def merge_topk(s_a, i_a, s_b, i_b, k: int):
    """Associative merge of two (scores, ids) top-k buffers -> top-k of union."""
    s = jnp.concatenate([s_a, s_b], axis=-1)
    i = jnp.concatenate([i_a, i_b], axis=-1)
    return topk_smallest(s, i, k)


def running_topk_init(batch_shape, k: int):
    s = jnp.full(batch_shape + (k,), MASKED_SCORE, jnp.float32)
    i = jnp.full(batch_shape + (k,), INVALID_ID, jnp.int32)
    return s, i


def mask_scores(scores: jax.Array, valid: jax.Array) -> jax.Array:
    """Push masked rows past any real score so they never enter a top-k."""
    return jnp.where(valid, scores, MASKED_SCORE)


def dedup_by_id(scores: jax.Array, ids: jax.Array):
    """Mask duplicate ids (keep best-scoring occurrence).

    Needed when a row exists both in a main partition (stale, tombstoned
    lazily) and the delta-store (fresh upsert): upsert semantics say the
    delta copy wins. Inputs are sorted ascending by score, so the first
    occurrence of an id is the one to keep.
    """
    order = jnp.argsort(scores, axis=-1)
    s = jnp.take_along_axis(scores, order, axis=-1)
    i = jnp.take_along_axis(ids, order, axis=-1)
    # first occurrence mask: id differs from every earlier id
    eq = i[..., :, None] == i[..., None, :]  # [.., K, K]
    earlier = jnp.tril(jnp.ones(eq.shape[-2:], bool), k=-1)
    dup = jnp.any(eq & earlier, axis=-1) & (i != INVALID_ID)
    s = jnp.where(dup, MASKED_SCORE, s)
    i = jnp.where(dup, INVALID_ID, i)
    return topk_smallest(s, i, s.shape[-1])


def tournament_merge(scores: jax.Array, ids: jax.Array, k: int, axis_name: str):
    """Log-depth cross-device top-k reduction along a mesh axis.

    Inside `shard_map`: every device holds a local [.., k] buffer; after the
    tournament every device holds the global top-k. Uses ppermute halving
    (hypercube exchange) so each round moves k rows instead of all-gathering
    world_size * k rows -- the TPU analogue of the paper's "efficient
    parallel heap merge", and cheaper on ICI than a flat all-gather when
    world size is large.
    """
    size = jax.lax.axis_size(axis_name)
    assert size & (size - 1) == 0, "hypercube merge needs a power-of-2 axis"
    step = 1
    while step < size:
        perm = [(i, i ^ step) for i in range(size)]
        peer_s = jax.lax.ppermute(scores, axis_name, perm)
        peer_i = jax.lax.ppermute(ids, axis_name, perm)
        scores, ids = merge_topk(scores, ids, peer_s, peer_i, k)
        step <<= 1
    return scores, ids


def allgather_merge(scores: jax.Array, ids: jax.Array, k: int, axis_name: str):
    """Flat all-gather + local top-k (baseline collective schedule)."""
    s = jax.lax.all_gather(scores, axis_name, axis=scores.ndim - 1, tiled=True)
    i = jax.lax.all_gather(ids, axis_name, axis=ids.ndim - 1, tiled=True)
    return topk_smallest(s, i, k)
