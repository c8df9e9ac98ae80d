"""Streaming updates: upsert / delete via the delta-store (paper §3.6).

Semantics (faithful):
  * insert with upsert semantics -- a new vector for an existing asset id
    replaces the old one everywhere;
  * deletes tombstone rows (valid=False) without moving data;
  * newly inserted vectors live in the delta partition until maintenance
    flushes them into the IVF layout (core/maintenance.py);
  * every query always scans the delta partition, so readers see updates
    immediately (the consistency requirement of §2.1).

All update ops are pure jitted functions IVFIndex -> IVFIndex, so they
compose with pjit sharding; the host wrapper (storage.MicroNN) serialises
writers, mirrors each op durably in SQLite, and triggers flushes when the
delta cursor approaches capacity -- reproducing the paper's single-writer /
multi-reader regime.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from . import quantize
from .types import DeltaStore, INVALID_ID, IVFIndex, normalize_if_cosine


def _tombstone_main(row_ids, valid, counts, ids: jax.Array):
    """Invalidate any main-partition rows whose id appears in `ids`."""
    hit = (row_ids[:, :, None] == ids[None, None, :]).any(-1)  # [k, p_max]
    hit = hit & valid
    new_valid = valid & ~hit
    new_counts = counts - hit.sum(-1).astype(counts.dtype)
    return new_valid, new_counts


def _tombstone_delta(delta: DeltaStore, ids: jax.Array):
    hit = (delta.ids[:, None] == ids[None, :]).any(-1) & delta.valid
    return delta.valid & ~hit


# The jitted writes take only the leaves they read. A jit over the whole
# IVFIndex would hand back fresh copies of the untouched vector and code
# tiers, doubling their device memory on every write.
def upsert(index: IVFIndex, vecs: jax.Array, ids: jax.Array,
           attrs: jax.Array) -> IVFIndex:
    """Insert a batch of [B] rows with upsert semantics.

    Precondition (enforced by the host wrapper, which flushes first if
    needed): delta.count + B <= delta capacity.
    """
    valid, counts, delta = _upsert(
        index.ids, index.valid, index.counts, index.delta, index.qstats,
        vecs, ids, attrs, metric=index.config.metric)
    return dataclasses.replace(index, valid=valid, counts=counts,
                               delta=delta)


@partial(jax.jit, static_argnames=("metric",))
def _upsert(row_ids, valid, counts, d: DeltaStore, qstats, vecs, ids,
            attrs, *, metric: str):
    vecs = normalize_if_cosine(vecs.astype(jnp.float32), metric)
    B = vecs.shape[0]

    # 1. upsert semantics: tombstone any existing copies
    new_valid, new_counts = _tombstone_main(row_ids, valid, counts, ids)
    dvalid = _tombstone_delta(d, ids)

    # 2. append at the write cursor (quantized tier: encode on insert, so
    # flush_delta can move codes verbatim instead of re-deriving them)
    slots = d.count + jnp.arange(B, dtype=jnp.int32)
    new_codes = d.codes
    if qstats is not None and d.codes is not None:
        new_codes = d.codes.at[slots].set(quantize.encode(qstats, vecs))
    new_delta = DeltaStore(
        vectors=d.vectors.at[slots].set(vecs),
        ids=d.ids.at[slots].set(ids.astype(jnp.int32)),
        attrs=d.attrs.at[slots].set(attrs.astype(jnp.float32)),
        valid=dvalid.at[slots].set(True),
        count=d.count + B,
        codes=new_codes,
    )
    return new_valid, new_counts, new_delta


def delete(index: IVFIndex, ids: jax.Array) -> IVFIndex:
    """Tombstone a batch of asset ids (no-op for unknown ids)."""
    valid, counts, dvalid = _delete(index.ids, index.valid, index.counts,
                                    index.delta, ids)
    return dataclasses.replace(
        index, valid=valid, counts=counts,
        delta=dataclasses.replace(index.delta, valid=dvalid))


@jax.jit
def _delete(row_ids, valid, counts, d: DeltaStore, ids):
    new_valid, new_counts = _tombstone_main(row_ids, valid, counts, ids)
    return new_valid, new_counts, _tombstone_delta(d, ids)


def delta_only_upsert(delta: DeltaStore, vecs: jax.Array, ids: jax.Array,
                      attrs: jax.Array, metric: str,
                      qstats=None) -> DeltaStore:
    """Paged-mode insert: append into the delta store alone. The main tier
    lives in SQLite, so stale main-tier copies are handled durably by the
    engine (store upsert + frame invalidation) instead of via a device
    tombstone; only an existing *delta* copy needs tombstoning here."""
    vecs = normalize_if_cosine(vecs.astype(jnp.float32), metric)
    B = vecs.shape[0]
    dvalid = _tombstone_delta(delta, ids)
    slots = delta.count + jnp.arange(B, dtype=jnp.int32)
    new_codes = delta.codes
    if qstats is not None and delta.codes is not None:
        new_codes = delta.codes.at[slots].set(quantize.encode(qstats, vecs))
    return DeltaStore(
        vectors=delta.vectors.at[slots].set(vecs),
        ids=delta.ids.at[slots].set(ids.astype(jnp.int32)),
        attrs=delta.attrs.at[slots].set(attrs.astype(jnp.float32)),
        valid=dvalid.at[slots].set(True),
        count=delta.count + B,
        codes=new_codes,
    )


def delta_only_delete(delta: DeltaStore, ids: jax.Array) -> DeltaStore:
    """Paged-mode delete: tombstone any delta copy of the given asset ids
    (main-tier copies are deleted durably + invalidated by the engine)."""
    return dataclasses.replace(delta, valid=_tombstone_delta(delta, ids))


def delta_free_slots(index: IVFIndex) -> int:
    return int(index.delta.capacity - index.delta.count)


def delta_live(index: IVFIndex) -> int:
    return int(index.delta.valid.sum())
