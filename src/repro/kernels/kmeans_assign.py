"""Penalised nearest-centroid assignment Pallas TPU kernel (Alg. 1's
NEAREST, batch-parallel form).

Streams centroid tiles HBM->VMEM, computes the [s, kt] distance block on
the MXU, adds the balance penalty (lambda * scale * count/target), and
keeps a running (best, argbest) per batch row across tiles.

The within-batch sequential count accumulation of Alg. 1 lives in the
pure-JAX path (core/kmeans.assign_minibatch, a lax.scan); this kernel is
the high-throughput variant used for the *final* assignment pass (Alg. 1
line 16, penalty weight 0) and for balanced re-assignment during
maintenance, where counts are frozen for the duration of a batch.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ivf_scan import default_interpret


def _assign_kernel(x_ref, c_ref, penalty_ref, out_i_ref, out_d_ref,
                   best_d, best_i, *, kt: int):
    t = pl.program_id(0)
    nt = pl.num_programs(0)

    @pl.when(t == 0)
    def _init():
        best_d[...] = jnp.full_like(best_d, jnp.finfo(jnp.float32).max)
        best_i[...] = jnp.zeros_like(best_i)

    x = x_ref[...].astype(jnp.float32)              # [s, d]
    c = c_ref[...].astype(jnp.float32)              # [kt, d]
    dots = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)
    c2 = jnp.sum(c * c, axis=-1)
    d2 = x2 + c2[None, :] - 2.0 * dots              # [s, kt]
    pen = d2 + penalty_ref[...][None, :]

    tile_best = jnp.min(pen, axis=1)
    tile_arg = jnp.argmin(pen, axis=1).astype(jnp.int32) + t * kt
    better = tile_best < best_d[...]
    best_d[...] = jnp.where(better, tile_best, best_d[...])
    best_i[...] = jnp.where(better, tile_arg, best_i[...])

    @pl.when(t == nt - 1)
    def _out():
        out_i_ref[...] = best_i[...]
        out_d_ref[...] = best_d[...]


def kmeans_assign(
    batch: jax.Array,        # [s, d]
    centroids: jax.Array,    # [k, d]
    counts: jax.Array,       # [k] f32
    *,
    balance_weight: float = 0.0,
    target_size: int = 100,
    scale: float = 1.0,
    tile_k: int = 256,
    interpret: Optional[bool] = None,   # None: auto by backend
) -> Tuple[jax.Array, jax.Array]:
    """-> (assign [s] int32, best penalised cost [s] f32).

    The balance penalty (lambda * scale * count / target, Alg. 1 NEAREST)
    is folded into a per-centroid penalty vector on the host side so the
    kernel streams exactly two operand tiles per grid step.
    """
    if interpret is None:
        interpret = default_interpret()
    s, d = batch.shape
    k = centroids.shape[0]
    penalty = counts.astype(jnp.float32) * (
        jnp.asarray(balance_weight, jnp.float32)
        * jnp.asarray(scale, jnp.float32) / target_size)
    pad = (-k) % tile_k
    if pad:
        centroids = jnp.pad(centroids, ((0, pad), (0, 0)))
        penalty = jnp.pad(penalty, (0, pad),
                          constant_values=jnp.float32(1e18))  # repel padding
    kp = centroids.shape[0]
    nt = kp // tile_k

    kernel = pl.pallas_call(
        functools.partial(_assign_kernel, kt=tile_k),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((s, d), lambda t: (0, 0)),
            pl.BlockSpec((tile_k, d), lambda t: (t, 0)),
            pl.BlockSpec((tile_k,), lambda t: (t,)),
        ],
        out_specs=[
            pl.BlockSpec((s,), lambda t: (0,)),
            pl.BlockSpec((s,), lambda t: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s,), jnp.int32),
            jax.ShapeDtypeStruct((s,), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((s,), jnp.float32),
            pltpu.VMEM((s,), jnp.int32),
        ],
        interpret=interpret,
    )
    return tuple(kernel(batch, centroids, penalty))
