"""Fused int8 scalar-quantized IVF scan + running top-k Pallas TPU kernel.

Same contract and grid structure as kernels/ivf_scan.py (one grid step per
probed partition, scalar-prefetched partition ids, VMEM running top-k),
but the partition payload streamed from HBM is the *int8 code tier* -- 4x
fewer bytes on the scan's bandwidth-bound axis -- and the distance
accumulation itself runs in the INTEGER domain on the MXU:

    queries are folded ONCE per scan (core/quantize.fold_queries) into a
    stacked two-term int8 encoding (primary + rounding residual)
        q_i8  = [q1; q2]                            [2Q, d] int8
        alpha = [alpha1; alpha2]                    [2Q] f32
        beta  = rank-1 epilogue constants           [Q] f32
    the kernel accumulates  acc = q_i8 . c_i8  with
        preferred_element_type=jnp.int32   (the int8 MXU path)
    and applies the affine (lo, scale) correction as the epilogue
        dots ~= (alpha * acc)[:Q] + (alpha * acc)[Q:] + beta.
    The residual term costs one extra query row in the bandwidth-bound
    matmul and buys ~2^-15 relative query precision, so candidate
    selection matches the dequantize-then-f32 scan.

The int8 codes are never dequantized on the matmul path -- the 4x
bandwidth win of the code tier becomes a FLOP win too. For l2 the
per-row constant ||decode(c)||^2 comes from the precomputed
IVFIndex.code_norms tier (an extra [1, p_max] f32 block per partition);
when the caller has no norms resident (paged frame scans) the kernel
falls back to the decode-and-reduce expression in-register, which is
bitwise-identical to how code_norms was precomputed.

This is the *candidate* stage of the paper's low-memory design: callers
over-fetch k' = rerank_factor * k rows here and rerank them at float32
(core/executor.py), so the `ids` input is typically the flat row index
(partition * p_max + slot) rather than the asset id -- whatever the
caller needs to gather rerank rows. MQO selection masks and fused
attribute predicates behave exactly as in ivf_scan. The query-side
quantization error only moves *candidate selection*, never reported
scores (the f32 rerank contract).

On a real TPU the int8 tile minimum is (32, 128); p_max must be a
multiple of 32 when running compiled (core/types.effective_pad_to bumps
the build-time padding automatically; sq_scan_topk asserts it so a
mis-padded layout fails loud instead of mis-compiling). The folded query
block is int8 too, so Q is padded up to the 32-sublane minimum
internally and the outputs sliced back; interpret mode runs the same
padded grid.

Frame-indirect entry (storage/pager.py): `codes` may be the pager's
frame *pool* [F, p_max, d] rather than the full code tier, with
`part_ids` carrying frame indices -- the kernel is layout-agnostic, it
streams whichever blocks the scalar-prefetched probe list names, so the
paged and resident scans share this one implementation.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import quantize
from .ivf_scan import (MASKED, default_interpret, merge_tile, probe_ids,
                       qsel_rows, query_tiling, selected)

# Minimum second-to-last tile dimension for int8 operands on real TPU
# hardware (the (32, 128) tile); interpret mode is unconstrained.
INT8_SUBLANE_MIN = 32


def _sq_scan_kernel(part_ids_ref,              # scalar prefetch [n]
                    *refs,
                    k_out: int, metric: str, mqo: bool, has_norms: bool):
    del part_ids_ref                 # consumed by the codes' index_map
    refs = list(refs)
    q_ref, alpha_ref, beta_ref, lo_ref, scale_ref, c_ref, ids_ref = refs[:7]
    rest = refs[7:]
    qsel_ref = rest.pop(0) if mqo else None
    norms_ref = rest.pop(0) if has_norms else None
    out_s_ref, out_i_ref, run_s, run_i = rest
    i = pl.program_id(1)
    n = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        run_s[...] = jnp.full_like(run_s, MASKED)
        run_i[...] = jnp.full_like(run_i, -1)

    # integer-domain accumulation: int8 x int8 -> int32 on the MXU, one
    # product per term of the two-term query fold ([2, qt, d] block)
    c = c_ref[0]

    def term(t):
        acc = jax.lax.dot_general(q_ref[t], c, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return alpha_ref[t] * acc.astype(jnp.float32)   # [qt, p_max]

    # rank-1 affine epilogue: dots ~= alpha1*(q1.c) + alpha2*(q2.c) + beta
    dots = term(0) + term(1) + beta_ref[...]
    if metric == "l2":
        if has_norms:
            v2 = norms_ref[0]                        # [1, p_max] precomputed
        else:
            # paged fallback: decode-and-reduce, the exact expression
            # code_norms was precomputed with (bitwise-identical values)
            v = (c.astype(jnp.float32) + 128.0) * scale_ref[...] + lo_ref[...]
            v2 = jnp.sum(v * v, axis=-1)[None, :]
        scores = v2 - 2.0 * dots
    else:
        scores = -dots
    ids_row = ids_ref[0]                             # [1, p_max]
    ok = ids_row != -1
    if mqo:
        ok = ok & selected(qsel_ref[0, 0])           # [qt, 1]
    merge_tile(run_s, run_i, scores, ok, ids_row, k_out)

    @pl.when(i == n - 1)
    def _out():
        out_s_ref[...] = run_s[...]
        out_i_ref[...] = run_i[...]


def sq_scan_topk(
    queries: jax.Array,          # [Q, d] f32 (normalised)
    codes: jax.Array,            # [k, p_max, d] int8
    lo: jax.Array,               # [d] f32 quantizer minima
    scale: jax.Array,            # [d] f32 quantizer scales
    valid: jax.Array,            # [k, p_max] bool/int8
    ids: jax.Array,              # [k, p_max] int32 (asset or flat row ids)
    part_ids: jax.Array,         # [n] int32 -- partitions to stream
    k_out: int,
    metric: str = "l2",
    qsel: Optional[jax.Array] = None,   # [Q, n] bool (MQO mask)
    attrs: Optional[jax.Array] = None,  # [k, p_max, n_attr] f32
    attr_filter=None,                   # compiled predicate (hybrid.py)
    norms: Optional[jax.Array] = None,  # [k, p_max] f32 ||decode(c)||^2
    interpret: Optional[bool] = None,   # None: auto by backend
) -> Tuple[jax.Array, jax.Array]:
    if interpret is None:
        interpret = default_interpret()
    kp, p_max, d = codes.shape
    assert interpret or p_max % INT8_SUBLANE_MIN == 0, \
        f"compiled int8 scan needs p_max % {INT8_SUBLANE_MIN} == 0 " \
        f"(got {p_max}); build with pad_to=32 (types.effective_pad_to)"
    q_n = queries.shape[0]
    n = part_ids.shape[0]
    part_ids = part_ids.astype(jnp.int32)
    mqo = qsel is not None

    # fold the query block into the int8 domain ONCE per scan; the fold
    # is the stacked two-term form ([q1; q2], [alpha1; alpha2], beta)
    stats = quantize.QuantStats(lo=jnp.asarray(lo, jnp.float32),
                                scale=jnp.asarray(scale, jnp.float32))
    q_i8, alpha, beta = quantize.fold_queries(stats, queries)

    # compiled int8 operands tile at 32 sublanes: pad Q up, slice back.
    # The fold's two terms become a leading axis, so a query tile carries
    # both of its terms.
    q_pad, qt = query_tiling(q_n, INT8_SUBLANE_MIN)
    padw = [(0, 0), (0, q_pad - q_n), (0, 0)]
    q_i8 = jnp.pad(q_i8.reshape(2, q_n, d), padw)
    alpha = jnp.pad(alpha.reshape(2, q_n, 1), padw)
    beta = jnp.pad(beta.reshape(q_n, 1), padw[1:])

    has_norms = norms is not None and metric == "l2"
    in_specs = [
        pl.BlockSpec((2, qt, d), lambda b, i, pids: (0, b, 0)),
        pl.BlockSpec((2, qt, 1), lambda b, i, pids: (0, b, 0)),
        pl.BlockSpec((qt, 1), lambda b, i, pids: (b, 0)),
        pl.BlockSpec((1, d), lambda b, i, pids: (0, 0)),
        pl.BlockSpec((1, d), lambda b, i, pids: (0, 0)),
        pl.BlockSpec((1, p_max, d), lambda b, i, pids: (pids[i], 0, 0)),
        pl.BlockSpec((1, 1, p_max), lambda b, i, pids: (i, 0, 0)),
    ]
    inputs = [q_i8.astype(jnp.int8), alpha.astype(jnp.float32),
              beta.astype(jnp.float32),
              lo.reshape(1, d).astype(jnp.float32),
              scale.reshape(1, d).astype(jnp.float32),
              codes.astype(jnp.int8),
              probe_ids(valid, ids, part_ids, attrs, attr_filter)]
    if mqo:
        in_specs.append(pl.BlockSpec((1, 1, 1, qt),
                                     lambda b, i, pids: (i, b, 0, 0)))
        inputs.append(qsel_rows(qsel, q_pad, qt))
    if has_norms:
        in_specs.append(pl.BlockSpec((1, 1, p_max),
                                     lambda b, i, pids: (i, 0, 0)))
        inputs.append(norms[part_ids].astype(jnp.float32)[:, None, :])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(q_pad // qt, n),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((qt, k_out), lambda b, i, pids: (b, 0)),
            pl.BlockSpec((qt, k_out), lambda b, i, pids: (b, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((qt, k_out), jnp.float32),
            pltpu.VMEM((qt, k_out), jnp.int32),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(_sq_scan_kernel, k_out=k_out, metric=metric,
                          mqo=mqo, has_norms=has_norms),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((q_pad, k_out), jnp.float32),
            jax.ShapeDtypeStruct((q_pad, k_out), jnp.int32),
        ],
        interpret=interpret,
        name="sq_scan_topk",
    )
    out_s, out_i = kernel(part_ids, *inputs)
    if q_pad != q_n:
        out_s, out_i = out_s[:q_n], out_i[:q_n]
    return out_s, out_i
