"""Integer-domain SQ scan (PR 6 tentpole): bitwise XLA-vs-Pallas parity,
recall non-regression against the dequantize-then-f32 scan it replaced,
the small-Q gather specialization, and the code_norms invariant.

Parity chain: the Pallas kernel accumulates int8 x int8 -> int32 on the
MXU; the XLA reference accumulates the cast integers in f32 at HIGHEST
precision -- every product and partial sum is an exact integer < 2^24
for d <= 1024, so the two accumulators hold IDENTICAL values. The f32
affine epilogue (alpha * acc, summed across the two terms) is written in
the same op order in both, but the compiler may fuse it into fma with
different rounding per program, so raw scan scores can differ by ~1 ulp.
The pinned contract is therefore: candidate SELECTION identical (ids
bitwise), scan scores equal to a couple of ulp, and the end-to-end
SearchResult bitwise identical across backends -- the exact-f32 rerank
rescores the identical candidate set with one shared jitted expression.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import executor, ivf, quantize
from repro.core.query import Q
from repro.core.types import IVFConfig
from repro.kernels import sq_scan


def _mk_index(n=1200, d=24, seed=0, metric="l2", **cfg_kw):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(10, d)).astype(np.float32) * 5
    X = (centers[rng.integers(0, 10, n)]
         + rng.normal(size=(n, d))).astype(np.float32)
    cfg = IVFConfig(dim=d, metric=metric, target_partition_size=64,
                    kmeans_iters=8, quantize="int8", rerank_factor=4,
                    **cfg_kw)
    return ivf.build_index(X, cfg=cfg), X


def _cand_recall(cand, ref, k):
    hits = 0
    for a, b in zip(cand, ref[:, :k]):
        real = set(int(x) for x in b if x >= 0)
        hits += len(set(int(x) for x in a if x >= 0) & real)
    return hits / max(1, ref.shape[0] * k)


# -- the two-term query fold --------------------------------------------------


def test_fold_queries_two_term_shapes_and_precision():
    rng = np.random.default_rng(1)
    d, q_n = 32, 6
    lo = rng.normal(size=d).astype(np.float32)
    scale = (rng.random(d).astype(np.float32) + 0.1) / 50
    stats = quantize.QuantStats(lo=jnp.asarray(lo), scale=jnp.asarray(scale))
    q = jnp.asarray(rng.normal(size=(q_n, d)).astype(np.float32))
    q_i8, alpha, beta = quantize.fold_queries(stats, q)
    assert q_i8.shape == (2 * q_n, d) and q_i8.dtype == jnp.int8
    assert alpha.shape == (2 * q_n,) and beta.shape == (q_n,)
    # reconstruct q.scale from the stacked two-term encoding: the
    # residual term must leave only ~2^-15 relative error
    w = np.asarray(q) * scale[None, :]
    rec = (np.asarray(alpha)[:q_n, None]
           * np.asarray(q_i8, np.float32)[:q_n]
           + np.asarray(alpha)[q_n:, None]
           * np.asarray(q_i8, np.float32)[q_n:])
    err = np.abs(rec - w).max()
    assert err <= 2.0 ** -14 * np.abs(w).max() + 1e-12


# -- bitwise XLA vs Pallas(interpret) parity ---------------------------------


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("with_norms", [True, False])
def test_int8_scan_xla_matches_pallas_interpret_bitwise(metric, with_norms):
    _int8_parity_case(metric, with_norms, q_n=5)


@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_int8_scan_parity_across_query_tiles(metric):
    _int8_parity_case(metric, True, q_n=40)     # two 32-row query tiles


def _int8_parity_case(metric, with_norms, q_n):
    idx, X = _mk_index(metric=metric)
    q = jnp.asarray(X[:q_n])
    plan = executor.plan_ann(idx, q, k=16, n_probe=4)
    norms = idx.code_norms if with_norms else None
    kprime = 48
    s_x, i_x = executor._xla_sq_scan(
        plan.queries, idx.codes, idx.qstats, idx.valid, idx.ids,
        plan.part_ids, kprime, metric=metric, qsel=plan.qsel, norms=norms)
    s_p, i_p = sq_scan.sq_scan_topk(
        plan.queries, idx.codes, idx.qstats.lo, idx.qstats.scale,
        idx.valid, idx.ids, plan.part_ids, kprime, metric=metric,
        qsel=plan.qsel, norms=norms, interpret=True)
    np.testing.assert_array_equal(np.asarray(i_x), np.asarray(i_p))
    # scores: identical integer accumulators, epilogue within fma noise
    np.testing.assert_allclose(np.asarray(s_x), np.asarray(s_p),
                               rtol=0, atol=1e-3)


def test_execute_plan_quantized_bitwise_across_backends():
    # the end-to-end pin: same plan through the Pallas(interpret) and XLA
    # backends must return bit-identical ids AND scores -- the quantized
    # path's exact-f32 rerank rescores the (identical) candidate set
    # through one shared jitted expression
    idx, X = _mk_index(n=1500, d=16)
    q = jnp.asarray(X[:6])
    plan = executor.plan_ann(idx, q, k=12, n_probe=4)
    r_x = executor.execute_plan(idx, plan, backend="xla", quantized=True)
    r_p = executor.execute_plan(idx, plan, backend="pallas", quantized=True)
    np.testing.assert_array_equal(np.asarray(r_x.ids), np.asarray(r_p.ids))
    assert np.array_equal(np.asarray(r_x.scores), np.asarray(r_p.scores))


def test_int8_scan_norms_fallback_bitwise_matches_precomputed():
    # the in-scan decode-and-reduce fallback (paged frames carry no
    # code_norms tier) must reproduce the precomputed tier exactly
    idx, X = _mk_index()
    q = jnp.asarray(X[:4])
    plan = executor.plan_ann(idx, q, k=8, n_probe=3)
    s_n, i_n = executor._xla_sq_scan(
        plan.queries, idx.codes, idx.qstats, idx.valid, idx.ids,
        plan.part_ids, 32, metric="l2", qsel=plan.qsel,
        norms=idx.code_norms)
    s_f, i_f = executor._xla_sq_scan(
        plan.queries, idx.codes, idx.qstats, idx.valid, idx.ids,
        plan.part_ids, 32, metric="l2", qsel=plan.qsel, norms=None)
    np.testing.assert_array_equal(np.asarray(i_n), np.asarray(i_f))
    assert np.array_equal(np.asarray(s_n), np.asarray(s_f))


# -- recall non-regression vs the dequantize-then-f32 scan -------------------


@pytest.mark.parametrize("rerank_factor", [1, 2, 4])
def test_int8_domain_candidate_recall_not_below_dequant(rerank_factor):
    idx, X = _mk_index(n=2000, d=32)
    k, n_probe = 20, 4
    q = jnp.asarray(X[:16])
    ref = np.asarray(executor.run(
        idx, q, Q.knn(k=k, n_probe=n_probe).quantized(False)).ids)
    plan = executor.plan_ann(idx, q, k=k, n_probe=n_probe)
    kprime = min(rerank_factor * k, int(idx.valid.sum()))
    _, i_i8 = executor._xla_sq_scan(
        plan.queries, idx.codes, idx.qstats, idx.valid, idx.ids,
        plan.part_ids, kprime, metric="l2", qsel=plan.qsel,
        norms=idx.code_norms)
    _, i_dq = executor._xla_sq_scan_dequant(
        plan.queries, idx.codes, idx.qstats, idx.valid, idx.ids,
        plan.part_ids, kprime, metric="l2", qsel=plan.qsel)
    rec_i8 = _cand_recall(np.asarray(i_i8), ref, k)
    rec_dq = _cand_recall(np.asarray(i_dq), ref, k)
    assert rec_i8 + 1e-12 >= rec_dq, \
        f"int8-domain recall {rec_i8:.3f} < dequant {rec_dq:.3f} " \
        f"at rerank_factor={rerank_factor}"


# -- small-Q gather specialization -------------------------------------------


@pytest.mark.parametrize("quantized", [False, True])
def test_small_q_gather_matches_shared_union(quantized):
    idx, X = _mk_index(n=1500, d=16)
    q = jnp.asarray(X[:4])
    k, n_probe = 12, 3
    r_g = executor.execute_plan(
        idx, executor.plan_ann_gather(idx, q, k, n_probe),
        quantized=quantized)
    r_u = executor.execute_plan(
        idx, executor.plan_ann(idx, q, k, n_probe), quantized=quantized)
    np.testing.assert_array_equal(np.asarray(r_g.ids), np.asarray(r_u.ids))
    np.testing.assert_allclose(np.asarray(r_g.scores),
                               np.asarray(r_u.scores), rtol=1e-5, atol=1e-5)


def test_small_q_bucket_shares_one_trace():
    # Q=5/7/8 all bucket to 8 <= SMALL_Q_GATHER_MAX: the gather selection
    # is static per (spec, bucket), so no retrace across the bucket
    idx, X = _mk_index(n=1000, d=16)
    spec = Q.knn(k=10, n_probe=3)
    executor.run(idx, jnp.asarray(X[:5]), spec)         # warm bucket 8
    t0 = executor.trace_count()
    r7 = executor.run(idx, jnp.asarray(X[:7]), spec)
    r8 = executor.run(idx, jnp.asarray(X[:8]), spec)
    assert executor.trace_count() == t0, \
        "same (spec, Q-bucket) must not retrace"
    assert np.asarray(r7.ids).shape[0] == 7
    assert np.asarray(r8.ids).shape[0] == 8


def test_run_routes_small_q_through_gather_same_results():
    # end-to-end: run() on a small batch (gather path) agrees with the
    # forced shared-union plan on ids
    idx, X = _mk_index(n=1500, d=16)
    q = jnp.asarray(X[:3])
    spec = Q.knn(k=10, n_probe=4)
    r = executor.run(idx, q, spec)
    plan = executor.plan_ann(idx, q, k=10, n_probe=4)
    r_u = executor.execute_plan(idx, plan)
    np.testing.assert_array_equal(np.asarray(r.ids), np.asarray(r_u.ids))


# -- code_norms invariant -----------------------------------------------------


def test_code_norms_tracks_codes_through_build_and_grow():
    idx, X = _mk_index()
    assert idx.code_norms is not None
    np.testing.assert_array_equal(
        np.asarray(idx.code_norms),
        np.asarray(quantize.row_norms(idx.qstats, idx.codes)))
    grown = ivf.grow_layout(idx, idx.vectors.shape[1] + 32)
    np.testing.assert_array_equal(
        np.asarray(grown.code_norms),
        np.asarray(quantize.row_norms(grown.qstats, grown.codes)))


def test_code_norms_tracks_codes_through_flush():
    from repro.core import delta, maintenance
    idx, X = _mk_index(n=800, d=16, delta_capacity=128)
    rng = np.random.default_rng(3)
    nv = jnp.asarray(rng.normal(size=(60, 16)).astype(np.float32))
    ids = jnp.arange(10_000, 10_060, dtype=jnp.int32)
    idx = delta.upsert(idx, nv, ids, jnp.zeros((60, 0)))
    idx, _ = maintenance.flush_delta(idx)
    np.testing.assert_array_equal(
        np.asarray(idx.code_norms),
        np.asarray(quantize.row_norms(idx.qstats, idx.codes)))
