"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("dim", [16, 64, 128])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_scan_topk_sweep(dim, metric):
    rng = np.random.default_rng(dim)
    k, p_max, Q, n, K = 10, 24, 5, 4, 8
    vectors = jnp.asarray(rng.normal(size=(k, p_max, dim)).astype(np.float32))
    valid = jnp.asarray(rng.random((k, p_max)) > 0.25)
    ids = jnp.arange(k * p_max, dtype=jnp.int32).reshape(k, p_max)
    queries = jnp.asarray(rng.normal(size=(Q, dim)).astype(np.float32))
    part_ids = jnp.asarray(rng.choice(k, n, replace=False).astype(np.int32))
    s_k, i_k = ops.scan_topk(queries, vectors, valid, ids, part_ids, K,
                             metric=metric)
    s_r, i_r = ref.ivf_scan_ref(queries, vectors, valid, ids, part_ids, K,
                                metric=metric)
    assert (np.asarray(i_k) == np.asarray(i_r)).all()
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_scan_topk_dtypes(dtype):
    rng = np.random.default_rng(3)
    k, p_max, dim, Q, n, K = 6, 16, 32, 3, 3, 5
    vectors = jnp.asarray(rng.normal(size=(k, p_max, dim))).astype(dtype)
    valid = jnp.ones((k, p_max), bool)
    ids = jnp.arange(k * p_max, dtype=jnp.int32).reshape(k, p_max)
    queries = jnp.asarray(rng.normal(size=(Q, dim))).astype(dtype)
    part_ids = jnp.arange(n, dtype=jnp.int32)
    s_k, i_k = ops.scan_topk(queries, vectors, valid, ids, part_ids, K)
    s_r, i_r = ref.ivf_scan_ref(queries.astype(jnp.float32),
                                vectors.astype(jnp.float32), valid, ids,
                                part_ids, K)
    # bf16 rounding can swap near-ties; compare sets + scores loosely
    tol = 1e-4 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=tol, atol=tol)


def _mqo_case(Q):
    rng = np.random.default_rng(5)
    k, p_max, dim, n, K = 8, 16, 32, 5, 6
    vectors = jnp.asarray(rng.normal(size=(k, p_max, dim)).astype(np.float32))
    valid = jnp.asarray(rng.random((k, p_max)) > 0.1)
    ids = jnp.arange(k * p_max, dtype=jnp.int32).reshape(k, p_max)
    queries = jnp.asarray(rng.normal(size=(Q, dim)).astype(np.float32))
    part_ids = jnp.asarray(rng.choice(k, n, replace=False).astype(np.int32))
    qsel = jnp.asarray(rng.random((Q, n)) > 0.4)
    s_k, i_k = ops.scan_topk_mqo(queries, vectors, valid, ids, part_ids,
                                 qsel, K)
    s_r, i_r = ref.ivf_scan_ref(queries, vectors, valid, ids, part_ids, K,
                                qsel=qsel)
    assert (np.asarray(i_k) == np.asarray(i_r)).all()


def test_scan_topk_mqo_mask():
    _mqo_case(6)


def test_scan_topk_mqo_mask_query_tiles():
    _mqo_case(70)       # three 32-row query tiles, the last one padded


@pytest.mark.parametrize("k_cent,tile", [(100, 32), (256, 128), (300, 256)])
def test_kmeans_assign_sweep(k_cent, tile):
    rng = np.random.default_rng(k_cent)
    s, d = 48, 24
    batch = jnp.asarray(rng.normal(size=(s, d)).astype(np.float32))
    cents = jnp.asarray(rng.normal(size=(k_cent, d)).astype(np.float32))
    counts = jnp.asarray(rng.integers(0, 300, k_cent).astype(np.float32))
    a_k, d_k = ops.assign_nearest(batch, cents, counts, balance_weight=1.5,
                                  target_size=100, scale=4.0, tile_k=tile)
    a_r, d_r = ref.kmeans_assign_ref(batch, cents, counts, 1.5, 100, 4.0)
    assert (np.asarray(a_k) == np.asarray(a_r)).all()
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r),
                               rtol=1e-3, atol=1e-3)


def test_kernel_topk_handles_all_masked():
    """Partitions with zero valid rows must yield INVALID_ID fills."""
    k, p_max, dim, Q = 4, 8, 16, 2
    vectors = jnp.zeros((k, p_max, dim))
    valid = jnp.zeros((k, p_max), bool)
    ids = jnp.arange(k * p_max, dtype=jnp.int32).reshape(k, p_max)
    queries = jnp.ones((Q, dim))
    s, i = ops.scan_topk(queries, vectors, valid, ids,
                         jnp.arange(2, dtype=jnp.int32), 5)
    assert (np.asarray(i) == -1).all()
