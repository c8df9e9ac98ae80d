"""Unified query-execution layer: backend parity + plan-cache behaviour.

Parity contract: on an identical QueryPlan the Pallas (interpret) backend
and the XLA reference backend return identical ids, and both match the
exact_search oracle at full probe width (recall@k == 1.0) for ann, mqo
(batched shared-scan) and filtered plans.

Cache contract: repeated queries whose count lands in the same bucket
never retrace the jitted entry point.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import delta, executor, ivf, search
from repro.core.hybrid import And, Pred, compile_filter
from repro.core.types import INVALID_ID, IVFConfig


@pytest.fixture(scope="module", params=["l2", "ip", "cosine"])
def exec_index(request):
    rng = np.random.default_rng(7)
    centers = rng.normal(size=(16, 24)).astype(np.float32) * 5
    X = (centers[rng.integers(0, 16, 1500)]
         + rng.normal(size=(1500, 24))).astype(np.float32)
    attrs = np.stack([rng.integers(0, 8, 1500),
                      rng.normal(size=1500) * 10], 1).astype(np.float32)
    cfg = IVFConfig(dim=24, target_partition_size=50, kmeans_iters=30,
                    delta_capacity=128, metric=request.param)
    idx = ivf.build_index(X, attrs=attrs, cfg=cfg)
    # live delta rows so the epilogue merge is exercised too
    nv = rng.normal(size=(10, 24)).astype(np.float32)
    idx = delta.upsert(idx, jnp.asarray(nv),
                       jnp.arange(5000, 5010, dtype=jnp.int32),
                       jnp.asarray(attrs[:10]))
    return idx, X, attrs


def _ids(res):
    return np.asarray(res.ids)


def test_backend_parity_ann(exec_index):
    idx, X, _ = exec_index
    plan = executor.plan_ann(idx, jnp.asarray(X[:8]), 10, 6)
    rx = executor.execute_plan(idx, plan, backend="xla")
    rp = executor.execute_plan(idx, plan, backend="pallas")
    assert (_ids(rx) == _ids(rp)).all()
    np.testing.assert_allclose(np.asarray(rx.scores), np.asarray(rp.scores),
                               rtol=1e-4, atol=1e-4)


def test_backend_parity_mqo_plan(exec_index):
    idx, X, _ = exec_index
    plan = executor.plan_ann(idx, jnp.asarray(X[:32]), 10, 4, u_max=24)
    rx = executor.execute_plan(idx, plan, backend="xla")
    rp = executor.execute_plan(idx, plan, backend="pallas")
    assert (_ids(rx) == _ids(rp)).all()


def test_backend_parity_filtered(exec_index):
    idx, X, attrs = exec_index
    f = compile_filter(And((Pred(0, "eq", 3.0), Pred(1, "gt", 0.0))))
    plan = executor.plan_ann(idx, jnp.asarray(X[:8]), 10, 8, attr_filter=f)
    rx = executor.execute_plan(idx, plan, backend="xla")
    rp = executor.execute_plan(idx, plan, backend="pallas")
    assert (_ids(rx) == _ids(rp)).all()
    # fused predicate honoured (ids < 5000 index the attrs table)
    for i in _ids(rx).ravel():
        if 0 <= i < 5000:
            assert attrs[i, 0] == 3 and attrs[i, 1] > 0


def test_backend_parity_exact_and_prefilter(exec_index):
    idx, X, _ = exec_index
    f = compile_filter(Pred(0, "eq", 3.0))
    for plan in (executor.plan_exact(idx, jnp.asarray(X[:4]), 10),
                 executor.plan_prefilter(idx, jnp.asarray(X[:4]), 10, f,
                                         cap=512)):
        rx = executor.execute_plan(idx, plan, backend="xla")
        rp = executor.execute_plan(idx, plan, backend="pallas")
        assert (_ids(rx) == _ids(rp)).all(), plan.kind


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_full_probe_matches_exact_oracle(exec_index, backend):
    idx, X, _ = exec_index
    q = jnp.asarray(X[:8])
    oracle = search.exact_search(idx, q, 10)
    plan = executor.plan_ann(idx, q, 10, idx.k)
    res = executor.execute_plan(idx, plan, backend=backend)
    assert float(search.recall_at_k(res, oracle, 10)) == 1.0
    assert (_ids(res) == _ids(oracle)).all()


def test_filtered_plan_matches_filtered_oracle(exec_index):
    idx, X, _ = exec_index
    f = compile_filter(Pred(0, "ne", 3.0))
    q = jnp.asarray(X[:8])
    oracle = search.exact_search(idx, q, 10, attr_filter=f)
    plan = executor.plan_ann(idx, q, 10, idx.k, attr_filter=f)
    for backend in ("xla", "pallas"):
        res = executor.execute_plan(idx, plan, backend=backend)
        assert float(search.recall_at_k(res, oracle, 10)) == 1.0


def test_no_retrace_within_bucket(exec_index):
    idx, X, _ = exec_index
    executor.search(idx, jnp.asarray(X[:5]), k=10, n_probe=6)   # warm bucket 8
    c0 = executor.trace_count()
    executor.search(idx, jnp.asarray(X[:5]), k=10, n_probe=6)   # same shape
    executor.search(idx, jnp.asarray(X[:7]), k=10, n_probe=6)   # same bucket
    executor.search(idx, jnp.asarray(X[:8]), k=10, n_probe=6)   # bucket edge
    assert executor.trace_count() == c0
    executor.search(idx, jnp.asarray(X[:9]), k=10, n_probe=6)   # new bucket
    assert executor.trace_count() == c0 + 1


def test_no_retrace_repeated_predicate(exec_index):
    idx, X, _ = exec_index
    q = jnp.asarray(X[:4])
    pred = And((Pred(0, "eq", 2.0), Pred(1, "le", 5.0)))
    executor.search(idx, q, k=5, n_probe=4,
                    attr_filter=compile_filter(pred))
    c0 = executor.trace_count()
    # a structurally equal predicate compiles to the *same* callable, so
    # the jit cache key (predicate_id) is stable across calls
    pred2 = And((Pred(0, "eq", 2.0), Pred(1, "le", 5.0)))
    executor.search(idx, q, k=5, n_probe=4,
                    attr_filter=compile_filter(pred2))
    assert executor.trace_count() == c0


def test_bucket_padding_is_invisible(exec_index):
    """Results for Q queries must not depend on bucket padding rows."""
    idx, X, _ = exec_index
    q5 = jnp.asarray(X[:5])
    res5 = executor.search(idx, q5, k=10, n_probe=6)            # bucket 8
    res5_nb = executor.search(idx, q5, k=10, n_probe=6, bucket=False)
    assert res5.ids.shape[0] == 5
    assert (_ids(res5) == _ids(res5_nb)).all()


def test_invalid_fill_when_under_k(exec_index):
    idx, X, _ = exec_index
    f = compile_filter(And((Pred(0, "eq", 3.0), Pred(1, "gt", 25.0))))
    res = executor.search(idx, jnp.asarray(X[:2]), k=50, kind="exact",
                          attr_filter=f)
    ids = _ids(res)
    n_match = (ids >= 0).sum(axis=1)
    # highly selective predicate: fewer than k matches, rest INVALID
    assert (ids != INVALID_ID).any()
    assert ((ids == INVALID_ID) == (np.asarray(res.scores) >= 1e37)).all()
    assert (n_match < 50).all()
