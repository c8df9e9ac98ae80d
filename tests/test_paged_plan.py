"""The paged planner as one compiled call (`executor._paged_plan`).

Everything from `paged_search`'s entry to the first `cache.fault` is one
jitted call: the staged query in, the normalised query, its mask, the
probe union and the empty running top-k out. Pins:

  * its probe list and selection mask are bitwise those of eager
    `_probe_union` (Q=1 and a padded Q=3; L2 and cosine);
  * a paged `MicroNN.query` answers with the resident engine's bits on
    both backends (padded Q=3);
  * repeated paged queries of one bucket do not retrace, by the plan's own
    counter, and `executor.trace_count()` does not move for them;
  * an untraced NumPy paged query issues no eager `jnp` op before the
    first fault;
  * `paged_plans_fused` counts paged ANN runs, not exact ones, and the
    traced `probe` span says it ran fused and where the query was staged;
  * the live counts cross with every call, so a write that empties or
    fills a partition changes the next probe set.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import executor
from repro.core.query import Q
from repro.core.types import IVFConfig, normalize_if_cosine
from repro.obs import metrics as obs_metrics
from repro.storage import MicroNN
from repro.storage import engine as engine_mod
from repro.storage import pager as pager_mod
from repro.core import types as types_mod
from tests.conftest import clustered_data

DIM = 16
N = 900
BACKENDS = ["xla", "pallas"]


def _cfg(metric="l2", quant="int8"):
    return IVFConfig(dim=DIM, metric=metric, target_partition_size=40,
                     kmeans_iters=10, delta_capacity=64, quantize=quant,
                     rerank_factor=4)


def _paged(tmp_path, name, metric="l2", quant="int8"):
    X = clustered_data(n=N, dim=DIM, seed=4)
    e = MicroNN(dim=DIM, path=str(tmp_path / f"{name}.db"),
                config=_cfg(metric, quant), memory_budget_mb=0.05)
    e.upsert(np.arange(N), X)
    e.build()
    return e, X


@pytest.fixture(scope="module", params=["l2", "cosine"])
def paged_by_metric(request, tmp_path_factory):
    e, X = _paged(tmp_path_factory.mktemp("plan"), request.param,
                  metric=request.param)
    yield e, X
    e.store.close()


@pytest.fixture(scope="module")
def pair(tmp_path_factory):
    """(resident, paged) engines recovered from the same durable state."""
    path = str(tmp_path_factory.mktemp("plan_pair") / "pair.db")
    X = clustered_data(n=N, dim=DIM, seed=5)
    eng = MicroNN(dim=DIM, path=path, config=_cfg())
    eng.upsert(np.arange(N), X)
    eng.build()
    eng.store.db.commit()
    res = MicroNN(dim=DIM, path=path, config=_cfg())
    res.recover()
    pag = MicroNN(dim=DIM, path=path, config=_cfg(), memory_budget_mb=0.02)
    pag.recover()
    yield res, pag, X
    for e in (eng, res, pag):
        e.store.close()


def _queries(X, n):
    return X[11:11 + n] + np.float32(0.01)


def _fused():
    return obs_metrics.default_registry().counter(
        "paged_plans_fused", component="executor").value


@pytest.mark.parametrize("n", [1, 3], ids=["q1", "q3-padded"])
def test_plan_probes_are_bitwise_the_eager_probe_union(paged_by_metric, n):
    e, X = paged_by_metric
    pidx = e.index
    metric = pidx.config.metric
    q, qmask, n_real, on_device = executor._stage_batch(_queries(X, n))
    assert not on_device and n_real == n and q.shape[0] == (1 if n == 1
                                                            else 4)
    qn, qm, upart, qsel, run_s, run_i = executor._paged_plan(
        q, qmask, pidx.centroids, np.asarray(pidx.counts, np.int32),
        n_probe=6, metric=metric, k_want=40, p_max=pidx.p_max)
    q_ref = normalize_if_cosine(jnp.asarray(q), metric)
    counts = jnp.asarray(np.asarray(pidx.counts), jnp.int32)
    up_ref, qsel_ref = executor._probe_union(
        pidx.centroids, counts, metric, q_ref, 6,
        qmask=jnp.asarray(qmask))
    np.testing.assert_array_equal(np.asarray(upart), np.asarray(up_ref))
    assert upart.dtype == up_ref.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(qsel), np.asarray(qsel_ref))
    np.testing.assert_array_equal(np.asarray(qm), qmask)
    np.testing.assert_allclose(np.asarray(qn), np.asarray(q_ref),
                               rtol=1e-6, atol=1e-7)
    k_run = min(40, upart.shape[0] * pidx.p_max)
    assert run_s.shape == run_i.shape == (q.shape[0], k_run)
    assert bool((run_i == -1).all()) and bool((run_s >= 3e38).all())


@pytest.mark.parametrize("backend", BACKENDS)
def test_paged_query_is_bitwise_the_resident_answer(pair, backend):
    res, pag, X = pair
    assert pag.index.cache.capacity < pag.index.k     # the budget pages
    spec = Q.knn(k=10, n_probe=8).backend(backend)
    q = _queries(X, 3)
    ids_r, scores_r = res.query(q, spec).to_numpy()
    f0 = _fused()
    ids_p, scores_p = pag.query(q, spec).to_numpy()
    assert _fused() == f0 + 1
    assert ids_p.shape == (3, 10)
    np.testing.assert_array_equal(ids_p, ids_r)
    np.testing.assert_array_equal(scores_p.view(np.int32),
                                  scores_r.view(np.int32))


def test_repeated_paged_queries_of_one_bucket_do_not_retrace(pair):
    _, pag, X = pair
    spec = Q.knn(k=7, n_probe=5)
    pag.query(X[:3], spec).to_numpy()            # compile the bucket of 4
    p0, c0 = executor.paged_plan_trace_count(), executor.trace_count()
    for i in range(3):
        for q in (X[i:i + 3], jnp.asarray(X[i:i + 4]),
                  X[i:i + 4].astype(np.float64)):
            pag.query(q, spec).to_numpy()
    assert executor.paged_plan_trace_count() == p0
    assert executor.trace_count() == c0
    # a new bucket compiles the plan once more, and only once
    pag.query(X[:1], spec).to_numpy()
    pag.query(X[1:2], spec).to_numpy()
    assert executor.paged_plan_trace_count() == p0 + 1
    assert executor.trace_count() == c0
    reg = obs_metrics.default_registry()
    assert reg.gauge("paged_plan_trace_count",
                     component="executor").value == p0 + 1


class _NoJnp:
    """Stands in for a module's `jnp` while armed: any function of
    jax.numpy raises (its dtypes pass), so no eager device op can pass
    unseen."""

    def __init__(self):
        self.armed = True

    def __getattr__(self, name):
        fn = getattr(jnp, name)
        if not callable(fn) or isinstance(fn, type):
            return fn

        def guarded(*a, **kw):
            if self.armed:
                raise AssertionError(f"eager jnp.{name} before the fault")
            return fn(*a, **kw)
        return guarded


@pytest.mark.parametrize("backend", BACKENDS)
def test_untraced_host_paged_query_issues_no_eager_device_op(
        pair, monkeypatch, backend):
    _, pag, X = pair
    spec = Q.knn(k=5, n_probe=4).backend(backend)
    q = X[9:10] + np.float32(0.01)
    ids_ref, scores_ref = pag.query(q, spec).to_numpy()   # warm the plan
    guard = _NoJnp()
    plan = executor._paged_plan
    fault = pag.index.cache.fault
    seen = []

    def checked_plan(queries, qmask, centroids, counts, **kw):
        seen.append((type(queries), type(qmask), type(counts),
                     counts.dtype))
        return plan(queries, qmask, centroids, counts, **kw)

    def checked_fault(*a, **kw):
        guard.armed = False
        return fault(*a, **kw)

    for mod in (executor, engine_mod, types_mod, pager_mod):
        monkeypatch.setattr(mod, "jnp", guard)
    monkeypatch.setattr(executor, "_paged_plan", checked_plan)
    monkeypatch.setattr(pag.index.cache, "fault", checked_fault)
    rs = pag.query(q, spec)
    assert rs.trace is None
    assert not guard.armed                      # the fault was reached
    assert seen == [(np.ndarray, np.ndarray, np.ndarray, np.int32)]
    monkeypatch.undo()
    ids, scores = rs.to_numpy()
    np.testing.assert_array_equal(ids, ids_ref)
    np.testing.assert_array_equal(scores, scores_ref)


@pytest.mark.parametrize("staged", ["host", "device"])
def test_paged_plans_fused_counts_ann_runs_and_the_probe_span(pair, staged):
    _, pag, X = pair
    q = _queries(X, 3)
    if staged == "device":
        q = jnp.asarray(q)
    f0 = _fused()
    pag.query(q, Q.knn(k=5, n_probe=4)).to_numpy()
    assert _fused() == f0 + 1                   # one run of three queries
    pag.query(q[:1], Q.knn(k=5, n_probe=4)).to_numpy()
    assert _fused() == f0 + 2
    pag.query(q[:1], Q.exact(k=5)).to_numpy()   # exact does not probe
    assert _fused() == f0 + 2
    tr = pag.query(q, Q.knn(k=5, n_probe=4), trace=True).trace
    assert _fused() == f0 + 3
    assert tr.counter("probe", "fused") == 1
    assert tr.counter("probe", "staged") == staged
    assert tr.counter("probe", "partitions") == min(4 * 4, pag.index.k)
    assert tr.get("probe").dur_ms > 0
    tr = pag.query(q[:1], Q.exact(k=5), trace=True).trace
    assert tr.counter("probe", "fused") == 0
    assert tr.counter("probe", "kind") == "exact"
    assert _fused() == f0 + 3


@pytest.mark.parametrize("how", ["delete", "upsert"])
def test_write_that_empties_or_fills_a_partition_moves_the_probe_set(
        tmp_path, monkeypatch, how):
    e, X = _paged(tmp_path, how)
    pidx = e.index
    plan = executor._paged_plan
    probes = []

    def spy(*a, **kw):
        out = plan(*a, **kw)
        probes.append(np.asarray(out[2]))
        return out

    monkeypatch.setattr(executor, "_paged_plan", spy)
    spec = Q.knn(k=5, n_probe=1)
    counts = np.asarray(pidx.counts)
    p = int(np.argmax(counts))
    q = np.asarray(pidx.centroids)[p:p + 1]
    e.query(q, spec).to_numpy()
    assert probes[-1].tolist() == [p]
    members, _ = e.store.scan_partition(p)
    assert len(members) == counts[p] > 0
    if how == "delete":
        e.delete(members)
    else:                                       # move every row far away
        e.upsert(members, np.full((len(members), DIM), 50.0, np.float32))
    assert int(np.asarray(e.index.counts)[p]) == 0
    t0 = executor.paged_plan_trace_count()
    ids, _ = e.query(q, spec).to_numpy()
    assert executor.paged_plan_trace_count() == t0      # counts crossed
    assert probes[-1].tolist() != [p]
    assert not set(ids[0].tolist()) & set(members.tolist())
    # refill: rows at the centroid land in p at the next flush
    new = np.arange(10_000, 10_004)
    e.upsert(new, np.repeat(q, len(new), axis=0))
    assert e.maintain(force="flush") == "flush"
    assert int(np.asarray(e.index.counts)[p]) == len(new)
    ids, _ = e.query(q, spec).to_numpy()
    assert probes[-1].tolist() == [p]
    assert set(ids[0][:len(new)].tolist()) == set(new.tolist())
    e.store.close()
