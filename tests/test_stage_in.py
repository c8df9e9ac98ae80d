"""Query staging before the jitted resident call (`stage_in`).

Host queries (NumPy) are cast, padded and masked with NumPy and cross to
the device inside the jitted `_run_spec` call; a caller's jax.Array keeps
the device staging. Pins:

  * both stagings give bitwise-identical ids and scores, on both
    backends, for Q=1, a padded Q=3 and a float64 input;
  * both hit the same trace: alternating them within one bucket never
    retraces;
  * an untraced `MicroNN.query` on a NumPy query issues no eager device
    op before `_run_spec` (the staging counters and patched `jnp`);
  * `run_coalesced` on host chunks answers each caller as the
    device-staged fused run does, and as its solo run on the float32
    tier.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import executor
from repro.core.query import Q
from repro.core.types import IVFConfig
from repro.obs import metrics as obs_metrics
from repro.storage import MicroNN
from repro.storage import engine as engine_mod
from tests.conftest import clustered_data

DIM = 16
BACKENDS = ["xla", "pallas"]


def _engine(tmp_path_factory, name, **quant):
    cfg = IVFConfig(dim=DIM, target_partition_size=50, kmeans_iters=8,
                    delta_capacity=64, **quant)
    path = tmp_path_factory.mktemp("stage_in") / f"{name}.db"
    e = MicroNN(dim=DIM, path=str(path), config=cfg)
    X = clustered_data(n=400, dim=DIM, seed=0)
    e.upsert(np.arange(400), X)
    e.build()
    e.X = X
    return e


@pytest.fixture(scope="module")
def eng(tmp_path_factory):
    """int8 codes + float32 rerank, as the benchmark's resident cell."""
    e = _engine(tmp_path_factory, "int8", quantize="int8", rerank_factor=4)
    yield e
    e.store.close()


@pytest.fixture(scope="module")
def eng32(tmp_path_factory):
    """float32 scan tier only, as the front door's parity tests."""
    e = _engine(tmp_path_factory, "f32")
    yield e
    e.store.close()


def _staged():
    reg = obs_metrics.default_registry()
    return tuple(reg.counter(name, component="executor").value
                 for name in ("queries_staged_host",
                              "queries_staged_device"))


def _host_queries(X, case):
    rng = np.random.default_rng(11)
    n = {"q1": 1, "q3": 3, "f64": 2}[case]
    q = X[5:5 + n].astype(np.float64) + rng.normal(scale=0.01,
                                                   size=(n, DIM))
    return q if case == "f64" else q.astype(np.float32)


@pytest.mark.parametrize("case", ["q1", "q3", "f64"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_host_and_device_staging_are_bitwise_identical(eng, backend, case):
    spec = Q.knn(k=5, n_probe=4).backend(backend)
    qh = _host_queries(eng.X, case)
    qd = jnp.asarray(qh, jnp.float32)       # the device staging's input
    h0, d0 = _staged()
    ids_h, scores_h = executor.run(eng.index, qh, spec).to_numpy()
    assert _staged() == (h0 + 1, d0)
    ids_d, scores_d = executor.run(eng.index, qd, spec).to_numpy()
    assert _staged() == (h0 + 1, d0 + 1)
    assert ids_h.shape == (qh.shape[0], 5)
    np.testing.assert_array_equal(ids_h, ids_d)
    assert scores_h.dtype == scores_d.dtype == np.float32
    np.testing.assert_array_equal(scores_h.view(np.int32),
                                  scores_d.view(np.int32))
    # the engine's own entry point answers the same bits
    ids_e, scores_e = eng.query(qh, spec).to_numpy()
    np.testing.assert_array_equal(ids_e, ids_h)
    np.testing.assert_array_equal(scores_e.view(np.int32),
                                  scores_h.view(np.int32))


@pytest.mark.parametrize("backend", BACKENDS)
def test_alternating_host_and_device_inputs_do_not_retrace(eng, backend):
    spec = Q.knn(k=6, n_probe=3).backend(backend)
    X = eng.X
    eng.query(X[:3], spec).to_numpy()          # compile the bucket of 4
    c0 = executor.trace_count()
    for i in range(3):
        for q in (X[i:i + 3], jnp.asarray(X[i:i + 4]),
                  X[i:i + 4].astype(np.float64), jnp.asarray(X[i:i + 3])):
            executor.run(eng.index, q, spec).to_numpy()
            eng.query(q, spec).to_numpy()
    assert executor.trace_count() == c0


class _NoEagerJnp:
    """Stands in for a module's `jnp` while armed: the eager staging ops
    raise, everything else passes through to jax.numpy."""

    def __init__(self):
        self.armed = True

    def __getattr__(self, name):
        fn = getattr(jnp, name)
        if name not in ("asarray", "arange", "concatenate"):
            return fn

        def guarded(*a, **kw):
            if self.armed:
                raise AssertionError(f"eager jnp.{name} before _run_spec")
            return fn(*a, **kw)
        return guarded


@pytest.mark.parametrize("backend", BACKENDS)
def test_untraced_host_query_issues_no_eager_device_op(eng, monkeypatch,
                                                      backend):
    spec = Q.knn(k=5, n_probe=4).backend(backend)
    q = eng.X[9:10] + np.float32(0.01)
    ids_ref, scores_ref = eng.query(q, spec).to_numpy()   # warm the bucket
    guard = _NoEagerJnp()
    run_spec = executor._run_spec
    seen = []

    def checked_run_spec(index, queries, qmask, spec_):
        seen.append((type(queries), type(qmask)))
        guard.armed = False
        return run_spec(index, queries, qmask, spec_)

    monkeypatch.setattr(executor, "jnp", guard)
    monkeypatch.setattr(engine_mod, "jnp", guard)
    monkeypatch.setattr(executor, "_run_spec", checked_run_spec)
    h0, d0 = _staged()
    rs = eng.query(q, spec)
    assert rs.trace is None
    assert _staged() == (h0 + 1, d0)
    assert seen == [(np.ndarray, np.ndarray)]
    monkeypatch.undo()
    ids, scores = rs.to_numpy()
    np.testing.assert_array_equal(ids, ids_ref)
    np.testing.assert_array_equal(scores, scores_ref)


@pytest.mark.parametrize("backend", BACKENDS)
def test_coalesced_host_chunks_match_solo_runs(eng, eng32, backend):
    """One fused host-staged run answers each caller with the bits of
    the device-staged fused run; on the float32 tier those are also its
    solo run's bits (the front door's contract). The int8 rerank's
    scores depend on the batch's bucket on the CPU, on either staging,
    so there the solo comparison is left to the ids."""
    spec = Q.knn(k=5, n_probe=4).backend(backend)
    for e in (eng32, eng):
        X = e.X
        chunks = [X[20], X[21:23] + np.float32(0.01),
                  X[30:31].astype(np.float64)]
        solo = [executor.run(e.index, c, spec).to_numpy() for c in chunks]
        on_dev = [jnp.asarray(c, jnp.float32) for c in chunks]
        h0, d0 = _staged()
        outs = executor.run_coalesced(e.index, chunks, spec)
        assert _staged() == (h0 + 1, d0)        # one fused host-staged run
        ref = executor.run_coalesced(e.index, on_dev, spec)
        assert _staged() == (h0 + 1, d0 + 1)
        # a device chunk among host ones keeps the device staging
        mixed = executor.run_coalesced(
            e.index, [chunks[0], on_dev[1], chunks[2]], spec)
        assert _staged() == (h0 + 1, d0 + 2)
        assert len(outs) == len(ref) == len(mixed) == len(chunks)
        for rs, rd, rm, (ids_s, scores_s) in zip(outs, ref, mixed, solo):
            ids_c, scores_c = rs.to_numpy()
            for other in (rd, rm):
                ids_o, scores_o = other.to_numpy()
                np.testing.assert_array_equal(ids_c, ids_o)
                np.testing.assert_array_equal(scores_c.view(np.int32),
                                              scores_o.view(np.int32))
            np.testing.assert_array_equal(ids_c, ids_s)
            if e is eng32:
                np.testing.assert_array_equal(scores_c.view(np.int32),
                                              scores_s.view(np.int32))
    assert isinstance(executor.as_query_batch(jnp.zeros(DIM)), jax.Array)
    assert isinstance(executor.as_query_batch([0.0] * DIM), np.ndarray)
