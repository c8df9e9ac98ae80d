"""Fleet flight recorder + SLO health + exposition endpoint (PR 10).

Pins the tentpole contracts:

  * **capture -> replay is bit-exact** -- a workload captured by the
    flight recorder (resident xla + pallas, paged, and multi-tenant
    through Fleet) replays to bit-identical ids AND exact-f32 scores;
  * **bounded + sampled** -- max_records caps the file, sample_every=N
    keeps exactly every Nth call, recording-off captures nothing;
  * **noisy-neighbor attribution** -- every cross-tenant CLOCK eviction
    lands in the (victim, evictor) matrix and its registry counters,
    and 1000 synthetic tenants stay inside the registry's per-name
    cardinality guard;
  * **SLO health** -- Fleet.health() has a pinned schema, burns error
    budget off the per-tenant latency histograms, and flips tenants to
    "degraded" exactly when their burn rate exceeds 1;
  * **manifest** -- the tenant directory is the SQLite manifest, not
    the filesystem: create/drop are transactional, recover() reports
    orphan files and missing stores, health() surfaces both;
  * **exposition endpoint** -- /metrics, /healthz, /traces, /events
    serve well-formed output during a live workload without taking the
    engine write mutex and without perturbing results.
"""
import json
import os
import shutil
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.core.query import Q
from repro.core.types import IVFConfig
from repro.fleet import Fleet, FramePool, TenantSLO
from repro.obs import metrics as obs_metrics
from repro.obs import recorder as obs_recorder
from repro.obs.http import ExpositionServer
from repro.storage import MicroNN
from tests.conftest import clustered_data

DIM = 16


def _mk(tmp_path, name, *, paged=False, n=400, seed=0, **eng_kw):
    cfg = IVFConfig(dim=DIM, target_partition_size=50, kmeans_iters=8,
                    delta_capacity=64)
    eng = MicroNN(dim=DIM, path=str(tmp_path / f"{name}.db"), config=cfg,
                  memory_budget_mb=0.05 if paged else None, **eng_kw)
    X = clustered_data(n=n, dim=DIM, seed=seed)
    eng.upsert(np.arange(n), X)
    eng.build()
    return eng, X


def _mk_fleet(tmp_path, *, tenants=("a", "b"), n=300, budget_mb=0.5,
              **kw):
    cfg = IVFConfig(dim=DIM, target_partition_size=50, kmeans_iters=4)
    fleet = Fleet(str(tmp_path / "fleet"), dim=DIM, budget_mb=budget_mb,
                  config=cfg, **kw)
    X = clustered_data(n=n, dim=DIM, seed=3)
    for t in tenants:
        eng = fleet.get(t)
        with eng.session() as s:
            s.upsert(np.arange(n), X)
        eng.build()
    return fleet, X


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read()


# -- capture / replay --------------------------------------------------------


@pytest.mark.parametrize("paged", [False, True])
def test_replay_bit_identical_engine(tmp_path, paged):
    eng, X = _mk(tmp_path, f"rep{paged}", paged=paged)
    cap = str(tmp_path / "cap.db")
    specs = [Q.knn(k=5, n_probe=4).backend("xla"),
             Q.knn(k=3, n_probe=4).backend("pallas"),
             Q.knn(k=7, n_probe=4)]
    with obs_recorder.recording(cap) as rec:
        for i, spec in enumerate(specs):
            eng.query(X[i:i + 2], spec)
        assert rec.recorded == len(specs)
    rep = obs_recorder.replay(cap, engine=eng, strict=True)
    assert rep.ok and rep.replayed == len(specs) \
        and rep.matched == len(specs)
    eng.store.close()


def test_replay_detects_divergence(tmp_path):
    """A store mutated between capture and replay MUST be caught: the
    digest compare is the whole point, not a formality."""
    eng, X = _mk(tmp_path, "div")
    cap = str(tmp_path / "cap.db")
    with obs_recorder.recording(cap):
        eng.query(X[:2], Q.knn(k=3, n_probe=4))
    # shift every stored vector: same ids, different scores
    eng.upsert(np.arange(200), X[:200] + 1.0)
    eng.maintain(force="flush")
    rep = obs_recorder.replay(cap, engine=eng)
    assert not rep.ok and rep.mismatches
    with pytest.raises(AssertionError):
        obs_recorder.replay(cap, engine=eng, strict=True)
    eng.store.close()


def test_replay_multi_tenant_fleet(tmp_path):
    fleet, X = _mk_fleet(tmp_path, tenants=("a", "b", "c"))
    cap = str(tmp_path / "cap.db")
    with obs_recorder.recording(cap):
        for i in range(6):
            fleet.query(f"{'abc'[i % 3]}", X[i:i + 2],
                        Q.knn(k=4, n_probe=4))
    recs = obs_recorder.load(cap)
    # every engine.query capture carries its tenant + digest; the
    # fleet.get touches interleave as events
    sites = {r.site for r in recs}
    assert obs_recorder.SITE_ENGINE in sites
    assert obs_recorder.SITE_FLEET_GET in sites
    rep = obs_recorder.replay(cap, fleet=fleet, strict=True)
    assert rep.ok and rep.replayed == 6 and rep.events == 6
    fleet.close()


def test_recorder_bounded_and_sampled(tmp_path):
    eng, X = _mk(tmp_path, "bnd")
    spec = Q.knn(k=3, n_probe=4)
    cap1 = str(tmp_path / "cap1.db")
    with obs_recorder.recording(cap1, sample_every=3) as rec:
        for i in range(9):
            eng.query(X[i:i + 1], spec)
    assert rec.recorded == 3                    # every 3rd call
    assert len(obs_recorder.load(cap1)) == 3
    cap2 = str(tmp_path / "cap2.db")
    with obs_recorder.recording(cap2, max_records=4) as rec:
        for i in range(10):
            eng.query(X[i:i + 1], spec)
        assert rec.stats()["full"]
    assert len(obs_recorder.load(cap2)) == 4    # capped, not crashed
    # recording off: nothing captured, hooks take the one-branch path
    assert obs_recorder.active() is None
    eng.query(X[:1], spec)
    assert len(obs_recorder.load(cap2)) == 4
    eng.store.close()


def test_recorder_unpicklable_spec_dropped(tmp_path):
    eng, X = _mk(tmp_path, "unp")
    cap = str(tmp_path / "cap.db")

    class Opaque:                               # lambda-style: no pickle
        def __reduce__(self):
            raise TypeError("not picklable")

    with obs_recorder.recording(cap) as rec:
        rec.record(obs_recorder.SITE_ENGINE, None, X[:1], Opaque())
        eng.query(X[:1], Q.knn(k=3, n_probe=4))
        st = rec.stats()
        assert st["dropped"] == 1
    recs = obs_recorder.load(cap)               # only the sound record
    assert len(recs) == 1 and recs[0].digest is not None
    assert obs_recorder.replay(cap, engine=eng, strict=True).ok
    eng.store.close()


def test_frontdoor_capture_replays(tmp_path):
    from repro.serving import FrontDoor
    eng, X = _mk(tmp_path, "fd")
    cap = str(tmp_path / "cap.db")
    fd = FrontDoor(eng)
    spec = Q.knn(k=5, n_probe=4)
    with obs_recorder.recording(cap):
        futs = [fd.submit(X[i:i + 1], spec) for i in range(6)]
        for f in futs:
            f.result(timeout=30)
    fd.close()
    recs = obs_recorder.load(cap, sites=[obs_recorder.SITE_FRONTDOOR])
    assert len(recs) == 6 and all(r.digest is None for r in recs)
    # digestless records self-check by double execution -- coalesced
    # admission replayed solo is still bit-stable (PR 7 parity)
    rep = obs_recorder.replay(cap, engine=eng, strict=True)
    assert rep.ok and rep.self_checked == 6
    eng.store.close()


def test_frontdoor_solo_dispatch_records_once(tmp_path):
    # one blocking caller: every window holds a single request, which the
    # dispatcher runs through the engine's solo path -- captured at
    # admission only, never a second time at the engine site
    from repro.serving import FrontDoor
    eng, X = _mk(tmp_path, "fd1")
    cap = str(tmp_path / "cap.db")
    spec = Q.knn(k=5, n_probe=4)
    with FrontDoor(eng) as fd, obs_recorder.recording(cap) as rec:
        for i in range(4):
            fd.query(X[i:i + 1], spec, timeout=30)
        assert rec.recorded == 4
    assert fd.stats()["batches"] == 0
    recs = obs_recorder.load(cap)
    assert [r.site for r in recs] == [obs_recorder.SITE_FRONTDOOR] * 4
    eng.store.close()


# -- noisy-neighbor attribution ----------------------------------------------


def test_eviction_matrix_attributes_cross_tenant(tmp_path):
    # budget ~4 frames: two tenants with disjoint hot sets MUST evict
    # each other; the matrix has to say so, by name
    fleet, X = _mk_fleet(tmp_path, tenants=("alice", "bob"),
                         budget_mb=0.02)
    spec = Q.knn(k=4, n_probe=8)
    for i in range(12):
        fleet.query("alice", X[i:i + 1], spec)
        fleet.query("bob", X[i + 1:i + 2], spec)
    st = fleet.pool.stats()
    matrix = st["eviction_matrix"]
    assert matrix, "no evictions recorded under a 4-frame budget"
    pairs = {(v, e) for v, row in matrix.items() for e in row}
    assert any(v != e for v, e in pairs), \
        f"expected cross-tenant evictions, got {pairs}"
    total = sum(n for row in matrix.values() for n in row.values())
    top = fleet.pool.top_evictors(3)
    assert top and top[0]["evictions"] <= total
    assert {"evictor", "victim", "evictions"} <= set(top[0])
    # the registry counters carry the same attribution
    snap = obs_metrics.default_registry().snapshot()["counters"]
    attributed = {k: v for k, v in snap.items()
                  if k.startswith("evictions_attributed")
                  and ("alice" in k or "bob" in k)}
    assert sum(attributed.values()) >= total > 0
    fleet.close()


def test_attribution_cardinality_bounded_1000_tenants():
    """1000 synthetic tenants evicting each other must not grow the
    registry without bound: the per-name LRU guard caps the series and
    the pool matrix folds overflow pairs into one bucket."""
    reg = obs_metrics.default_registry()
    evicted0 = reg.counter("obs_series_evicted").value
    pool = FramePool(dim=4, p_max=8, budget_bytes=1 << 16)
    with pool._lock:
        for i in range(1000):
            pool._note_eviction(i, (i + 1) % 1000)
    with reg._lock:
        n_series = len(reg._by_name.get("evictions_attributed", ()))
    assert n_series <= reg.max_series_per_name
    evicted = reg.counter("obs_series_evicted").value - evicted0
    assert evicted >= 1000 - reg.max_series_per_name
    st = pool.stats()
    n_pairs = sum(len(r) for r in st["eviction_matrix"].values())
    assert n_pairs + st["eviction_matrix_overflow"] == 1000
    assert n_pairs <= pool.attr_max_pairs


# -- SLO layer + health ------------------------------------------------------


def test_health_schema_and_slo_verdicts(tmp_path):
    fleet, X = _mk_fleet(tmp_path, tenants=("fast", "slow"))
    for i in range(8):
        fleet.query("fast", X[i:i + 1], Q.knn(k=3, n_probe=4))
        fleet.query("slow", X[i:i + 1], Q.knn(k=3, n_probe=4))
    # generous objective: inside budget; absurd objective: every query
    # (compile included) violates it -> burn >> 1 -> degraded
    fleet.set_slo("fast", p99_ms=600_000.0, target=0.5)
    fleet.set_slo("slow", p99_ms=1e-6, target=0.99)
    h = fleet.health()
    # pinned schema (the /healthz document)
    assert set(h) == {"schema", "status", "tenants", "degraded", "pool",
                      "daemon_alive", "live_tenants", "noisy_neighbors",
                      "manifest"}
    assert h["schema"] == 1
    assert set(h["pool"]) == {"budget_bytes", "resident_bytes",
                              "pressure"}
    assert set(h["manifest"]) == {"orphans", "missing"}
    t = h["tenants"]["fast"]
    assert set(t) == {"verdict", "queries", "p99_ms", "objective_ms",
                      "target", "violation_fraction", "burn_rate"}
    assert t["verdict"] == "ok" and t["burn_rate"] <= 1.0
    assert t["queries"] >= 8
    s = h["tenants"]["slow"]
    assert s["verdict"] == "degraded" and s["burn_rate"] > 1.0
    assert "slow" in h["degraded"] and h["status"] == "degraded"
    assert 0.0 < h["pool"]["pressure"] <= 1.0
    assert json.dumps(h)                       # JSON-serializable as-is
    fleet.close()


def test_slo_default_and_override(tmp_path):
    fleet, _ = _mk_fleet(tmp_path, tenants=("a",),
                         slo=TenantSLO(p99_ms=123.0, target=0.9))
    assert fleet.slo_for("a").p99_ms == 123.0
    fleet.set_slo("a", p99_ms=7.0, target=0.95)
    assert fleet.slo_for("a") == TenantSLO(p99_ms=7.0, target=0.95)
    assert fleet.slo_for("other").p99_ms == 123.0   # default applies
    # an idle tenant burns nothing
    assert fleet._tenant_health("ghost")["verdict"] == "ok"
    fleet.close()


# -- manifest ----------------------------------------------------------------


def test_manifest_is_the_tenant_directory(tmp_path):
    fleet, _ = _mk_fleet(tmp_path, tenants=("a", "b"))
    assert fleet.tenants() == ["a", "b"]
    fleet.close()
    # a new Fleet over the same root reads the durable manifest
    cfg = IVFConfig(dim=DIM, target_partition_size=50, kmeans_iters=4)
    f2 = Fleet(str(tmp_path / "fleet"), dim=DIM, budget_mb=0.5,
               config=cfg)
    assert f2.tenants() == ["a", "b"]
    # drop: one transaction + file removal; survives reopen
    f2.drop("a")
    assert f2.tenants() == ["b"]
    assert not os.path.exists(os.path.join(f2.root, "a.db"))
    f2.close()
    f3 = Fleet(str(tmp_path / "fleet"), dim=DIM, budget_mb=0.5,
               config=cfg)
    assert f3.tenants() == ["b"]
    f3.close()


def test_manifest_reconciles_orphans_and_missing(tmp_path):
    fleet, _ = _mk_fleet(tmp_path, tenants=("a", "b"))
    # orphan: a db file the manifest never registered (spill "a" first
    # so the copied main file is checkpointed + self-contained)
    fleet.close(name="a")
    shutil.copy(os.path.join(fleet.root, "a.db"),
                os.path.join(fleet.root, "stray.db"))
    # missing: registered tenant whose files vanished out-of-band
    fleet.close(name="b")
    for suffix in ("", "-wal", "-shm"):
        try:
            os.remove(os.path.join(fleet.root, "b.db" + suffix))
        except FileNotFoundError:
            pass
    drift = fleet.recover()
    assert drift == {"orphans": ["stray"], "missing": ["b"]}
    assert fleet.health()["manifest"] == drift
    assert "stray" not in fleet.tenants()       # manifest is authority
    # touching the orphan adopts it: registered + no longer drifting
    fleet.get("stray")
    assert "stray" in fleet.tenants()
    assert fleet.recover()["orphans"] == []
    fleet.close()


# -- exposition endpoint -----------------------------------------------------


def test_http_endpoints_engine(tmp_path):
    eng, X = _mk(tmp_path, "http", paged=True)
    eng.query(X[:2], Q.knn(k=3, n_probe=4), trace=True)
    srv = ExpositionServer.for_target(eng).start()
    try:
        code, ctype, body = _get(srv.url + "/metrics")
        assert code == 200 and ctype.startswith("text/plain")
        assert b"# TYPE " in body and b"# HELP " in body
        code, ctype, body = _get(srv.url + "/healthz")
        doc = json.loads(body)
        assert code == 200 and ctype.startswith("application/json")
        assert "hits" in doc and "misses" in doc     # MicroNN.stats()
        code, _, body = _get(srv.url + "/traces")
        traces = json.loads(body)
        assert code == 200 and len(traces) == 1 \
            and "spans" in traces[0]
        for path in ("/slow", "/events"):
            code, _, body = _get(srv.url + path)
            assert code == 200 and isinstance(json.loads(body), list)
        assert _get(srv.url + "/metrics")[2]         # repeat scrape ok
        try:
            _get(srv.url + "/nope")
            assert False, "404 expected"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        srv.stop()
        eng.store.close()


def test_http_serves_while_engine_mutex_held(tmp_path):
    """The endpoint must never need the engine write mutex: a scrape
    issued while a writer holds eng.lock still answers."""
    eng, X = _mk(tmp_path, "mutex")
    srv = ExpositionServer.for_target(eng).start()
    try:
        with eng.lock:
            assert _get(srv.url + "/metrics", timeout=10)[0] == 200
            assert _get(srv.url + "/healthz", timeout=10)[0] == 200
            assert _get(srv.url + "/traces", timeout=10)[0] == 200
    finally:
        srv.stop()
        eng.store.close()


def test_http_live_workload_unperturbed(tmp_path):
    """Concurrent scraping of every endpoint during a live fleet
    workload (daemon on) returns well-formed output and leaves query
    results bit-identical to the quiet run."""
    fleet, X = _mk_fleet(tmp_path, tenants=("a", "b"))
    spec = Q.knn(k=5, n_probe=4)
    quiet = [fleet.query("a", X[i:i + 2], spec).to_numpy()
             for i in range(6)]
    srv = ExpositionServer.for_target(fleet).start()
    fleet.start_maintenance()
    stop = threading.Event()
    errs = []

    def scrape():
        paths = ("/metrics", "/healthz", "/traces", "/events", "/slow")
        i = 0
        while not stop.is_set():
            try:
                code, _, body = _get(srv.url + paths[i % len(paths)])
                assert code == 200 and body
            except Exception as e:      # pragma: no cover
                errs.append(e)
                return
            i += 1

    threads = [threading.Thread(target=scrape) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        live = [fleet.query("a", X[i:i + 2], spec).to_numpy()
                for i in range(6)]
        for _ in range(4):
            fleet.query("b", X[:3], spec)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        fleet.stop_maintenance()
        srv.stop()
    assert not errs, errs
    for (qi, qs), (li, ls) in zip(quiet, live):
        np.testing.assert_array_equal(qi, li)
        np.testing.assert_array_equal(qs, ls)
    # the health doc stayed schema-valid mid-workload
    assert fleet.health()["schema"] == 1
    fleet.close()
