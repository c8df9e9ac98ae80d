"""Observability layer (PR 8): unified metrics registry, per-query trace
spans, the maintenance event log.

Pins the layer's three contracts:

  * **free when off** -- untraced queries allocate no new registry
    series, record nothing into the trace ring, and return
    `rs.trace is None`;
  * **exact when on** -- `explain()` returns a complete per-stage
    QueryTrace in all four engine modes (resident/paged x f32/int8, on
    both backends), whose pager-fault counters reconcile EXACTLY with
    the pager's registry counters across the traced call and whose scan
    `compiled` count reconciles with the executor's jit trace count;
  * **one source of truth** -- `MicroNN.stats()` / `FrontDoor.stats()`
    keys are derived views over the registry (scheduler telemetry,
    pager counters), and every series exports through snapshot() /
    to_prometheus().
"""
import re
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import executor
from repro.core.query import Q
from repro.core.types import IVFConfig
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serving import FrontDoor
from repro.storage import MicroNN
from tests.conftest import clustered_data

DIM = 16


def _mk(tmp_path, name, *, paged=False, quant=False, n=400, seed=0,
        **eng_kw):
    cfg = IVFConfig(dim=DIM, target_partition_size=50, kmeans_iters=8,
                    delta_capacity=64,
                    **({"quantize": "int8", "rerank_factor": 4}
                       if quant else {}))
    eng = MicroNN(dim=DIM, path=str(tmp_path / f"{name}.db"), config=cfg,
                  memory_budget_mb=0.05 if paged else None, **eng_kw)
    X = clustered_data(n=n, dim=DIM, seed=seed)
    eng.upsert(np.arange(n), X)
    eng.build()
    return eng, X


# -- metrics registry unit behaviour -----------------------------------------


def test_counter_gauge_get_or_create():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("reqs", comp="a")
    c.inc()
    c.inc(4)
    assert c.value == 5
    # same (name, labels) -> same object; different labels -> new series
    assert reg.counter("reqs", comp="a") is c
    assert reg.counter("reqs", comp="b") is not c
    g = reg.gauge("depth")
    g.set(3.5)
    assert g.value == 3.5
    reg.gauge("live", fn=lambda: 7)
    assert reg.gauge("live").value == 7
    # a name registered as one kind cannot be re-registered as another
    with pytest.raises(AssertionError):
        reg.histogram("reqs", comp="a")


def test_histogram_quantiles_and_merge():
    reg = obs_metrics.MetricsRegistry()
    h = reg.histogram("lat")
    assert h.quantile(0.5) == 0.0            # empty -> 0 (empty_stats)
    for v in (0.001, 0.002, 0.004, 0.008, 0.1):
        h.observe(v)
    assert h.count == 5 and h.sum == pytest.approx(0.115)
    p50 = h.quantile(0.50)
    assert 0.001 <= p50 <= 0.01
    assert h.quantile(1.0) == pytest.approx(0.1)
    # merge folds counts elementwise (same edges required)
    h2 = obs_metrics.Histogram("lat2")
    h2.observe(0.2)
    h.merge(h2)
    assert h.count == 6
    assert h.quantile(1.0) == pytest.approx(0.2)
    with pytest.raises(AssertionError):
        h.merge(obs_metrics.Histogram("odd", buckets=(1.0, 2.0)))


def test_scope_binds_and_nests_labels():
    reg = obs_metrics.MetricsRegistry()
    s = reg.scope(engine="0")
    c = s.counter("ops", component="pager")
    assert dict(c.labels) == {"engine": "0", "component": "pager"}
    # nested scopes merge, inner wins on conflict
    s2 = s.scope(component="exec").scope(component="exec2")
    assert dict(s2.counter("ops").labels) == {"engine": "0",
                                              "component": "exec2"}


def test_snapshot_and_prometheus_export():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("hits", component="pager").inc(3)
    reg.gauge("depth").set(2)
    reg.histogram("wait_s").observe(0.005)
    snap = reg.snapshot()
    assert snap["counters"]['hits{component="pager"}'] == 3
    assert snap["gauges"]["depth"] == 2
    hs = snap["histograms"]["wait_s"]
    assert hs["count"] == 1 and hs["p50"] > 0
    text = reg.to_prometheus()
    assert "# TYPE hits counter" in text
    assert 'hits{component="pager"} 3' in text
    assert "# TYPE wait_s histogram" in text
    assert 'le="+Inf"' in text and "wait_s_count 1" in text


# -- explain(): complete traces in every engine mode -------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("paged", [False, True], ids=["resident", "paged"])
def test_explain_complete_all_modes(tmp_path, paged, quant, backend):
    """Acceptance: explain() returns a per-stage QueryTrace in all four
    engine modes, on both backends, with the mode-appropriate spans and
    work counters."""
    eng, X = _mk(tmp_path, f"ex-{paged}-{quant}-{backend}",
                 paged=paged, quant=quant)
    spec = Q.knn(k=5, n_probe=4).backend(backend)
    tr = eng.explain(X[:2] + 0.01, spec)
    assert tr is not None and tr.mode == ("paged" if paged else "resident")
    assert tr.n_queries == 2 and tr.total_ms > 0 and tr.spec is not None
    for stage in ("plan", "probe", "scan", "merge"):
        assert stage in tr, (stage, tr.span_names)
    scan = tr.get("scan")
    assert scan.counters["partitions"] > 0
    assert scan.counters["rows"] > 0
    assert scan.counters["backend"] == backend
    assert scan.counters["quantized"] is quant
    assert tr.counter("probe", "partitions") > 0
    if paged:
        assert "pager_fault" in tr
    if quant:
        assert "rerank" in tr
    # the trace carries its ResultSet, and the ring kept it
    assert tr.result is not None and tr.result.trace is tr
    assert tr in eng.traces.traces()
    # format() renders every span (the quickstart prints this)
    txt = tr.format()
    assert "scan" in txt and "QueryTrace" in txt
    eng.store.close()


def test_trace_counters_reconcile_paged(tmp_path):
    """Acceptance: the fault span's hits/misses/bytes_read equal the
    pager's registry-counter deltas across the traced call, EXACTLY."""
    eng, X = _mk(tmp_path, "recon", paged=True, quant=True, n=600)
    spec = Q.knn(k=5, n_probe=4)
    eng.query(X[:2], spec)                      # warm compile path
    s0 = eng.stats()
    tr = eng.explain(X[300:302], spec)
    s1 = eng.stats()
    assert tr.counter("pager_fault", "hits") == s1["hits"] - s0["hits"]
    assert tr.counter("pager_fault", "misses") == \
        s1["misses"] - s0["misses"]
    assert tr.counter("pager_fault", "bytes_read") == \
        s1["bytes_read"] - s0["bytes_read"]
    # the traced call faulted SOMETHING (fresh probe set, cold frames)
    assert tr.counter("pager_fault", "hits") \
        + tr.counter("pager_fault", "misses") > 0
    eng.store.close()


def test_trace_compile_counter_reconciles_resident(tmp_path):
    """Acceptance: scan `compiled` == executor.trace_count() delta --
    cold Q-bucket compiles, warm bucket is a cache hit."""
    eng, X = _mk(tmp_path, "compiles")
    spec = Q.knn(k=7, n_probe=5)                # fresh spec: cold cache
    c0 = executor.trace_count()
    tr_cold = eng.explain(X[:1], spec)
    c1 = executor.trace_count()
    tr_warm = eng.explain(X[1:2], spec)
    c2 = executor.trace_count()
    assert tr_cold.counter("scan", "compiled") == c1 - c0 > 0
    assert tr_cold.counter("scan", "cache_hit") is False
    assert tr_warm.counter("scan", "compiled") == c2 - c1 == 0
    assert tr_warm.counter("scan", "cache_hit") is True
    eng.store.close()


# -- tracing-off hot path: zero cost, zero allocation ------------------------


def test_untraced_queries_allocate_nothing(tmp_path, monkeypatch):
    eng, X = _mk(tmp_path, "zero")
    paged, _ = _mk(tmp_path, "zero-paged", paged=True, quant=True)
    spec = Q.knn(k=5, n_probe=4)
    for e in (eng, paged):
        e.query(X[:1], spec).to_numpy()         # register + compile once
    reg = obs_metrics.default_registry()
    size0, ring0 = reg.size(), len(eng.traces)
    # with no trace and no profiler, every stage hook returns the shared
    # no-op stage: building a stage object here fails the test
    assert obs_trace.stage(obs_trace.STAGE_DISPATCH) is obs_trace.OFF

    def no_stage(*a, **kw):
        raise AssertionError("a stage was built with both sinks off")

    monkeypatch.setattr(obs_trace, "_Stage", no_stage)
    for i in range(5):
        for e in (eng, paged):
            rs = e.query(X[i:i + 1], spec)
            assert rs.trace is None
            rs.to_numpy()                       # device_wait + fetch hooks
    assert reg.size() == size0, "untraced query registered a new series"
    assert len(eng.traces) == ring0, "untraced query entered the ring"
    # global kill-switch: even trace=True records nothing
    obs_trace.set_enabled(False)
    try:
        rs = eng.query(X[:1], spec, trace=True)
        assert rs.trace is None and len(eng.traces) == ring0
        assert obs_trace.stage(obs_trace.STAGE_PLAN,
                               obs_trace.QueryTrace()) is obs_trace.OFF
    finally:
        obs_trace.set_enabled(True)
    eng.store.close()
    paged.store.close()


# -- host stages: both sinks, and no device work of their own ----------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_traced_resident_query_does_no_device_work_of_its_own(
        tmp_path, monkeypatch, backend):
    """A span-traced resident query records the host stages, runs no
    probe of its own once the jitted call is compiled, and answers as an
    untraced one does."""
    eng, X = _mk(tmp_path, f"stages-{backend}", quant=True)
    spec = Q.knn(k=5, n_probe=4).backend(backend)
    q = X[7:8] + 0.01
    eng.query(X[:1], spec).to_numpy()           # compile the one bucket

    def no_probe(*a, **kw):
        raise AssertionError("the probe ran outside the jitted call")

    monkeypatch.setattr(executor, "find_nearest_centroids", no_probe)
    monkeypatch.setattr(executor, "_probe_union", no_probe)
    c0 = executor.trace_count()
    traced = eng.query(q, spec, trace=True)
    plain = eng.query(q, spec)
    assert executor.trace_count() == c0
    tr = traced.trace
    for stage in ("plan", "stage_in", "dispatch", "device_wait", "fetch"):
        assert stage in tr, (stage, tr.span_names)
        assert tr.get(stage).dur_ms > 0, stage
    assert tr.get("stage_in").calls == 2        # engine part + executor part
    assert tr.counter("stage_in", "staged") == "host"
    # the fused stages are counter-only markers
    for stage in ("probe", "scan", "rerank", "merge"):
        assert tr.get(stage).dur_ms == 0.0 and tr.counter(stage, "fused") == 1
    assert tr.counter("probe", "partitions") == 4
    assert tr.counter("scan", "compiled") == 0
    assert tr.total_ms >= sum(tr.get(s).dur_ms for s in
                              ("plan", "stage_in", "dispatch",
                               "device_wait", "fetch"))
    ids_t, scores_t = traced.to_numpy()
    ids_p, scores_p = plain.to_numpy()
    np.testing.assert_array_equal(ids_t, ids_p)
    np.testing.assert_array_equal(scores_t, scores_p)
    # a caller's device array keeps the device staging, same answer
    on_dev = eng.query(jnp.asarray(q), spec, trace=True)
    assert on_dev.trace.get("stage_in").calls == 2
    assert on_dev.trace.counter("stage_in", "staged") == "device"
    ids_d, scores_d = on_dev.to_numpy()
    np.testing.assert_array_equal(ids_d, ids_p)
    np.testing.assert_array_equal(scores_d, scores_p)
    assert executor.trace_count() == c0
    eng.store.close()


def test_untraced_query_stages_reach_the_profiler(tmp_path):
    """Under a profiler session an untraced query still emits its host
    stages, as `micronn.*` events nested in the caller's annotation on
    the profiler's clock."""
    import jax

    from chipbench import trace_reduce
    eng, X = _mk(tmp_path, "profiled")
    spec = Q.knn(k=5, n_probe=4)
    eng.query(X[:1], spec).to_numpy()
    tdir = str(tmp_path / "trace")
    with jax.profiler.trace(tdir):
        with jax.profiler.TraceAnnotation("window"):
            for i in range(3):
                with jax.profiler.TraceAnnotation("query"):
                    rs = eng.query(X[i:i + 1], spec)
                    rs.to_numpy()
                assert rs.trace is None
    names = ("query", "micronn.stage_in", "micronn.plan", "micronn.dispatch",
             "micronn.device_wait", "micronn.fetch")
    ev = trace_reduce.load(tdir, names)
    queries = [e for e in ev if e.name == "query"]
    assert len(queries) == 3
    for name in names[1:]:
        got = [e for e in ev if e.name == name]
        assert len(got) == (6 if name == "micronn.stage_in" else 3), name
        for e in got:
            assert any(q.plane == e.plane and q.start_ns <= e.start_ns
                       and e.end_ns <= q.end_ns for q in queries), name
    eng.store.close()


# -- front door: per-caller traces under concurrent load ---------------------


def test_frontdoor_traced_submits_under_threads(tmp_path):
    """Traced and untraced callers interleave from many threads: every
    traced caller gets its own queue_wait + the shared fused spans;
    untraced callers get rs.trace None; results match solo query()."""
    eng, X = _mk(tmp_path, "fdtrace")
    spec = Q.knn(k=5, n_probe=4)
    n_req = 8
    solo = [eng.query(X[i] + 0.01, spec) for i in range(n_req)]
    results = [None] * n_req
    with FrontDoor(eng, window_s=0.2, max_batch_rows=64) as fd:
        def worker(i):
            results[i] = fd.query(X[i] + 0.01, spec,
                                  trace=(i % 2 == 0), timeout=30)
        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(n_req)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        st = fd.stats()
    assert st["completed"] == n_req and st["failed"] == 0
    for i, rs in enumerate(results):
        np.testing.assert_array_equal(np.asarray(rs.ids),
                                      np.asarray(solo[i].ids))
        if i % 2 == 0:
            tr = rs.trace
            assert tr is not None and "queue_wait" in tr
            for stage in ("plan", "probe", "scan"):
                assert stage in tr, (stage, tr.span_names)
            assert tr.shared is not None        # adopted the fused call
            assert tr in eng.traces.traces()
        else:
            assert rs.trace is None
    # coalesced traced callers reference the SAME fused-scan Span and
    # record their share of the batch in the split sub-span
    traced = [r.trace for i, r in enumerate(results) if i % 2 == 0]
    by_shared = {}
    for tr in traced:
        by_shared.setdefault(id(tr.shared), []).append(tr)
    for group in by_shared.values():
        if len(group) > 1:
            assert len({id(t.get("scan")) for t in group}) == 1
            for t in group:
                assert t.counter("split", "callers") >= len(group)
    eng.store.close()


def test_frontdoor_stats_derive_from_histograms(tmp_path):
    """The reservoir replacement: percentile keys are now derived from
    registry histograms and stay non-zero after traffic (the shape pin
    lives in test_serving's uniform-stats test)."""
    eng, X = _mk(tmp_path, "fdh")
    with FrontDoor(eng, window_s=0.0) as fd:
        for i in range(4):
            fd.query(X[i], Q.knn(k=5, n_probe=4), timeout=30)
        st = fd.stats()
        assert st["total_p50_ms"] > 0 and st["execute_p99_ms"] > 0
        # the series live in the process registry under this scope
        assert fd.metrics.histogram("total_s").count == 4
    eng.store.close()


# -- scheduler telemetry + maintenance event log -----------------------------


def test_scheduler_telemetry_and_event_log(tmp_path):
    eng, X = _mk(tmp_path, "sched", n=400)
    eng.upsert(np.arange(400, 480),
               clustered_data(n=80, dim=DIM, seed=9))
    reports = eng.maintain(until_idle=True)
    assert reports, "expected at least one maintenance step"
    st = eng.scheduler.stats()
    assert st["steps"] == len(reports)
    assert st["rows_moved"] == sum(r.rows for r in reports)
    assert st["bytes_written"] == sum(r.bytes_written for r in reports)
    assert sum(st["actions"].values()) == st["steps"]
    assert st["actions"]["flush"] >= 1
    # surfaced through the engine's uniform stats dict
    assert eng.stats()["scheduler"]["steps"] == st["steps"]
    # the event log saw every step: planned -> step pairs, in order
    events = eng.traces.events()
    kinds = [e.kind for e in events]
    assert kinds.count("step") == len(reports)
    assert kinds.index("planned") < kinds.index("step")
    steps = [e for e in events if e.kind == "step"]
    assert sum(e.rows for e in steps) == st["rows_moved"]
    assert all(e.dur_ms >= 0 and e.action for e in steps)
    assert all(e.to_dict()["kind"] == e.kind for e in events)
    eng.store.close()


# -- trace ring + slow-query log ---------------------------------------------


def test_trace_ring_bounded_and_slow_log(tmp_path):
    eng, X = _mk(tmp_path, "ring", trace_ring_capacity=4,
                 slow_query_ms=0.0)           # every trace is "slow"
    spec = Q.knn(k=5, n_probe=4)
    for i in range(6):
        eng.explain(X[i:i + 1], spec)
    assert len(eng.traces) == 4               # ring rotated
    assert len(eng.traces.traces()) == 4
    slow = eng.traces.slow()
    assert len(slow) == 6                     # slow log kept them all
    assert all(t.total_ms >= 0.0 for t in slow)
    eng.traces.clear()
    assert len(eng.traces) == 0 and not eng.traces.slow()
    eng.store.close()


def test_slow_log_threshold_filters(tmp_path):
    eng, X = _mk(tmp_path, "slowhi", slow_query_ms=1e9)
    eng.explain(X[:1], Q.knn(k=5, n_probe=4))
    assert len(eng.traces.traces()) == 1
    assert eng.traces.slow() == []            # under the threshold
    eng.store.close()


# -- registry cardinality guard (PR 9 satellite) -----------------------------


def test_registry_cardinality_guard_caps_per_name_series():
    """A runaway label set (one series per request id, say) is bounded:
    per-name LRU keeps the cap hottest series, evictions are counted in
    obs_series_evicted, and other names are untouched."""
    reg = obs_metrics.MetricsRegistry(max_series_per_name=4)
    for i in range(10):
        reg.counter("chatty", rid=str(i)).inc()
    snap = reg.snapshot()
    chatty = [k for k in snap["counters"] if k.startswith("chatty")]
    assert len(chatty) == 4
    kept = {k.split('rid="')[1].rstrip('"}') for k in chatty}
    assert kept == {"6", "7", "8", "9"}     # LRU: most recent survive
    ev = reg.counter("obs_series_evicted")
    assert ev.value == 6
    # an evicted series re-registers fresh (counts reset -- the guard
    # trades unbounded memory for that)
    c0 = reg.counter("chatty", rid="0")
    assert c0.value == 0


def test_registry_cardinality_guard_lru_touch_on_reuse():
    """Re-fetching a series refreshes its LRU slot, so steady-state
    series survive churn from one-shot labels."""
    reg = obs_metrics.MetricsRegistry(max_series_per_name=3)
    hot = reg.counter("m", k="hot")
    hot.inc(5)
    for i in range(8):
        reg.counter("m", k=f"cold{i}")
        assert reg.counter("m", k="hot") is hot     # touch keeps it live
    assert hot.value == 5
    assert reg.counter("obs_series_evicted").value == 6
    # distinct names each get their own budget; single-series names are
    # never at risk (the guard key is (name) -> labels LRU)
    for i in range(10):
        reg.gauge("g_other", i=str(i)).set(i)
    assert reg.counter("m", k="hot") is hot


# -- Prometheus exposition hardening (PR 10) ---------------------------------


_PROM_SAMPLE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (\S+)$')


def _parse_prom_labels(s):
    """Strict text-format label parser: `k="v",...` where v uses the
    \\\\ , \\" and \\n escapes. Raises on anything malformed -- the
    test's point is that a strict scraper accepts the page."""
    out = {}
    i = 0
    while i < len(s):
        eq = s.index("=", i)
        key = s[i:eq]
        assert s[eq + 1] == '"', s
        i, val = eq + 2, []
        while s[i] != '"':
            if s[i] == "\\":
                esc = s[i + 1]
                assert esc in ('\\', '"', 'n'), f"bad escape \\{esc}"
                val.append({"\\": "\\", '"': '"', "n": "\n"}[esc])
                i += 2
            else:
                val.append(s[i])
                i += 1
        out[key] = "".join(val)
        i += 1                                  # closing quote
        if i < len(s):
            assert s[i] == ",", s
            i += 1
    return out


def test_prometheus_roundtrip_nasty_labels():
    """Acceptance (PR 10): label values containing backslash, quote and
    newline survive export -> strict parse -> exact round-trip, and
    every metric family carries exactly one # HELP + # TYPE header."""
    reg = obs_metrics.MetricsRegistry()
    nasty = {"path": 'C:\\tmp\\"x"', "note": 'line1\nline2',
             "plain": "ok"}
    reg.counter("pager.hits", **nasty).inc(3)
    reg.counter("pager.hits", plain="other").inc(1)
    reg.gauge("depth", q='say "when"').set(2.5)
    reg.histogram("wait.s", tenant="a\\b").observe(0.004)
    text = reg.to_prometheus()

    helps, types, samples = {}, {}, []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP "):
            fam = line.split(" ", 3)[2]
            helps[fam] = helps.get(fam, 0) + 1
        elif line.startswith("# TYPE "):
            fam = line.split(" ", 3)[2]
            types[fam] = types.get(fam, 0) + 1
            assert fam in helps, f"# TYPE {fam} before its # HELP"
        else:
            m = _PROM_SAMPLE.match(line)
            assert m, f"unparseable sample line: {line!r}"
            name, raw, value = m.groups()
            labels = _parse_prom_labels(raw) if raw else {}
            samples.append((name, labels, float(value)))
            fam = re.sub(r"_(bucket|sum|count)$", "", name)
            assert fam in types or name in types, \
                f"sample {name} precedes its # TYPE"
    # exactly one header pair per family, names sanitized (dots -> _)
    assert helps == {"pager_hits": 1, "depth": 1, "wait_s": 1}
    assert types == helps
    assert types and all(n == 1 for n in types.values())
    # bit-exact label round-trip through the escapes
    got = [ls for n, ls, v in samples
           if n == "pager_hits" and v == 3.0]
    assert got == [nasty]
    assert any(n == "depth" and ls == {"q": 'say "when"'} and v == 2.5
               for n, ls, v in samples)
    assert any(n == "wait_s_count" and ls == {"tenant": "a\\b"}
               for n, ls, _ in samples)
    # cumulative le series end at +Inf with the family labels intact
    infs = [ls for n, ls, _ in samples
            if n == "wait_s_bucket" and ls.get("le") == "+Inf"]
    assert infs == [{"tenant": "a\\b", "le": "+Inf"}]


# -- interleave stress: recorder + traces under concurrency (PR 10) ----------


def test_interleave_recorder_traces_pinned_vs_twin(tmp_path):
    """Flight recorder + TraceRing + live maintenance daemon under
    multi-threaded FrontDoor.submit(trace=True): every answer is
    bit-identical to a single-threaded twin engine, the concurrent
    capture replays cleanly on that twin, traced callers all reach the
    ring, and the daemon survives the churn."""
    import repro.obs.recorder as obs_recorder

    eng, X = _mk(tmp_path, "il-mt", seed=5)
    twin, _ = _mk(tmp_path, "il-st", seed=5)    # same build, no threads
    spec = Q.knn(k=5, n_probe=4)
    n_threads, per = 4, 6
    probes = [[X[(t * per + j) % len(X)] + 0.01 for j in range(per)]
              for t in range(n_threads)]
    results = [[None] * per for _ in range(n_threads)]
    errors = []
    cap = str(tmp_path / "cap.db")

    with obs_recorder.recording(cap) as rec:
        with FrontDoor(eng, window_s=0.002, maintenance=True) as fd:
            def caller(t):
                try:
                    for j in range(per):
                        results[t][j] = fd.query(
                            probes[t][j], spec,
                            trace=(t % 2 == 0), timeout=60)
                except BaseException as e:  # noqa: BLE001
                    errors.append(e)

            threads = [threading.Thread(target=caller, args=(t,))
                       for t in range(n_threads)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(120)
            assert not errors, errors
            assert eng.scheduler.daemon_alive
            # maintenance events interleave with the capture stream
            eng.upsert(np.arange(400, 440),
                       clustered_data(n=40, dim=DIM, seed=6))
            eng.maintain(until_idle=True)
            assert any(e.kind == "step" for e in eng.traces.events())
        assert rec.recorded == n_threads * per

    # single-threaded twin: identical probes, identical bits
    for t in range(n_threads):
        for j in range(per):
            solo = twin.query(probes[t][j], spec)
            np.testing.assert_array_equal(
                np.asarray(results[t][j].ids), np.asarray(solo.ids))
            np.testing.assert_array_equal(
                np.asarray(results[t][j].scores),
                np.asarray(solo.scores))
    # traced callers reached the ring; untraced stayed out of it
    for t in range(n_threads):
        for rs in results[t]:
            if t % 2 == 0:
                assert rs.trace is not None \
                    and rs.trace in eng.traces.traces()
            else:
                assert rs.trace is None
    # the concurrent capture replays deterministically on the twin
    # (front-door records are digestless: double-run self-check)
    rep = obs_recorder.replay(cap, engine=twin, strict=True)
    assert rep.ok and rep.self_checked == n_threads * per
    eng.store.close()
    twin.store.close()
