"""Partition pager: buffer-pool semantics (clock eviction, pin-on-scan,
byte budget), batched store reads, cache invalidation through the paged
engine's write path, and paged-vs-resident search parity.

Parity contract (the PR's acceptance pin): with any memory budget, a
paged engine recovered from the same durable state as a resident engine
returns BIT-IDENTICAL SearchResults (ids and scores) on both backends --
the frame pool + disk-gather rerank only changes where bytes live, never
what the search computes.
"""
import dataclasses
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.types import IVFConfig, effective_pad_to
from repro.storage import MicroNN, VectorStore
from repro.storage.pager import PartitionCache
from tests.conftest import clustered_data


def _mk_store(tmp_path, name="p.db", n=200, d=8, k=10, n_attr=0, seed=0):
    """A store with a hand-made clustering: n rows over k partitions."""
    rng = np.random.default_rng(seed)
    st = VectorStore(str(tmp_path / name), dim=d, n_attr=n_attr)
    X = rng.normal(size=(n, d)).astype(np.float32)
    attrs = rng.integers(0, 4, (n, n_attr)).astype(np.float32) \
        if n_attr else None
    st.upsert(list(range(n)), X, attrs)
    assign = rng.integers(0, k, n)
    st.set_partitions(np.arange(n), assign,
                      rng.normal(size=(k, d)).astype(np.float32),
                      np.zeros(k))
    return st, X, assign


# -- batched store reads (satellite: no per-row round-trips) -----------------


def test_scan_partitions_matches_per_pid_scan(tmp_path):
    st, X, assign = _mk_store(tmp_path, n_attr=2)
    p_max = int(np.bincount(assign).max())
    pids = [3, 0, 7]
    blocks = st.scan_partitions(pids, p_max, with_attrs=True)
    for j, pid in enumerate(pids):
        ids, vecs = st.scan_partition(pid)
        m = len(ids)
        assert blocks.valid[j].sum() == m
        np.testing.assert_array_equal(blocks.ids[j, :m], ids)
        np.testing.assert_array_equal(blocks.vecs[j, :m], vecs)
        assert (blocks.ids[j, m:] == -1).all()
        np.testing.assert_array_equal(blocks.attrs[j, :m],
                                      st.attributes_for(ids))


def test_scan_partitions_codes_ride_along(tmp_path):
    st, X, assign = _mk_store(tmp_path)
    codes = np.clip(X * 10, -128, 127).astype(np.int8)
    # leave one asset without a durable code
    st.set_code_tier(np.arange(1, len(X)), codes[1:],
                     np.zeros(8, np.float32), np.ones(8, np.float32))
    p_max = int(np.bincount(assign).max())
    blocks = st.scan_partitions([int(assign[0])], p_max, with_codes=True)
    row = np.nonzero(blocks.ids[0] == 0)[0][0]
    assert not blocks.code_ok[0, row]           # missing code flagged
    other = np.nonzero(blocks.valid[0] & blocks.code_ok[0])[0]
    for r in other:
        np.testing.assert_array_equal(blocks.codes[0, r],
                                      codes[blocks.ids[0, r]])
    with pytest.raises(AssertionError):
        st.scan_partitions([1, 1], p_max)       # duplicate pids rejected


def test_attributes_for_batched_with_duplicates(tmp_path):
    st, _, _ = _mk_store(tmp_path, n_attr=2)
    want = np.array([5, 3, 5, 9999])            # dup + missing id
    got = st.attributes_for(want)
    np.testing.assert_array_equal(got[0], got[2])
    np.testing.assert_array_equal(got[3], np.zeros(2))
    single = np.concatenate([st.attributes_for(np.array([int(a)]))
                             for a in want[:3]])
    np.testing.assert_array_equal(got[:3], single.reshape(3, 2))


def test_vectors_for_batched_gather(tmp_path):
    st, X, _ = _mk_store(tmp_path)
    want = [7, 3, 12345, 7]
    out, found = st.vectors_for(want)
    np.testing.assert_array_equal(found, [True, True, False, True])
    np.testing.assert_array_equal(out[0], X[7])
    np.testing.assert_array_equal(out[1], X[3])
    np.testing.assert_array_equal(out[3], X[7])


# -- buffer pool: budget, clock eviction, pins -------------------------------


def _mk_cache(st, assign, n_frames, **kw):
    p_max = int(np.bincount(assign).max())
    fb = PartitionCache.compute_frame_bytes(p_max, st.dim)
    return PartitionCache(st, p_max=p_max, budget_bytes=n_frames * fb, **kw)


def test_budget_too_small_for_one_frame_raises(tmp_path):
    st, _, assign = _mk_store(tmp_path)
    with pytest.raises(ValueError):
        PartitionCache(st, p_max=int(np.bincount(assign).max()),
                       budget_bytes=8)


def test_hit_miss_counters_and_frame_content(tmp_path):
    st, X, assign = _mk_store(tmp_path)
    cache = _mk_cache(st, assign, 4)
    f = cache.fault([2, 5])
    cache.unpin(f)
    assert (cache.hits, cache.misses) == (0, 2)
    f2 = cache.fault([5, 2, 6])
    cache.unpin(f2)
    assert (cache.hits, cache.misses) == (2, 3)
    # frame content matches a direct partition scan
    ids, vecs = st.scan_partition(5)
    j = int(f2[0])
    m = len(ids)
    np.testing.assert_array_equal(np.asarray(cache.ids_pool)[j, :m], ids)
    np.testing.assert_array_equal(np.asarray(cache.payload_pool)[j, :m], vecs)
    assert not np.asarray(cache.valid_pool)[j, m:].any()


def test_clock_eviction_order_second_chance(tmp_path):
    st, _, assign = _mk_store(tmp_path)
    cache = _mk_cache(st, assign, 3)
    cache.unpin(cache.fault([0, 1, 2]))     # fill: frames 0,1,2 all ref'd
    # cold fault: the sweep clears every ref bit, wraps, and reclaims the
    # first frame past the hand -- pid 0 (FIFO when everything is warm)
    cache.unpin(cache.fault([3]))
    assert cache.evictions == 1
    assert set(cache._pid_frame) == {1, 2, 3}
    cache.unpin(cache.fault([1]))           # re-reference pid 1 ...
    cache.unpin(cache.fault([4]))
    resident = set(cache._pid_frame)
    # ... so its ref bit buys it a second chance: the cold pid 2 goes
    assert 1 in resident and 4 in resident and 2 not in resident
    assert cache.evictions == 2


def test_pin_semantics_block_eviction(tmp_path):
    st, _, assign = _mk_store(tmp_path)
    cache = _mk_cache(st, assign, 2)
    pinned = cache.fault([3, 4])                # both frames pinned
    with pytest.raises(RuntimeError):
        cache.fault([5])                        # no victim available
    with pytest.raises(ValueError):
        cache.fault([1, 2, 3])                  # probe set > pool
    cache.unpin(pinned[:1])
    f = cache.fault([5])                        # now a victim exists
    cache.unpin(f)
    assert 5 in cache._pid_frame
    cache.unpin(pinned[1:])


def test_budget_never_exceeded_randomized_workload(tmp_path):
    st, _, assign = _mk_store(tmp_path, n=400, k=20, seed=3)
    cache = _mk_cache(st, assign, 3)
    budget = cache.budget_bytes
    rng = np.random.default_rng(0)
    for _ in range(50):
        pids = rng.choice(20, size=rng.integers(1, 4), replace=False)
        f = cache.fault(list(pids))
        assert cache.resident_bytes <= budget
        cache.unpin(f)
    assert cache.resident_bytes <= budget
    assert cache.evictions > 0 and cache.hits > 0
    s = cache.stats()
    assert s["resident_bytes"] == cache.resident_bytes
    assert s["capacity_frames"] == 3


def test_fault_failure_rolls_back_registrations(tmp_path, monkeypatch):
    """A failed fetch (e.g. a transient 'database is locked') must leave
    no pinned frames and no pid -> frame mappings for data that never
    arrived -- otherwise the next fault counts zero-filled frames as
    hits and pins starve the pool."""
    st, _, assign = _mk_store(tmp_path)
    cache = _mk_cache(st, assign, 4)
    cache.unpin(cache.fault([0]))

    def boom(*a, **k):
        raise RuntimeError("database is locked")
    monkeypatch.setattr(st, "scan_partitions", boom)
    with pytest.raises(RuntimeError):
        cache.fault([0, 1])         # hit(0) + miss(1): the fetch fails
    assert (cache._pins == 0).all()             # no leaked pins
    assert 1 not in cache._pid_frame            # no phantom mapping
    assert 0 in cache._pid_frame                # the real frame survives
    monkeypatch.undo()
    f = cache.fault([0, 1])                     # pool fully usable again
    ids, _ = st.scan_partition(1)
    assert np.asarray(cache.valid_pool)[f[1]].sum() == len(ids)
    cache.unpin(f)


def test_resize_failure_keeps_old_geometry(tmp_path):
    st, _, assign = _mk_store(tmp_path)
    cache = _mk_cache(st, assign, 2)
    p_max, fb, cap = cache.p_max, cache.frame_bytes, cache.capacity
    with pytest.raises(ValueError):
        cache.resize(p_max * 1000)              # budget cannot seat it
    # validation happens before mutation: old geometry fully intact
    assert (cache.p_max, cache.frame_bytes, cache.capacity) == \
        (p_max, fb, cap)
    cache.unpin(cache.fault([0]))               # still serviceable


def test_invalidate_forces_refetch(tmp_path):
    st, X, assign = _mk_store(tmp_path)
    cache = _mk_cache(st, assign, 4)
    cache.unpin(cache.fault([1]))
    # overwrite a row durably, then invalidate: next fault sees new bytes
    victim = int(np.nonzero(assign == 1)[0][0])
    newv = np.full((1, 8), 42.0, np.float32)
    st.upsert([victim], newv, partition_id=1)
    cache.invalidate([1])
    f = cache.fault([1])
    j = int(f[0])
    ids = np.asarray(cache.ids_pool)[j]
    row = np.nonzero(ids == victim)[0][0]
    np.testing.assert_array_equal(np.asarray(cache.payload_pool)[j, row],
                                  newv[0])
    cache.unpin(f)
    assert cache.misses == 2                    # invalidation -> refetch


# -- paged engine: parity + invalidation through the write path --------------


@pytest.fixture(scope="module", params=[
    (metric, quant) for metric in ("l2", "ip", "cosine")
    for quant in ("none", "int8")], ids="-".join)
def paged_pair(request, tmp_path_factory):
    """(resident, paged) engines recovered from the same durable state."""
    metric, quant = request.param
    X = clustered_data(n=1500, dim=16, seed=8)
    path = str(tmp_path_factory.mktemp("pager") / f"{metric}-{quant}.db")
    cfg = IVFConfig(dim=16, target_partition_size=50, kmeans_iters=15,
                    delta_capacity=64, quantize=quant, rerank_factor=4,
                    metric=metric)
    eng = MicroNN(dim=16, n_attr=1, path=path, config=cfg)
    eng.upsert(np.arange(len(X)), X, np.ones((len(X), 1), np.float32))
    eng.build()
    eng.store.db.commit()
    res = MicroNN(dim=16, n_attr=1, path=path, config=cfg)
    res.recover()
    pag = MicroNN(dim=16, n_attr=1, path=path, config=cfg,
                  memory_budget_mb=0.05)
    pag.recover()
    return res, pag, X


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_paged_matches_resident_bitwise(paged_pair, backend):
    res, pag, X = paged_pair
    # the budget forces paging: the pool holds only a fraction of the tier
    assert pag.index.cache.capacity < pag.index.k
    q = X[:16]
    r1 = res.search(q, k=10, n_probe=8, backend=backend)
    r2 = pag.search(q, k=10, n_probe=8, backend=backend)
    np.testing.assert_array_equal(np.asarray(r1.ids), np.asarray(r2.ids))
    np.testing.assert_array_equal(np.asarray(r1.scores),
                                  np.asarray(r2.scores))


def test_paged_exact_streams_whole_collection(paged_pair):
    res, pag, X = paged_pair
    q = X[:4]
    r2 = pag.search(q, k=10, exact=True)
    if pag.index.quantized:
        # int8 pool: full-probe SQ scan + rerank is a near-oracle
        r1 = res.search(q, k=10, exact=True)
        hits = sum(len(set(a) & set(b)) for a, b in
                   zip(np.asarray(r1.ids), np.asarray(r2.ids)))
        assert hits / r2.ids.size >= 0.95
    else:
        r1 = res.search(q, k=10, exact=True)
        np.testing.assert_array_equal(np.asarray(r1.ids), np.asarray(r2.ids))
        np.testing.assert_array_equal(np.asarray(r1.scores),
                                      np.asarray(r2.scores))


def test_paged_budget_held_and_stats_surface(paged_pair):
    _, pag, X = paged_pair
    budget = int(0.05 * 2 ** 20)
    for i in range(6):
        pag.search(X[i * 8:(i + 1) * 8], k=10, n_probe=8)
        assert pag.index.cache.resident_bytes <= budget
    s = pag.stats()
    assert s["paged"] and s["misses"] > 0 and s["evictions"] > 0
    assert s["resident_bytes"] <= budget == s["budget_bytes"]


def test_paged_flush_invalidates_and_stays_consistent(tmp_path):
    X = clustered_data(n=800, dim=16, seed=9)
    cfg = IVFConfig(dim=16, target_partition_size=40, kmeans_iters=10,
                    delta_capacity=32, quantize="int8")
    eng = MicroNN(dim=16, path=str(tmp_path / "f.db"), config=cfg,
                  memory_budget_mb=0.05)
    eng.upsert(np.arange(800), X)
    eng.build()
    nv = np.random.default_rng(3).normal(size=(8, 16)).astype(np.float32)
    eng.upsert(np.arange(9000, 9008), nv)
    eng.search(nv, k=1)                     # warm the touched partitions
    misses0 = eng.index.cache.misses
    assert eng.maintain(force="flush") == "flush"
    assert int(eng.index.delta.valid.sum()) == 0
    r = eng.search(nv[:4], k=1)             # now served from main frames
    assert list(np.asarray(r.ids)[:, 0]) == [9000, 9001, 9002, 9003]
    assert eng.index.cache.misses > misses0     # frames were invalidated
    # durable move happened: rows left the delta partition
    pids, _ = eng.store.scan_partition(-1)
    assert len(pids) == 0


def test_paged_rebuild_invalidates_everything(tmp_path):
    X = clustered_data(n=600, dim=16, seed=10)
    cfg = IVFConfig(dim=16, target_partition_size=40, kmeans_iters=10,
                    quantize="int8")
    eng = MicroNN(dim=16, path=str(tmp_path / "r.db"), config=cfg,
                  memory_budget_mb=0.05)
    eng.upsert(np.arange(600), X)
    eng.build()
    eng.search(X[:8], k=5)
    counters = (eng.index.cache.hits, eng.index.cache.misses)
    assert eng.maintain(force="rebuild") == "rebuild"
    assert len(eng.index.cache._pid_frame) == 0     # cold pool
    # counters are cumulative across the rebuild
    assert (eng.index.cache.hits, eng.index.cache.misses) == counters
    r = eng.search(X[:4], k=1)
    assert list(np.asarray(r.ids)[:, 0]) == [0, 1, 2, 3]


def test_paged_upsert_delete_invalidate_old_partitions(tmp_path):
    X = clustered_data(n=500, dim=16, seed=12)
    cfg = IVFConfig(dim=16, target_partition_size=40, kmeans_iters=10)
    eng = MicroNN(dim=16, path=str(tmp_path / "u.db"), config=cfg,
                  memory_budget_mb=0.1)
    eng.upsert(np.arange(500), X)
    eng.build()
    counts0 = int(eng.index.counts.sum())
    r = eng.search(X[:1], k=1)
    assert int(np.asarray(r.ids)[0, 0]) == 0
    # move row 0 far away: the old main-tier copy must stop matching
    eng.upsert(np.asarray([0]), np.full((1, 16), 50.0, np.float32))
    r = eng.search(X[:1], k=1)
    assert int(np.asarray(r.ids)[0, 0]) != 0
    assert int(eng.index.counts.sum()) == counts0 - 1
    r = eng.search(np.full((1, 16), 50.0, np.float32), k=1)
    assert int(np.asarray(r.ids)[0, 0]) == 0        # delta copy wins
    eng.delete(np.asarray([1]))
    r = eng.search(X[1:2], k=5)
    assert 1 not in np.asarray(r.ids)[0]
    assert int(eng.index.counts.sum()) == counts0 - 2


def test_paged_predicate_on_cold_cache(tmp_path):
    """Regression: the frame pools are rebound by fault()'s functional
    scatter, so the scan must read them AFTER faulting -- a pre-fault
    reference scans stale (zero/evicted) attr frames and silently
    mis-filters. A predicate query against a completely cold cache is the
    sharpest probe: every frame is faulted inside the search itself."""
    from repro.core.hybrid import Pred
    rng = np.random.default_rng(4)
    n, d = 2000, 16
    X = (rng.normal(size=(n, d)) * 3).astype(np.float32)
    attrs = rng.integers(0, 4, (n, 1)).astype(np.float32)
    cfg = IVFConfig(dim=16, target_partition_size=50, kmeans_iters=10,
                    quantize="int8")
    eng = MicroNN(dim=16, n_attr=1, path=str(tmp_path / "pred.db"),
                  config=cfg, memory_budget_mb=0.05)
    eng.upsert(np.arange(n), X, attrs)
    eng.build()
    r = eng.search(X[:8], k=10, predicate=Pred(0, "eq", 3.0))
    ids = np.asarray(r.ids)
    real = ids[(ids >= 0) & (ids < n)]
    assert len(real) > 0, "cold-cache predicate search returned nothing"
    assert (attrs[real, 0] == 3.0).all()
    # and on a warm cache with churn (frames replaced mid-search)
    r2 = eng.search(X[8:16], k=10, predicate=Pred(0, "eq", 1.0))
    ids2 = np.asarray(r2.ids)
    real2 = ids2[(ids2 >= 0) & (ids2 < n)]
    assert len(real2) > 0 and (attrs[real2, 0] == 1.0).all()


# -- admission policy: scan-resistant faults (satellite) ---------------------


def test_scan_resistant_fault_preserves_hot_set(tmp_path):
    """A full-collection stream faulted with admit=False must cycle
    through the scan ring and leave every admitted (hot) frame resident."""
    st, _, assign = _mk_store(tmp_path)
    cache = _mk_cache(st, assign, 8)
    assert cache.scan_frames == 2
    hot = [0, 1, 2, 3, 4, 5]
    cache.unpin(cache.fault(hot))               # admitted working set
    for s in range(0, 10, cache.scan_frames):   # one-off full scan
        pids = list(range(s, min(s + cache.scan_frames, 10)))
        cache.unpin(cache.fault(pids, admit=False))
    h0, m0 = cache.hits, cache.misses
    cache.unpin(cache.fault(hot))               # hot set still resident
    assert (cache.hits, cache.misses) == (h0 + len(hot), m0)
    # the stream dirtied at most the ring, never the admitted frames
    assert cache._transient.sum() <= cache.scan_frames


def test_scan_ring_promotion_on_admitted_hit(tmp_path):
    st, _, assign = _mk_store(tmp_path)
    cache = _mk_cache(st, assign, 6)
    f = cache.fault([7], admit=False)           # lands in the scan ring
    cache.unpin(f)
    fr = int(f[0])
    assert cache._transient[fr] and fr in cache._ring
    f2 = cache.fault([7])                       # admitted hit -> promote
    cache.unpin(f2)
    assert int(f2[0]) == fr
    assert not cache._transient[fr] and fr not in cache._ring


def test_admitted_fault_reclaims_ring_first(tmp_path):
    st, _, assign = _mk_store(tmp_path)
    cache = _mk_cache(st, assign, 8)
    cache.unpin(cache.fault([0, 1, 2, 3]))      # hot admitted frames
    ring = cache.fault([8], admit=False)        # one transient frame
    cache.unpin(ring)
    f_new = cache.fault([9])                    # admitted miss
    cache.unpin(f_new)
    # the transient frame is the preferred victim -- hot frames intact
    assert int(f_new[0]) == int(ring[0])
    for p in (0, 1, 2, 3):
        assert p in cache._pid_frame


def test_paged_exact_stream_keeps_hot_frames(tmp_path):
    """Engine-level: a one-off exact scan through a paged engine must not
    evict the ANN working set (ROADMAP open item)."""
    X = clustered_data(n=1500, dim=16, seed=15)
    cfg = IVFConfig(dim=16, target_partition_size=50, kmeans_iters=10)
    eng = MicroNN(dim=16, path=str(tmp_path / "adm.db"), config=cfg,
                  memory_budget_mb=0.08)
    eng.upsert(np.arange(len(X)), X)
    eng.build()
    cache = eng.index.cache
    assert cache.capacity < eng.index.k     # pool can't seat everything
    for i in range(4):                      # warm an ANN working set
        eng.search(X[i * 8:(i + 1) * 8], k=10, n_probe=4)
    hot = {p for p, f in cache._pid_frame.items() if not cache._transient[f]}
    assert hot
    r_exact = eng.search(X[:4], k=10, exact=True)   # one-off full stream
    # the stream may displace at most the scan ring's worth of frames
    # (ring bootstrap when the pool is fully hot), never the whole pool
    survivors = hot & set(cache._pid_frame)
    evicted = len(hot) - len(survivors)
    assert evicted <= cache.scan_frames, \
        f"exact scan evicted {evicted} hot frames " \
        f"(> scan ring {cache.scan_frames})"
    # and the stream still computed the true oracle
    res = MicroNN(dim=16, path=str(tmp_path / "adm.db"), config=cfg)
    res.recover()
    r_res = res.search(X[:4], k=10, exact=True)
    np.testing.assert_array_equal(np.asarray(r_exact.ids),
                                  np.asarray(r_res.ids))


# -- thread safety + deferred invalidation (PR 5 satellites) -----------------


def test_invalidate_pinned_frame_defers_release(tmp_path):
    """Invalidating a partition whose frame a scan still pins must not
    blow up (the scheduler and queries may interleave): the mapping drops
    immediately, the frame is freed at the last unpin."""
    st, _, assign = _mk_store(tmp_path)
    cache = _mk_cache(st, assign, 4)
    f = cache.fault([2])                  # pinned by an in-flight scan
    cache.invalidate([2])                 # scheduler moves partition 2
    assert 2 not in cache._pid_frame      # next fault refetches
    assert cache._stale[int(f[0])]
    f2 = cache.fault([2])                 # concurrent refetch: new frame
    assert int(f2[0]) != int(f[0])
    cache.unpin(f)                        # scan ends -> deferred release
    assert not cache._stale[int(f[0])]
    assert cache._frame_pid[int(f[0])] == -1
    cache.unpin(f2)


def test_partition_cache_thread_safe_interleaving(tmp_path):
    """Satellite: RLock around fault/evict/invalidate -- hammer the cache
    from several threads (as the background scheduler + query threads
    would) and assert counters/pins/mappings stay consistent."""
    import threading
    st, _, assign = _mk_store(tmp_path, n=400, k=20, seed=3)
    # pool must seat every thread's worst-case pinned set at once
    # (3 threads x 3 pins); capacity bounds pins, not thread safety
    cache = _mk_cache(st, assign, 12)
    errors = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        try:
            for i in range(40):
                pids = rng.choice(20, size=int(rng.integers(1, 4)),
                                  replace=False)
                f = cache.fault(list(pids))
                np.asarray(cache.payload_pool)   # a "scan"
                cache.unpin(f)
                if i % 7 == 0:
                    cache.invalidate([int(rng.integers(0, 20))])
        except Exception as e:               # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert (cache._pins == 0).all()
    assert cache.resident_bytes <= cache.budget_bytes
    # frame table and pid map are exact inverses
    for p, f in cache._pid_frame.items():
        assert cache._frame_pid[f] == p
    assert (cache.hits + cache.misses) >= 3 * 40


def test_fault_scatter_donates_pool_no_extra_allocation(tmp_path):
    """Satellite: the batched fault scatters through a donated jit -- the
    old pool buffers are consumed (updated in place), and the compiled
    scatter aliases its outputs to its inputs instead of allocating a
    second pool-sized buffer."""
    import jax
    from repro.storage import pager as pager_mod
    st, _, assign = _mk_store(tmp_path)
    cache = _mk_cache(st, assign, 4)
    old_payload, old_ids = cache.payload_pool, cache.ids_pool
    cache.unpin(cache.fault([0, 1]))
    # donation consumed the old buffers (no copy of the pool exists)
    assert old_payload.is_deleted() and old_ids.is_deleted()
    # compiled memory analysis: outputs alias the donated pools; temp
    # scratch stays far below one pool payload
    m = len([2])
    args = (cache.payload_pool, cache.ids_pool, cache.valid_pool,
            jnp.zeros((m,), jnp.int32),
            jnp.zeros((m, cache.p_max, st.dim), cache.payload_pool.dtype),
            jnp.zeros((m, cache.p_max), jnp.int32),
            jnp.zeros((m, cache.p_max), bool))
    mem = pager_mod._scatter_frames.lower(*args).compile() \
        .memory_analysis()
    pool_bytes = int(cache.payload_pool.nbytes + cache.ids_pool.nbytes
                     + cache.valid_pool.nbytes)
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < cache.payload_pool.nbytes
    # with foreign pins outstanding the fault must NOT donate (a
    # concurrent scan may still read the old arrays)
    pinned = cache.fault([3])
    held = cache.payload_pool
    cache.unpin(cache.fault([4, 5]))       # other partitions, pins held
    assert not held.is_deleted()
    np.asarray(held)                       # old snapshot still readable
    cache.unpin(pinned)


# -- read-ahead staging (PR 6 double-buffered faults) ------------------------


def test_stage_then_fault_consumes_staged_blocks(tmp_path, monkeypatch):
    """stage() pre-packs host blocks; the following fault must consume
    them WITHOUT another SQL round-trip and land the exact same bytes a
    cold fault would have."""
    st, _, assign = _mk_store(tmp_path)
    cache = _mk_cache(st, assign, 4)
    cache.stage([2, 5])
    assert set(cache._staged) == {2, 5}
    # the staged fault must never touch SQLite again
    def boom(*a, **k):                       # pragma: no cover
        raise AssertionError("staged fault re-fetched from the store")
    monkeypatch.setattr(st, "scan_partitions", boom)
    f = cache.fault([2, 5])
    assert not cache._staged                 # consumed, not copied
    assert (cache.hits, cache.misses) == (0, 2)   # still counted as misses
    monkeypatch.undo()
    for j, pid in zip(f, (2, 5)):
        ids, vecs = st.scan_partition(pid)
        m = len(ids)
        np.testing.assert_array_equal(
            np.asarray(cache.ids_pool)[int(j), :m], ids)
        np.testing.assert_array_equal(
            np.asarray(cache.payload_pool)[int(j), :m], vecs)
    cache.unpin(f)


def test_stage_skips_resident_partitions(tmp_path):
    st, _, assign = _mk_store(tmp_path)
    cache = _mk_cache(st, assign, 4)
    cache.unpin(cache.fault([1]))
    cache.stage([1, 4])                      # 1 is already resident
    assert set(cache._staged) == {4}
    # staging is advisory: faulting a staged pid is still a miss, a
    # resident one is still a hit
    cache.unpin(cache.fault([1, 4]))
    assert (cache.hits, cache.misses) == (1, 2)


def test_invalidate_drops_staged_blocks(tmp_path):
    """A durable write between stage() and fault() must not let the next
    fault consume the stale pre-write bytes."""
    st, X, assign = _mk_store(tmp_path)
    cache = _mk_cache(st, assign, 4)
    cache.stage([1])
    victim = int(np.nonzero(assign == 1)[0][0])
    newv = np.full((1, 8), 42.0, np.float32)
    st.upsert([victim], newv, partition_id=1)
    cache.invalidate([1])                    # write path always invalidates
    assert 1 not in cache._staged
    f = cache.fault([1])
    j = int(f[0])
    row = np.nonzero(np.asarray(cache.ids_pool)[j] == victim)[0][0]
    np.testing.assert_array_equal(np.asarray(cache.payload_pool)[j, row],
                                  newv[0])
    cache.unpin(f)


def test_invalidate_mid_fetch_discards_whole_stage_batch(tmp_path):
    """The generation counter: an invalidate that lands while a stage()
    is off-lock inside its SQLite fetch must poison the ENTIRE in-flight
    batch -- the stage read a mix of pre- and post-write rows and cannot
    tell which, so nothing it fetched may be inserted."""
    st, _, assign = _mk_store(tmp_path)
    cache = _mk_cache(st, assign, 4)
    real = st.scan_partitions

    def racing(*a, **k):
        blocks = real(*a, **k)
        cache.invalidate([9])        # a writer commits mid-fetch
        return blocks

    st.scan_partitions = racing
    try:
        cache.stage([3, 4])
    finally:
        st.scan_partitions = real
    assert not cache._staged         # whole batch dropped, not just pid 9
    cache.unpin(cache.fault([3, 4]))     # next fault re-reads fresh bytes
    assert cache.misses == 2


def test_paged_exact_prefetch_on_off_bitwise(paged_pair):
    """Engine-level pin: the read-ahead pipeline must never change what
    an exact paged scan computes -- ids AND scores bit-identical with
    prefetch forced off."""
    from repro.core import executor
    _, pag, X = paged_pair
    q = X[:4]
    before = executor.PAGED_PREFETCH
    try:
        executor.PAGED_PREFETCH = False
        r_off = pag.search(q, k=10, exact=True)
        executor.PAGED_PREFETCH = True
        r_on = pag.search(q, k=10, exact=True)
    finally:
        executor.PAGED_PREFETCH = before
    np.testing.assert_array_equal(np.asarray(r_off.ids),
                                  np.asarray(r_on.ids))
    np.testing.assert_array_equal(np.asarray(r_off.scores),
                                  np.asarray(r_on.scores))


# -- dtype-aware tile padding (satellite) ------------------------------------


def test_effective_pad_to_dtype_aware():
    f32 = IVFConfig(dim=8, pad_to=8)
    sq = IVFConfig(dim=8, pad_to=8, quantize="int8")
    assert effective_pad_to(f32, backend="tpu") == 8
    assert effective_pad_to(sq, backend="tpu") == 32
    assert effective_pad_to(sq, backend="cpu") == 8
    wide = IVFConfig(dim=8, pad_to=64, quantize="int8")
    assert effective_pad_to(wide, backend="tpu") == 64


def test_sq_kernel_asserts_tile_padding():
    from repro.kernels import sq_scan
    q = jnp.zeros((1, 8))
    codes = jnp.zeros((2, 8, 8), jnp.int8)      # p_max=8: not 32-aligned
    ok = jnp.ones((2, 8), bool)
    ids = jnp.zeros((2, 8), jnp.int32)
    with pytest.raises(AssertionError):
        sq_scan.sq_scan_topk(q, codes, jnp.zeros(8), jnp.ones(8), ok, ids,
                             jnp.arange(2, dtype=jnp.int32), 4,
                             interpret=False)
