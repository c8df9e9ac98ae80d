"""The build bounds partition size (maintenance.split_to_bar).

On isotropic Gaussian blobs at SIFT's and NYTimes' widths, where the
mini-batch k-means leaves partitions of two or three blobs (1,000 to
1,400 rows at a target of 400), a `build()` leaves none above
FRAME_ROWS_MAX (768 rows, what the paged int8 frame scan compiles)
except counted partitions whose rows are all equal; the resident and
paged builds give the same clustering; a full rebuild bounds partitions
as the build does; the counters and the `build_split` stage report what
was done; and the paged engine answers like the float32 brute force and
like the resident engine.
"""
import numpy as np
import pytest

from repro.core import maintenance
from repro.core.query import Q
from repro.core.types import FRAME_ROWS_MAX, IVFConfig, effective_pad_to
from repro.data import synthetic
from repro.obs import trace as obs_trace
from repro.storage import MicroNN

# 20,000 x 128 (L2) and 14,500 x 256 (cosine) rows, n/500 blobs each
SHAPES = {"sift": 0.02, "nytimes": 0.05}
SEEDS = [3100000004, 3100000005, 3100000006, 2 ** 31 + 77]
BAR = FRAME_ROWS_MAX
TARGET = 400
COUNTERS = ("build_splits", "build_rows_split", "build_unsplittable",
            "partition_max_rows")


def _build(path, X, metric, budget, target=TARGET):
    n, d = X.shape
    eng = MicroNN(dim=d, path=path, config=IVFConfig(
        dim=d, metric=metric, target_partition_size=target),
        quantize="int8", memory_budget_mb=budget)
    eng.upsert(np.arange(n), X)
    tr = obs_trace.QueryTrace(mode="build")
    with obs_trace.activate(tr):
        eng.build()
    return eng, tr


def _clustering(eng):
    ids, parts, _ = eng.store.all_rows()
    return np.asarray(eng.index.centroids), parts[np.argsort(ids)]


@pytest.fixture(scope="module", params=[(n, s) for n in SHAPES
                                        for s in SEEDS],
                ids=lambda p: f"{p[0]}-{p[1]}")
def built(request, tmp_path_factory):
    name, seed = request.param
    ds = synthetic.make(name, scale=SHAPES[name], seed=seed, with_gt=False)
    tmp = tmp_path_factory.mktemp(f"{name}{seed}")
    engines = {mode: _build(str(tmp / f"{mode}.db"), ds.X, ds.metric, budget)
               for mode, budget in (("resident", None), ("paged", 10))}
    yield ds, engines
    for eng, _ in engines.values():
        eng.store.close()


def test_no_partition_above_the_split_bar(built):
    _, engines = built
    for mode, (eng, _) in engines.items():
        st = eng.stats()["scheduler"]
        counts = np.asarray(eng.index.counts)
        # the k-means left partitions above the bar, and the pass split them
        assert st["build_splits"] > 0 and st["build_unsplittable"] == 0, mode
        assert counts.max() <= BAR, mode
        pad = effective_pad_to(eng.config)
        assert eng.index.p_max <= -(-BAR // pad) * pad, mode
        assert effective_pad_to(eng.config, backend="tpu") == 32
        assert counts.sum() == len(built[0].X)


def test_resident_and_paged_builds_cluster_identically(built):
    _, engines = built
    cents_r, assign_r = _clustering(engines["resident"][0])
    cents_p, assign_p = _clustering(engines["paged"][0])
    np.testing.assert_array_equal(cents_r, cents_p)
    np.testing.assert_array_equal(assign_r, assign_p)
    np.testing.assert_array_equal(
        np.asarray(engines["resident"][0].index.counts),
        np.asarray(engines["paged"][0].index.counts))


def test_full_rebuild_bounds_like_the_build(built):
    """maintenance.full_rebuild re-clusters with the same split pass: the
    resident engine's index, rebuilt, holds no partition above the bar."""
    ds, engines = built
    new, stats = maintenance.full_rebuild(engines["resident"][0].index)
    counts = np.asarray(new.counts)
    assert counts.max() <= BAR and counts.sum() == len(ds.X)
    assert stats.p_max_after == new.p_max


def test_counters_and_stage_report_the_split_pass(built):
    _, engines = built
    seen = []
    for mode, (eng, tr) in engines.items():
        st = eng.stats()["scheduler"]
        span = tr.get(obs_trace.STAGE_BUILD_SPLIT)
        assert span is not None and span.calls == 1, mode
        c = span.counters
        assert (st["build_splits"], st["build_rows_split"],
                st["build_unsplittable"], st["partition_max_rows"]) == \
            (c["splits"], c["rows_split"], c["unsplittable"], c["max_rows"])
        assert st["partition_max_rows"] == int(
            np.asarray(eng.index.counts).max())
        assert st["build_split_ms"] == c["ms"] > 0
        # each split's rows were all in partitions above the bar
        assert st["build_rows_split"] > BAR * st["build_splits"] // 2
        seen.append(tuple(st[k] for k in COUNTERS))
    assert seen[0] == seen[1]


def test_paged_answers_match_brute_force_and_resident(built):
    """The plain reference: float32 brute force over the rows. Recall of
    the paged engine's top-100 at 8 probes against it, the float32 L2 or
    cosine score of every returned row to 1e-5 of its float64 score
    (scaled by ||q||^2 + ||x||^2 for L2), and the resident engine's
    answers on the same clustering, bit for bit."""
    ds, engines = built
    qs, k = ds.Q[:32], 100
    spec = Q.knn(k=k, n_probe=8)
    res = engines["resident"][0].query(qs, spec)
    pag = engines["paged"][0].query(qs, spec)
    ids, scores = (np.asarray(a) for a in (pag.ids, pag.scores))
    np.testing.assert_array_equal(ids, np.asarray(res.ids))
    np.testing.assert_array_equal(scores, np.asarray(res.scores))
    gt = synthetic.exact_gt(ds.X, qs, k, ds.metric)
    assert synthetic.recall(ids, gt, np.arange(len(ds.X)), k) >= 0.9
    X, q = ds.X.astype(np.float64), qs.astype(np.float64)
    got = ids >= 0
    rows = X[np.where(got, ids, 0)]
    if ds.metric == "cosine":
        xn = rows / np.linalg.norm(rows, axis=-1, keepdims=True)
        qn = q / np.linalg.norm(q, axis=-1, keepdims=True)
        exact = -(xn * qn[:, None, :]).sum(-1)
        scale = 1.0
    else:
        exact = ((rows - q[:, None, :]) ** 2).sum(-1)
        scale = (q * q).sum(-1)[:, None] + (rows * rows).sum(-1)
    gap = np.abs(scores - exact) / scale
    assert gap[got].max() <= 1e-5


@pytest.mark.parametrize("budget", [None, 10], ids=["resident", "paged"])
def test_partitions_of_equal_rows_stay_and_are_counted(tmp_path, budget):
    """2-means cannot split rows that are all equal: that partition stays
    above the bar, counted as unsplittable, and every other is under it."""
    rng = np.random.default_rng(5)
    blobs = (rng.normal(size=(8, 16)) * 4)[rng.integers(0, 8, 1200)]
    X = np.concatenate([blobs + rng.normal(size=(1200, 16)),
                        np.full((BAR + 32, 16), 100.0)]).astype(np.float32)
    eng, tr = _build(str(tmp_path / "eq.db"), X, "l2", budget, target=20)
    st = eng.stats()["scheduler"]
    counts = np.asarray(eng.index.counts)
    assert st["build_unsplittable"] == 1
    assert (counts > BAR).sum() == 1 and counts.max() == BAR + 32
    assert tr.get(obs_trace.STAGE_BUILD_SPLIT).counters["unsplittable"] == 1
    eng.store.close()


def test_split_pass_alone_on_a_hand_made_clustering():
    """One partition of 1,600 rows in three far-apart groups of 700, 500
    and 400, beside one empty slot: the pass splits it until every piece
    is under the bar, each piece holding rows of one group; the first
    split fills the empty slot, the next appends."""
    rng = np.random.default_rng(0)
    off = np.zeros((3, 8), np.float32)
    off[1, 0], off[2, 1] = 50.0, 50.0
    group = np.repeat(np.arange(3), [700, 500, 400])
    X = (rng.normal(size=(1600, 8)) + off[group]).astype(np.float32)
    ids = rng.permutation(1600)
    assign = np.zeros(1600, np.int64)
    cents = np.stack([X.mean(0), np.zeros(8)]).astype(np.float32)
    csz = np.array([1600.0, 0.0], np.float32)
    c, s, a, st = maintenance.split_to_bar(cents, csz, ids, assign,
                                           lambda pos: X[pos], "l2")
    counts = np.bincount(a)
    assert counts.max() <= BAR and counts.sum() == 1600
    assert len(c) == len(s) == len(counts) == 3 and counts[1] > 0
    assert st.splits == 2 and st.unsplittable == 0
    assert st.max_rows == counts.max() and st.ms > 0
    for p in range(len(counts)):
        assert len(np.unique(group[a == p])) == 1
        np.testing.assert_allclose(c[p], X[a == p].mean(0), rtol=1e-5,
                                   atol=1e-5)
        assert s[p] == counts[p]


def test_build_split_reaches_the_profiler(tmp_path):
    """Under a profiler session an untraced build emits its split pass as
    a `micronn.build_split` host event."""
    import jax

    from chipbench import trace_reduce
    X = synthetic.make("sift", scale=0.002, seed=3, with_gt=False).X
    eng = MicroNN(dim=128, path=str(tmp_path / "p.db"),
                  config=IVFConfig(dim=128, target_partition_size=20),
                  memory_budget_mb=1)
    eng.upsert(np.arange(len(X)), X)
    tdir = str(tmp_path / "trace")
    with jax.profiler.trace(tdir):
        eng.build()
    name = obs_trace.PROFILER_PREFIX + obs_trace.STAGE_BUILD_SPLIT
    assert [e.name for e in trace_reduce.load(tdir, (name,))] == [name]
    eng.store.close()
