#!/usr/bin/env python3
"""Smoke run of MicroNN's query path on one TPU chip at SIFT-1M scale.

    python chip_smoke.py              # one chip: the engine end to end
    python chip_smoke.py --four-chip  # four chips: sharded search only

The one-chip run drives the public engine once, in one process, on the
paper's headline deployment (Table 2 SIFT shape: 1,000,000 x 128 f32, L2,
2 float attributes per row, the default IVFConfig with int8 codes and
rerank factor 4):

  1. device      a TPU must be attached, else exit 1 before any work
  2. cache       the persistent compilation cache directory in use
  3. load/build  upsert every row into SQLite, then build()
  4. queries     MicroNN.query on the default (Pallas) backend: exact
                 top-100 recall and f32-exact scores, f32 and int8 ANN
                 recall at N_PROBE, a filtered query, Q=1/Q=32 batches
                 with no retrace on a warm repeat
  5. writes      a 1,000-row session upsert, 100 deletes, a flush
  6. paged       a 10 MB disk-resident engine recovered from the file
  7. front door  8 threads coalesced through a FrontDoor

`--four-chip` builds the same index, shards its partitions over a (1, 4)
("data", "model") mesh and checks distributed_query's ids against the
same spec run on one device.

Every check raises on failure. The last line of standard output is the
JSON result, printed only when every phase passed. Times printed on the
way are smoke readings of one run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
DIM = 128
# Probes per ANN query, fixed before the chip run: at this data shape a
# 100,000-row build reaches recall@100 = 0.9999 at 4 probes and 1.0 at 8.
N_PROBE = 8
K = 100
N_QUERIES = 128
N_NEW, N_DELETED = 1000, 100
PAGED_BUDGET_MB = 10
PAGED_QUERIES = 32


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str):
    print(msg, flush=True)


class Phases:
    """Wall-clock time of each phase, printed as it ends."""

    def __init__(self):
        self.times = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        say(f"[{name}] start")
        yield
        self.times[name] = time.perf_counter() - t0
        say(f"[{name}] done in {self.times[name]:.3f} s (smoke reading)")


def require_tpu(n_chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found platform {dev.platform!r}; this smoke "
              f"run needs a TPU and has no CPU path", file=sys.stderr)
        sys.exit(1)
    if len(devs) < n_chips:
        print(f"needs {n_chips} TPU chips, found {len(devs)}",
              file=sys.stderr)
        sys.exit(1)
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    say(f"device: {info}")
    return info


def make_data(scale: float, seed: int):
    from repro.data import synthetic
    ds = synthetic.make("sift", scale=scale, seed=seed, with_gt=False)
    rng = np.random.default_rng(seed + 1)
    n = len(ds.X)
    attrs = np.stack([rng.integers(0, 10, n),          # "location"
                      rng.integers(2000, 2025, n)],    # "year"
                     axis=1).astype(np.float32)
    queries = ds.Q[:N_QUERIES]
    return ds.X, attrs, queries


def recall_at(ids: np.ndarray, gt_ids: np.ndarray, k: int) -> float:
    hits = sum(len(set(a[a >= 0].tolist()) & set(b[:k].tolist()))
               for a, b in zip(ids[:, :k], gt_ids))
    return hits / (len(gt_ids) * k)


def hbm_line(dev) -> str:
    st = dev.memory_stats() or {}
    return (f"HBM peak_bytes_in_use={st.get('peak_bytes_in_use')} "
            f"bytes_in_use={st.get('bytes_in_use')} "
            f"bytes_limit={st.get('bytes_limit')}")


def timed_query(eng, q, spec):
    t0 = time.perf_counter()
    rs = eng.query(q, spec)
    ids = np.asarray(rs.ids)
    return rs, ids, (time.perf_counter() - t0) * 1e3


def run_one_chip(work: str, scale: float = 1.0, seed: int = 0):
    import jax
    from repro.core import executor
    from repro.core.hybrid import Pred
    from repro.core.query import Q
    from repro.data import synthetic
    from repro.serving.frontdoor import FrontDoor
    from repro.storage import MicroNN

    phase = Phases()
    dev = jax.devices()[0]
    say(f"scan backend: {executor.default_backend()}; N_PROBE={N_PROBE}")

    with phase("data"):
        X, attrs, qv = make_data(scale, seed)
        n = len(X)
        gt = synthetic.exact_gt(X, qv, K, "l2")     # rows == asset ids
        say(f"rows={n} dim={X.shape[1]} queries={len(qv)}")

    db = os.path.join(work, "sift.db")
    with phase("load"):
        eng = MicroNN(dim=DIM, n_attr=2, path=db, quantize="int8",
                      rerank_factor=4)
        chunk = 100_000
        for s in range(0, n, chunk):
            e = min(s + chunk, n)
            eng.upsert(np.arange(s, e), X[s:e], attrs[s:e])
    with phase("build"):
        eng.build()
        jax.block_until_ready(eng.index.vectors)
    # read eng.index afresh each time: a local reference would pin this
    # version's tiers in HBM after the flush below replaces them
    f32_bytes = int(eng.index.vectors.nbytes)
    i8_bytes = int(eng.index.codes.nbytes)
    say(f"index: k={eng.index.k} p_max={eng.index.p_max} "
        f"f32 tier={f32_bytes} B int8 tier={i8_bytes} B "
        f"(rows {n * DIM * 4} B unpadded)")
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if limit is not None and f32_bytes + i8_bytes > limit:
        say(f"padded tiers ({f32_bytes + i8_bytes} B) exceed HBM ({limit} B)")
    say(hbm_line(dev))

    # -- resident queries -------------------------------------------------
    with phase("exact"):
        rs, ids, ms = timed_query(eng, qv, Q.exact(k=K))
        rec = recall_at(ids, gt, K)
        say(f"exact recall@{K}={rec:.6f} over {len(qv)} queries "
            f"({ms:.1f} ms incl. compile)")
        check(rec >= 0.999, f"exact recall {rec} < 0.999")
        sc = np.asarray(rs.scores, np.float64)
        got = ids >= 0
        check(got.all(), "exact query returned fewer than k rows")
        x64 = X[ids].astype(np.float64)                  # [Q, K, d]
        q64 = qv.astype(np.float64)
        ref = ((q64[:, None, :] - x64) ** 2).sum(-1)
        bound = 1e-5 * ((q64 ** 2).sum(-1)[:, None] + (x64 ** 2).sum(-1))
        err = np.abs(sc - ref)
        say(f"exact score error: max={err.max():.6g} "
            f"max/bound={(err / bound).max():.6g}")
        check((err <= bound).all(), "exact scores are not f32-exact")

    for quant in (False, True):
        name = "ann_int8" if quant else "ann_f32"
        with phase(name):
            spec = Q.knn(k=K, n_probe=N_PROBE).quantized(quant)
            rs, ids, ms = timed_query(eng, qv, spec)
            rec = recall_at(ids, gt, K)
            say(f"{name} recall@{K}={rec:.6f} n_probe={N_PROBE} "
                f"({ms:.1f} ms incl. compile)")
            check(rec >= 0.90, f"{name} recall {rec} < 0.90")

    with phase("filtered"):
        spec = Q.knn(k=10, n_probe=N_PROBE).where(Pred(0, "==", 3.0))
        rs, ids, ms = timed_query(eng, qv, spec)
        live = ids[ids >= 0]
        check((attrs[live, 0] == 3.0).all(), "filtered result breaks Pred")
        keep = np.nonzero(attrs[:, 0] == 3.0)[0]
        fgt = keep[synthetic.exact_gt(X[keep], qv, 10, "l2")]
        rec = recall_at(ids, fgt, 10)
        say(f"filtered recall@10={rec:.6f} (selectivity "
            f"{len(keep) / n:.3f}, {ms:.1f} ms incl. compile)")
        check(rec >= 0.90, f"filtered recall {rec} < 0.90")

    with phase("batches"):
        specs = {"exact": Q.exact(k=K),
                 "ann_f32": Q.knn(k=K, n_probe=N_PROBE).quantized(False),
                 "ann_int8": Q.knn(k=K, n_probe=N_PROBE).quantized(True)}
        for b in (1, 32):
            for name, spec in specs.items():
                timed_query(eng, qv[:b], spec)          # warm
        tc0 = executor.trace_count()
        for b in (1, 32):
            for name, spec in specs.items():
                _, ids, ms = timed_query(eng, qv[:b], spec)
                say(f"warm {name} Q={b}: {ms:.2f} ms (smoke reading) "
                    f"recall@{K}={recall_at(ids, gt[:b], K):.4f}")
        tc1 = executor.trace_count()
        say(f"trace_count before={tc0} after={tc1}")
        check(tc0 == tc1, "warm repeat retraced")

    # -- writes -----------------------------------------------------------
    rng = np.random.default_rng(seed + 2)
    new_ids = np.arange(n, n + N_NEW)
    new_vecs = (X[rng.integers(0, n, N_NEW)]
                + rng.normal(size=(N_NEW, DIM))).astype(np.float32)
    new_attrs = np.zeros((N_NEW, 2), np.float32)
    gone, kept = new_ids[:N_DELETED], new_ids[N_DELETED:]
    top1 = Q.knn(k=1, n_probe=N_PROBE)
    top10 = Q.knn(k=10, n_probe=N_PROBE)

    def check_writes(e, where: str):
        ids = np.asarray(e.query(new_vecs[N_DELETED:], top1).ids)[:, 0]
        check((ids == kept).all(),
              f"{where}: {int((ids != kept).sum())} upserted rows are not "
              f"their own top-1")
        ids = np.asarray(e.query(new_vecs[:N_DELETED], top10).ids)
        check(not np.isin(ids, gone).any(),
              f"{where}: a deleted row came back")
        say(f"{where}: {len(kept)} upserts are their own top-1, "
            f"{len(gone)} deletes absent")

    with phase("writes"):
        with eng.session() as s:
            s.upsert(new_ids, new_vecs, new_attrs)
        ids = np.asarray(eng.query(new_vecs, top1).ids)[:, 0]
        check((ids == new_ids).all(), "session upserts not visible")
        with eng.session() as s:
            s.delete(gone)
        check_writes(eng, "before flush")
        eng.maintain(force="flush")
        check_writes(eng, "after flush")
        say(f"p_max after flush={eng.index.p_max}")

    # -- paged: the paper's 10 MB disk-resident mode ------------------------
    with phase("paged"):
        peng = MicroNN(dim=DIM, n_attr=2, path=db, quantize="int8",
                       rerank_factor=4, memory_budget_mb=PAGED_BUDGET_MB)
        peng.recover()
        rs, ids, ms = timed_query(peng, qv[:PAGED_QUERIES],
                                  Q.knn(k=K, n_probe=N_PROBE))
        rec = recall_at(ids, gt[:PAGED_QUERIES], K)
        say(f"paged recall@{K}={rec:.6f} over {PAGED_QUERIES} queries "
            f"({ms:.1f} ms incl. compile)")
        check(rec >= 0.90, f"paged recall {rec} < 0.90")
        check_writes(peng, "paged after recover")
        # the pager's own counters (MicroNN.stats() would also plan the
        # maintenance queue, a host pass over all 10,000 partitions)
        st = peng.index.cache.stats()
        say(f"paged frames: resident_bytes={st['resident_bytes']} "
            f"budget_bytes={st['budget_bytes']} hits={st['hits']} "
            f"misses={st['misses']} evictions={st['evictions']}")
        check(st["resident_bytes"] <= st["budget_bytes"],
              "paged frames exceed the budget")

    # -- front door ---------------------------------------------------------
    with phase("frontdoor"):
        spec = Q.knn(k=10, n_probe=N_PROBE)
        chunks = [qv[4 * t:4 * t + 4] for t in range(8)]
        solo = [np.asarray(eng.query(c, spec).ids) for c in chunks]
        out = [None] * len(chunks)
        fd = FrontDoor(eng)
        try:
            def caller(t):
                out[t] = np.asarray(
                    fd.submit(chunks[t], spec).result(timeout=600).ids)
            threads = [threading.Thread(target=caller, args=(t,))
                       for t in range(len(chunks))]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            st = fd.stats()
        finally:
            fd.close()
        for t, (a, b) in enumerate(zip(out, solo)):
            check(a is not None and np.array_equal(a, b),
                  f"front-door caller {t} differs from its solo query")
        sched = eng.scheduler.stats()
        say(f"frontdoor: submitted={st['submitted']} batches={st['batches']} "
            f"coalesced={st['coalesced']} failed={st['failed']} "
            f"daemon_errors={sched['daemon_errors']}")
        check(st["failed"] == 0, "front-door requests failed")
        check(sched["daemon_errors"] == 0, "maintenance daemon errors")
    say(hbm_line(dev))
    say("phase times (s, smoke readings): "
        + json.dumps({k: round(v, 3) for k, v in phase.times.items()}))


def run_four_chip(scale: float = 1.0, seed: int = 0):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import executor, ivf
    from repro.core.query import Q
    from repro.core.types import IVFConfig
    from repro.distributed.sharded_index import (distributed_query,
                                                 index_shardings)

    phase = Phases()
    devs = jax.devices()[:4]
    with phase("build"):
        X, attrs, qv = make_data(scale, seed)
        qv = qv[:32]
        idx = ivf.build_index(X, np.arange(len(X), dtype=np.int32), attrs,
                              cfg=IVFConfig(dim=DIM, quantize="int8"))
        jax.block_until_ready(idx.vectors)
    say(f"index: k={idx.k} p_max={idx.p_max}")
    mesh = Mesh(np.array(devs).reshape(1, 4), ("data", "model"))
    with phase("shard"):
        sharded = jax.device_put(idx, index_shardings(idx, mesh))
        jax.block_until_ready(sharded.vectors)
    homes = {s.device for s in sharded.vectors.addressable_shards}
    say(f"partition shards on {len(homes)} devices: "
        f"{sorted(str(d) for d in homes)}")
    check(len(homes) == 4, "partition shards are not on 4 devices")
    spec = Q.knn(k=K, n_probe=N_PROBE).quantized(False).backend("xla")
    with phase("single_device"):
        ref = np.asarray(executor.run(idx, qv, spec).ids)
    q_sh = jax.device_put(qv, NamedSharding(mesh, P("data", None)))
    for merge in ("tournament", "allgather"):
        with phase(f"sharded_{merge}"):
            ids = np.asarray(
                distributed_query(sharded, q_sh, spec, mesh, merge=merge).ids)
        same = float((ids == ref).mean())
        say(f"{merge}: ids equal to one device's, position by position: "
            f"{same:.6f}")
        check(same == 1.0, f"{merge} merge differs from one device")
    say("phase times (s, smoke readings): "
        + json.dumps({k: round(v, 3) for k, v in phase.times.items()}))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded search on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = require_tpu(4 if args.four_chip else 1)
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro import compile_cache
    say("compile cache: "
        + compile_cache.configure(os.path.join(REPO, ".jax_cache")))
    if args.four_chip:
        run_four_chip(seed=args.seed)
    else:
        work = os.path.join(REPO, ".smoke")
        shutil.rmtree(work, ignore_errors=True)
        os.makedirs(work)
        try:
            run_one_chip(work, seed=args.seed)
        finally:
            shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
