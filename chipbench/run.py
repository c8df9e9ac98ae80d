#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A run names its device and refuses any but a TPU with the chips the
cell asks for (exit 1, no result). It makes the collection and the
queries from `--seed` with the benchmark's own generator, loads them
through `MicroNN.upsert` into SQLite inside the checkout, builds with the
deployment's own `build()`, warms the shapes the cell's traffic sends,
then drives the traffic for `--seconds`. `setup_s` runs from process
start to the window. After the window it reads the device's peak memory,
then judges a sample of the answers against the plain reference
(`reference.py`). `--trace 0` reports the end-to-end metrics; `--trace 1`
records the window with the JAX profiler, then sends a few more
requests with the engine's spans on (a span-traced request does work of
its own, so the window's requests run as in `--trace 0`), and reports
the per-layer metrics instead.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from chipbench import bench as benchfile  # noqa: E402
from chipbench import data, loadgen, reference, trace_reduce  # noqa: E402

WORK = os.path.join(ROOT, ".chipbench")
HOST_SPANS = ("query", "submit", "session")


class NoChip(RuntimeError):
    pass


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {devs[0].platform!r}; "
                     f"the benchmark has no CPU path")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


@contextlib.contextmanager
def _no_annotation(name):
    yield


def _annotation(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _pct(xs, p):
    return float(np.percentile(np.asarray(xs, np.float64), p))


def end_to_end(win: loadgen.Window, setup_s: float) -> dict:
    ok = [r for r in win.requests if r.error is None]
    lat = [(r.t1 - r.t0) * 1e3 for r in ok]
    out = {"setup_s": setup_s}
    if lat:
        out["query_p50_ms"] = _pct(lat, 50)
        out["query_p95_ms"] = _pct(lat, 95)
        out["qps"] = len(ok) / (win.t_end - win.t_start)
    if win.sessions:
        out["write_p95_ms"] = _pct(
            [(s.t1 - s.due) * 1e3 for s in win.sessions], 95)
    return out


def counters(eng, fd) -> dict:
    from repro.core import executor
    out = {"trace_count": executor.trace_count(),
           "scheduler": dict(eng.scheduler.stats())}
    if eng.paged:
        out["pager"] = dict(eng.index.cache.stats())
    if fd is not None:
        out["fd"] = dict(fd.stats())
    return out


def layout(eng) -> dict:
    idx = eng.index
    counts = np.asarray(idx.counts).astype(np.int64)
    p_max = int(idx.cache.p_max) if eng.paged else int(idx.vectors.shape[1])
    return {"counts": counts, "p_max": p_max, "k": int(len(counts)),
            "centroids": np.asarray(idx.centroids), "paged": eng.paged}


def probe_rows(lay: dict, queries: np.ndarray, n_probe: int,
               metric: str) -> np.ndarray:
    """Valid rows in each query's own probe set: the n_probe nearest
    non-empty partitions, as the engine's probe picks them."""
    c = lay["centroids"].astype(np.float32)
    q = data.normalize(queries) if metric == "cosine" else queries
    out = np.empty(len(q), np.int64)
    for s in range(0, len(q), 1024):
        qq = q[s:s + 1024].astype(np.float32)
        if metric == "cosine":
            sc = -(qq @ c.T)
        else:
            sc = (c * c).sum(1)[None, :] - 2.0 * (qq @ c.T)
        sc[:, lay["counts"] == 0] = np.inf
        top = np.argpartition(sc, n_probe - 1, axis=1)[:, :n_probe]
        out[s:s + 1024] = lay["counts"][top].sum(1)
    return out


class RunRecord:
    """What a per-layer reader reads: the cell, the window, the
    program's counters before and after it, its spans, the index
    layout, and the reduced device trace."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def delta(self, group: str, key: str) -> float:
        return float(self.after[group][key]) - float(self.before[group][key])


def _sample(n: int, size: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 3])
    return np.sort(rng.choice(n, size=min(size, n), replace=False))


def _answers(win, pool, sample, writer, n_base):
    """The sampled requests as `reference.Answer`s, each with the
    inserted rows that were live all through its flight and those whose
    state changed during it."""
    ups, dels = {}, {}
    if writer is not None:
        for s in win.sessions:
            for i in s.upserted:
                ups[int(i)] = (s.t0, s.t1)
            for i in s.deleted:
                dels[int(i)] = (s.t0, s.t1)
    out = []
    for r in sample:
        if r.error is not None:
            continue
        for j in range(r.ids.shape[0]):
            live = maybe = None
            if writer is not None:
                live, maybe = [], []
                for i in range(n_base, n_base + writer.next):
                    u0, u1 = ups.get(i, (-1.0, -1.0))
                    d0, d1 = dels.get(i, (np.inf, np.inf))
                    if u1 < r.t0 and d0 > r.t1:
                        live.append(i)
                    elif not (u0 > r.t1 or d1 < r.t0):
                        maybe.append(i)
                live, maybe = np.array(live, np.int64), np.array(maybe,
                                                                  np.int64)
            out.append(reference.Answer(q=pool[r.qi + j], ids=r.ids[j],
                                        scores=r.scores[j], live=live,
                                        maybe=maybe))
    return out


def read_back(eng, writer, Xall: np.ndarray, seed: int, n_probe: int,
              sample: int) -> int:
    """Acknowledged writes that the engine does not show: live inserted
    rows that are not their own top-1, and deleted rows that come back."""
    from repro.core.query import Q
    lost = 0
    live = np.array(writer.live, np.int64)
    for s in range(0, len(live), 64):
        ids = live[s:s + 64]
        got = eng.query(Xall[ids], Q.knn(k=1, n_probe=n_probe)).to_numpy()[0]
        lost += int((got[:, 0] != ids).sum())
    gone = np.setdiff1d(np.arange(writer.n_base,
                                  writer.n_base + writer.next), live)
    asked = gone[_sample(len(gone), sample, seed)]
    for s in range(0, len(asked), 64):
        ids = asked[s:s + 64]
        got = eng.query(Xall[ids], Q.knn(k=10, n_probe=n_probe)).to_numpy()[0]
        lost += int(np.isin(got, gone).sum())
    return lost


def judged_correct(checks: dict, limits: dict) -> bool:
    """`correct`: every compared number at or under its limit."""
    return all(checks[n] <= limits[n] for n in limits)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, require_tpu: bool = True,
             scale: float | None = None, control: bool = False,
             work: str = WORK, t_process: float = T_PROCESS) -> dict:
    """One run of one cell. Returns the result object the CLI prints
    (plus `control` readings when asked, for chipbench/control.py)."""
    bench = benchfile.load(root)
    c = benchfile.cell(bench, workload, root)
    cfg, mix, limits = c["config"], c["traffic"], c["limits"]
    import jax
    from repro import compile_cache
    from repro.core.types import IVFConfig
    from repro.storage import MicroNN
    device = device_info(c["workload"]["chips"], require_tpu)
    if require_tpu:     # CPU test runs keep the process's cache off
        compile_cache.configure(os.path.join(root, ".jax_cache"))

    phases = {}
    t_phase = [time.perf_counter()]

    def phase(name):
        now = time.perf_counter()
        phases[name] = now - t_phase[0]
        t_phase[0] = now
        print(f"[{workload}] {name} {phases[name]:.3f} s", file=sys.stderr,
              flush=True)

    phase("start")
    scale = cfg["scale"] if scale is None else scale
    n_pool = int(mix["query_pool"])
    n_warm = int(mix["q_rows"]) * (int(mix["callers"]) *
                                   int(mix["warmup_rounds"]) + 64)
    ds = data.make(cfg["dataset"], scale=scale, seed=seed,
                   n_queries=n_pool + n_warm)
    X, pool, warm_pool = ds.X, ds.Q[:n_pool], ds.Q[n_pool:]
    n_base, dim = X.shape
    metric = ds.metric
    assert metric == cfg["metric"] and dim == cfg["dim"], (metric, dim)
    writer = None
    Xall = X
    if "writer" in mix:
        w = mix["writer"]
        n_new = int(w["live_rows"]) + int(w["upserts"]) * int(
            (seconds + 5) / float(w["period_s"]) + 1)
        new = data.new_rows(X, n_new, seed)
        writer = loadgen.Writer(mix, new, n_base, int(cfg["n_attr"]))
        Xall = np.concatenate([X, new])

    phase("data")
    os.makedirs(work, exist_ok=True)
    db = os.path.join(work, f"{workload}.db")
    _rm_db(db)
    eng = fd = None
    try:
        e = cfg["engine"]
        eng = MicroNN(dim=dim, n_attr=int(cfg["n_attr"]), path=db,
                      config=IVFConfig(dim=dim, metric=metric),
                      quantize=e["quantize"],
                      rerank_factor=int(e["rerank_factor"]),
                      memory_budget_mb=e.get("memory_budget_mb"))
        attrs = data.attributes(n_base, seed)
        chunk = int(cfg["load_chunk_rows"])
        for s in range(0, n_base, chunk):
            eng.upsert(np.arange(s, min(s + chunk, n_base)), X[s:s + chunk],
                       attrs[s:s + chunk])
        phase("load")
        eng.build()
        if not eng.paged:
            jax.block_until_ready(eng.index.vectors)
        lay = layout(eng)
        phase("build")
        fd = loadgen.open_frontdoor(eng, mix)
        if writer is not None:
            writer.fill(eng)
            eng.maintain(force="flush")
        loadgen.warmup(eng, fd, mix, warm_pool)
        gc.collect()
        phase("warmup")
        before = counters(eng, fd)
        tdir = os.path.join(work, "trace", workload)
        if trace:
            shutil.rmtree(tdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(tdir, profiler_options=opts)
        setup_s = time.perf_counter() - t_process
        win = loadgen.window(eng, fd, mix, pool, seconds,
                             _annotation if trace else _no_annotation,
                             writer)
        if trace:
            jax.profiler.stop_trace()
        after = counters(eng, fd)
        phase("window")
        peak = int((jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use", 0))
        db_bytes = sum(os.path.getsize(db + x) for x in ("", "-wal")
                       if os.path.exists(db + x))
        if trace:
            spans, n_traced = loadgen.span_sample(eng, fd, mix, pool)
        if fd is not None:
            fd.close()
            fd = None
        failed = sum(r.error is not None for r in win.requests)
        attempted = len(win.requests) + len(win.sessions)
        # correctness: a seeded sample of the answered requests
        reqs = sorted(win.requests, key=lambda r: (r.t0, r.qi))
        pick = [reqs[i] for i in _sample(len(reqs), int(mix["sample"]),
                                         seed)]
        ans = _answers(win, pool, pick, writer, n_base)
        k = int(mix["k"])
        checks = reference.judge(ans, Xall, n_base, k, metric) if ans \
            else {"miss_rate": 1.0, "score_gap": 1.0, "bad_ids": 0}
        checks["unanswered"] = failed
        checks["retraces"] = after["trace_count"] - before["trace_count"]
        if writer is not None:
            checks["writes_lost"] = read_back(
                eng, writer, Xall, seed, int(mix["n_probe"]),
                int(mix["sample"]))
        # the limits file names the numbers compared; the rest are
        # readings (PERF.md says why each one is or is not compared)
        readings = {n: checks.pop(n) for n in list(checks)
                    if n not in limits}
        out_control = None
        if control:
            ctl = reference.control_answers(ans, Xall, n_base, k, metric)
            out_control = reference.judge(ctl, Xall, n_base, k, metric)
            # the control's answers in the program's place, the rest of
            # the run as it was
            out_control["correct"] = judged_correct(
                dict(checks, **{n: v for n, v in out_control.items()
                                if n in checks}), limits)
        result = {"correct": judged_correct(checks, limits),
                  "attempted": attempted, "failed": failed}
        if trace:
            events = trace_reduce.load(tdir, HOST_SPANS)
            red = trace_reduce.reduce(events, HOST_SPANS)
            shutil.rmtree(tdir, ignore_errors=True)
            rec = RunRecord(
                config=cfg, traffic=mix, window_s=win.t_end - win.t_start,
                n_requests=len(win.requests) - failed,
                spans=spans, n_traced=n_traced, before=before,
                after=after, layout=lay, trace=red,
                device_kind=device["kind"],
                probe_rows=probe_rows(lay, pool[[r.qi for r in win.requests
                                                 if r.error is None]],
                                      int(mix["n_probe"]), metric))
            metrics = {}
            for m in benchfile.metrics_for(bench, "per_layer", workload):
                v = benchfile.reader(m["name"], root)(rec)
                if v is None:
                    continue
                v = v if isinstance(v, dict) else {"value": v}
                metrics[m["name"]] = {"value": float(v.pop("value")),
                                      "unit": m["unit"], **v}
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {
                "device_ops": trace_reduce.top_ops(red["ops"]),
                "idle_gaps": red["gaps"]}
        else:
            e2e = end_to_end(win, setup_s)
            metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                                   "unit": m["unit"]}
                       for m in benchfile.metrics_for(bench, "end_to_end",
                                                      workload)
                       if m["name"] in e2e}
        device["memory_peak_bytes"] = peak
        result["metrics"] = metrics
        result["device"] = device
        result["window"] = {"requests": len(win.requests),
                            "sessions": len(win.sessions),
                            "writer_late_s": win.late_s,
                            "seconds": win.t_end - win.t_start}
        phase("check")
        result["phases_s"] = phases
        result["index"] = {"k": lay["k"], "p_max": lay["p_max"],
                           "paged": lay["paged"], "db_bytes": db_bytes}
        result["readings"] = readings
        if out_control is not None:
            result["control"] = out_control
        result["checks"] = {n: {"value": checks[n], "limit": limits[n]}
                            for n in checks}
        return result
    finally:
        if fd is not None:
            fd.close()
        if eng is not None:
            eng.scheduler.stop_daemon()
            eng.store.close()
        del eng
        gc.collect()
        _rm_db(db)


def _rm_db(db: str):
    for suffix in ("", "-wal", "-shm"):
        with contextlib.suppress(FileNotFoundError):
            os.remove(db + suffix)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(str(e), file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
