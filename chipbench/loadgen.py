"""The one load generator. A traffic mix is a data file of its
parameters (`traffic/<name>.json`); this module reads no other input.

Readers are closed-loop callers: each sends its next request when the
previous one has answered. With `"via": "engine"` a caller calls
`MicroNN.query`; with `"via": "frontdoor"` it calls
`FrontDoor.submit(...).result()`. Every request carries `q_rows` query
rows drawn in order from a pool made from the seed, so no query repeats
before the pool is spent. A request's latency runs from its send to the
moment its ids and scores are on the host. No request of the window
records the engine's spans; `span_sample` sends `span_sample` more,
one at a time, that do.

An optional writer is open-loop: every `period_s` a `WriteSession` is
due, with `upserts` new rows and the deletion of the `deletes` oldest
rows this run inserted, which keeps the inserted rows at `live_rows`
once set-up has inserted them. A session's latency runs from when it was
due to when its commit returned (acknowledged = committed).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    qi: int                 # first row of the request in the query pool
    t0: float
    t1: float
    ids: Optional[np.ndarray] = None      # [q_rows, k]
    scores: Optional[np.ndarray] = None
    error: Optional[str] = None


@dataclasses.dataclass
class Session:
    due: float
    t0: float
    t1: float
    upserted: np.ndarray
    deleted: np.ndarray


@dataclasses.dataclass
class Window:
    t_start: float
    t_end: float                      # last answer or commit
    requests: List[Request]
    sessions: List[Session]
    late_s: float                     # how late the writer ran, at most


class Writer:
    """The writer's rows and their order: ids n_base, n_base+1, ... in
    insertion order, vectors from `data.new_rows`."""

    def __init__(self, mix: dict, vecs: np.ndarray, n_base: int,
                 n_attr: int):
        w = mix["writer"]
        self.period = float(w["period_s"])
        self.ups = int(w["upserts"])
        self.dels = int(w["deletes"])
        self.live_rows = int(w["live_rows"])
        self.vecs = vecs
        self.n_base = n_base
        self.n_attr = n_attr
        self.next = 0
        self.live: deque = deque()

    def _session(self, eng, n_up: int, n_del: int) -> tuple:
        if self.next + n_up > len(self.vecs):
            raise RuntimeError("writer ran out of pre-made rows")
        up = np.arange(self.next, self.next + n_up)
        self.next += n_up
        gone = np.array([self.live.popleft() for _ in range(n_del)],
                        np.int64)
        with eng.session() as s:
            s.upsert(up + self.n_base, self.vecs[up],
                     np.zeros((n_up, self.n_attr), np.float32))
            if len(gone):
                s.delete(gone)
        self.live.extend((up + self.n_base).tolist())
        return up + self.n_base, gone

    def fill(self, eng):
        """Set-up: insert the first `live_rows` rows."""
        while len(self.live) < self.live_rows:
            self._session(eng, min(self.ups, self.live_rows - len(self.live)),
                          0)

    def run(self, eng, t_start: float, deadline: float, out: List[Session],
            annotate):
        i = 0
        while True:
            due = t_start + i * self.period
            if due >= deadline:
                return
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            with annotate("session"):
                t0 = time.perf_counter()
                up, gone = self._session(eng, self.ups, self.dels)
                t1 = time.perf_counter()
            out.append(Session(due, t0, t1, up, gone))
            i += 1


def _spec(mix: dict):
    from repro.core.query import Q
    return Q.knn(k=int(mix["k"]), n_probe=int(mix["n_probe"]))


def open_frontdoor(eng, mix: dict):
    if mix["via"] != "frontdoor":
        return None
    from repro.serving.frontdoor import FrontDoor
    return FrontDoor(eng, **mix.get("frontdoor", {}))


def _ask(eng, fd, q: np.ndarray, spec, traced: bool = False):
    if fd is None:
        rs = eng.query(q, spec, trace=traced)
    else:
        rs = fd.submit(q, spec, trace=traced).result(timeout=120)
    ids, scores = rs.to_numpy()
    return rs, ids, scores


def warmup(eng, fd, mix: dict, pool: np.ndarray):
    """Compile and warm the shapes this mix sends and no others: each
    query-count bucket a caller or the front door's coalescing can form,
    then every caller at once for `warmup_rounds` requests each."""
    spec = _spec(mix)
    rows = int(mix["q_rows"])
    most = rows * int(mix["callers"]) if fd is not None else rows
    b = 1
    while True:
        n = min(b, most)
        eng.query(pool[:n], spec).to_numpy()
        if n == most:
            break
        b *= 2
    qi = iter(range(0, len(pool) - rows + 1, rows))
    lock = threading.Lock()

    def caller():
        for _ in range(int(mix["warmup_rounds"])):
            with lock:
                i = next(qi)
            _ask(eng, fd, pool[i:i + rows], spec)

    _callers(int(mix["callers"]), caller)


def _callers(n: int, fn):
    if n == 1:
        fn()
        return
    th = [threading.Thread(target=fn) for _ in range(n)]
    for t in th:
        t.start()
    for t in th:
        t.join()


def window(eng, fd, mix: dict, pool: np.ndarray, seconds: float,
           annotate, writer: Optional[Writer] = None) -> Window:
    """Drive the mix for `seconds`; every request sent before the close
    is answered (or fails) before this returns."""
    spec = _spec(mix)
    rows = int(mix["q_rows"])
    label = "query" if fd is None else "submit"
    reqs: List[Request] = []
    sessions: List[Session] = []
    counter = {"next": 0}
    lock = threading.Lock()
    t_start = time.perf_counter()
    deadline = t_start + seconds

    def caller():
        mine = []
        while time.perf_counter() < deadline:
            with lock:
                i = counter["next"]
                counter["next"] = (i + rows) % (len(pool) - rows + 1)
            r = Request(qi=i, t0=0.0, t1=0.0)
            with annotate(label):
                r.t0 = time.perf_counter()
                try:
                    _, r.ids, r.scores = _ask(eng, fd, pool[i:i + rows],
                                              spec)
                except Exception as e:  # noqa: BLE001 -- counted as failed
                    r.error = f"{type(e).__name__}: {e}"
                r.t1 = time.perf_counter()
            mine.append(r)
        with lock:
            reqs.extend(mine)

    wt = None
    if writer is not None:
        wt = threading.Thread(target=writer.run,
                              args=(eng, t_start, deadline, sessions,
                                    annotate))
        wt.start()
    with annotate("window"):
        _callers(int(mix["callers"]), caller)
        if wt is not None:
            wt.join()
    ends = [r.t1 for r in reqs] + [s.t1 for s in sessions]
    late = max((s.t0 - s.due for s in sessions), default=0.0)
    return Window(t_start=t_start, t_end=max(ends, default=deadline),
                  requests=reqs, sessions=sessions, late_s=late)


def span_sample(eng, fd, mix: dict, pool: np.ndarray) -> tuple:
    """(summed span ms by name, requests) over `span_sample` requests
    sent one at a time with the engine's spans on, after the window:
    a traced request does work of its own (the resident probe span
    re-runs the probe), so none of the window's requests is traced."""
    spec = _spec(mix)
    rows = int(mix["q_rows"])
    spans: Dict[str, float] = {}
    n = 0
    for j in range(int(mix["span_sample"])):
        i = (j * rows) % (len(pool) - rows + 1)
        rs, _, _ = _ask(eng, fd, pool[i:i + rows], spec, traced=True)
        if rs.trace is None:
            continue
        n += 1
        for s in rs.trace.spans.values():
            spans[s.name] = spans.get(s.name, 0.0) + s.dur_ms
    return spans, n
