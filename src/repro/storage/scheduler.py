"""Budgeted background maintenance scheduler (the paper's §3.6 made
incremental): drains the monitor's prioritized work queue in bounded work
quanta so no query or upsert ever pays for a rebuild.

Contract:

  * `step()` executes AT MOST ONE work item and touches at most
    `max_rows_per_step` rows -- the on-device interruptibility story: a
    foreground app can interleave queries between steps, and a step's
    wall time is bounded by its row quantum, not the collection size.
    Flush items are divisible (a partial flush moves the first
    `max_rows_per_step` live delta rows and leaves the rest searchable
    in the delta); split/merge/recluster items bound themselves at plan
    time (maintenance.neighborhood admits neighbour partitions only
    while the quantum has room). Items whose seed partition alone
    exceeds the quantum are deferred -- raise `max_rows_per_step` above
    the largest partition (>= split_threshold * target size; the default
    leaves generous headroom) to guarantee progress.
  * The queue is re-polled from the monitor before every step, so each
    step sees the post-previous-step state -- items never go stale.
  * Items that plan to a no-op (degenerate split, emptied partitions)
    are remembered and skipped until the index state changes them.

Durability ordering per step (both engine modes): quantized codes for
the touched rows persist first (byte-stable re-encode under the existing
quantizer), then the row moves + touched-centroid rewrites commit as ONE
SQLite transaction (VectorStore.apply_repair) -- a crash between the two
leaves the pre-repair clustering fully servable (codes are keyed by
asset id and identical under either state), which
tests/test_maintenance.py pins. Repair write I/O therefore scales with
the touched neighbourhood; the full generation swap remains the rebuild
path's mechanism.

Daemon mode (PR 7): `start_daemon()` promotes the scheduler from a
hand-cranked `maintain_step()` to a real background thread that drains
one bounded quantum at a time whenever the serving queue is idle. Every
step runs under the engine's write mutex (`MicroNN.lock`), so daemon
repairs serialize with sessions/upserts while reads keep executing
against consistent snapshots (resident queries hold an immutable index
pytree; paged queries go through the RLock'd PartitionCache with
deferred pinned-frame invalidation, and their SQLite reads ride the
store's WAL snapshot connection). The `idle` callable -- typically the
serving front door's queue-empty probe -- is advisory back-pressure:
the daemon yields to foreground traffic but still makes progress on a
saturated queue every `interval_s * _BUSY_BACKOFF` seconds, so
maintenance can be starved only briefly, never forever. Liveness +
progress surface through MicroNN.stats() (`daemon_alive`,
`daemon_steps`, `scheduler_depth`).
"""
from __future__ import annotations

import dataclasses
import threading
import time
import weakref
from typing import Callable, List, Optional, Tuple

import numpy as np

from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace


def _partition_max_rows(engine) -> int:
    idx = None if engine is None else engine.index
    if idx is None or not idx.k:
        return 0
    return int(np.asarray(idx.counts).max())


@dataclasses.dataclass
class StepReport:
    """What one scheduler step did (surfaced by MicroNN.maintain_step)."""

    action: str               # "flush" | "split" | "merge" | "recluster"
    pids: Tuple[int, ...]     # partitions the step touched
    rows: int                 # rows the step processed (<= quantum)
    bytes_written: int        # durable write I/O of the step


class MaintenanceScheduler:
    """Drains `IndexMonitor.work_queue` against a MicroNN engine, one
    bounded quantum at a time. Owned by the engine (`engine.scheduler`);
    `MicroNN.maintain_step()` / `maintain(until_idle=True)` are the
    public entry points."""

    # idle-queue wait multiplier: with nothing to do the daemon sleeps
    # interval_s * _IDLE_BACKOFF between polls (woken early by kick())
    _IDLE_BACKOFF = 8
    # busy-queue starvation bound: after this many consecutive yields to
    # foreground traffic the daemon takes one quantum anyway
    _BUSY_BACKOFF = 64

    _ACTIONS = ("flush", "split", "merge", "repack", "recluster")

    def __init__(self, engine, max_rows_per_step: int = 4096,
                 metrics=None):
        assert max_rows_per_step >= 1, max_rows_per_step
        self.engine = engine
        self.max_rows_per_step = int(max_rows_per_step)
        # registry telemetry (PR 8): closes the scheduler's observability
        # gap -- it used to expose only a queue-depth probe. The engine
        # passes a sub-scope of its own labels; a standalone scheduler
        # registers under a fresh instance label.
        if metrics is None:
            metrics = obs_metrics.default_registry().scope(
                component="scheduler",
                inst=str(obs_metrics.next_instance()))
        self.metrics = metrics
        self._c_wakeups = metrics.counter("wakeups")
        self._c_idle_probes = metrics.counter("idle_probes")
        self._c_busy_backoffs = metrics.counter("busy_backoffs")
        self._c_steps = metrics.counter("steps")
        self._c_noops = metrics.counter("noops")
        self._c_rows_moved = metrics.counter("rows_moved")
        self._c_bytes_written = metrics.counter("bytes_written")
        self._c_actions = {a: metrics.counter("action_steps", action=a)
                           for a in self._ACTIONS}
        # the build's split pass (maintenance.split_to_bar): the split half
        # of maintenance, done before the clustering is persisted
        self._c_build_splits = metrics.counter("build_splits")
        self._c_build_rows_split = metrics.counter("build_rows_split")
        self._c_build_unsplittable = metrics.counter("build_unsplittable")
        self._g_build_split_ms = metrics.gauge("build_split_ms")
        # largest partition of the live index, read when read (a weak
        # reference: the process registry must not keep an engine alive)
        ref = weakref.ref(engine)
        self._g_partition_max = metrics.gauge(
            "partition_max_rows", fn=lambda: _partition_max_rows(ref()))
        # (action, pids, rows) triples that planned to a no-op within the
        # current run of fruitless polls; cleared whenever any step makes
        # progress, so changed row contents (or a remapped clustering
        # after rebuild/recover) can never be masked by a stale key
        self._skip: set = set()
        # -- daemon state ----------------------------------------------------
        self._daemon: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._idle_fn: Optional[Callable[[], bool]] = None
        self._interval_s = 0.002
        self.daemon_steps = 0          # quanta the daemon has executed
        self.daemon_errors = 0         # exceptions swallowed by the loop
        self.last_daemon_error: Optional[BaseException] = None

    def pending(self) -> List:
        """The monitor's current prioritized queue (fresh every call)."""
        if self.engine.index is None:
            return []
        return self.engine.monitor.work_queue(self.engine.index)

    def queue_depth(self) -> int:
        """Number of pending maintenance work items (stats probe)."""
        return len(self.pending())

    def _emit(self, kind: str, *, action: str = "", pids=(), rows: int = 0,
              bytes_written: int = 0, dur_ms: float = 0.0, error: str = "",
              daemon: bool = False):
        """Append a structured MaintEvent to the engine's trace ring (the
        maintenance event log); no-op without a ring or with tracing
        globally disabled."""
        ring = getattr(self.engine, "traces", None)
        if ring is None or not obs_trace.enabled():
            return
        ring.append(obs_trace.MaintEvent(
            kind=kind, action=action, pids=tuple(int(p) for p in pids),
            rows=int(rows), bytes_written=int(bytes_written),
            dur_ms=dur_ms, error=error, daemon=daemon))

    def step(self, *, daemon: bool = False) -> Optional[StepReport]:
        """Execute the highest-priority actionable work item; None when
        the queue is idle (or nothing actionable fits the quantum)."""
        budget = self.max_rows_per_step
        for item in self.pending():
            key = (item.action, item.pids, item.rows)
            if key in self._skip:
                continue
            if item.action != "flush" and item.rows > budget:
                # indivisible neighbourhood larger than the quantum:
                # defer (see module contract)
                self._skip.add(key)
                continue
            self._emit("planned", action=item.action, pids=item.pids,
                       rows=item.rows, daemon=daemon)
            t0 = time.perf_counter()
            if daemon:
                # count BEFORE the item commits: an observer that polls
                # queue_depth() without the engine lock and sees the
                # post-step index (queue drained) must also see the step
                # counted -- rolled back below on noop/error
                self.daemon_steps += 1
            try:
                report = self.engine._execute_work_item(item, budget)
            except BaseException:
                if daemon:
                    self.daemon_steps -= 1
                raise
            if report is None:
                if daemon:
                    self.daemon_steps -= 1
                self._skip.add(key)
                self._c_noops.inc()
                self._emit("noop", action=item.action, pids=item.pids,
                           daemon=daemon)
                continue
            self._skip.clear()      # progress: stale no-op keys expire
            self._c_steps.inc()
            counter = self._c_actions.get(report.action)
            if counter is not None:
                counter.inc()
            self._c_rows_moved.inc(report.rows)
            self._c_bytes_written.inc(report.bytes_written)
            self._emit("step", action=report.action, pids=report.pids,
                       rows=report.rows, bytes_written=report.bytes_written,
                       dur_ms=(time.perf_counter() - t0) * 1e3,
                       daemon=daemon)
            return report
        return None

    def record_build(self, split):
        """Count what a build's split pass did (a BuildSplitStats)."""
        self._c_build_splits.inc(split.splits)
        self._c_build_rows_split.inc(split.rows_split)
        self._c_build_unsplittable.inc(split.unsplittable)
        self._g_build_split_ms.set(split.ms)

    def stats(self) -> dict:
        """The scheduler's registry-backed telemetry (surfaced through
        MicroNN.stats()['scheduler']), the build's split-pass counters
        and the largest partition's rows among them."""
        return {"wakeups": self._c_wakeups.value,
                "idle_probes": self._c_idle_probes.value,
                "busy_backoffs": self._c_busy_backoffs.value,
                "steps": self._c_steps.value,
                "noops": self._c_noops.value,
                "rows_moved": self._c_rows_moved.value,
                "bytes_written": self._c_bytes_written.value,
                "daemon_errors": self.daemon_errors,
                "actions": {a: c.value
                            for a, c in self._c_actions.items()},
                "build_splits": self._c_build_splits.value,
                "build_rows_split": self._c_build_rows_split.value,
                "build_unsplittable": self._c_build_unsplittable.value,
                "build_split_ms": self._g_build_split_ms.value,
                "partition_max_rows": int(self._g_partition_max.value)}

    def drain(self, max_steps: Optional[int] = None) -> List[StepReport]:
        """Run steps until the queue is idle (maintain(until_idle=True)).
        `max_steps` is a runaway guard; the default scales with k."""
        out: List[StepReport] = []
        k = getattr(self.engine.index, "k", 1) if self.engine.index else 1
        limit = max_steps if max_steps is not None else 64 + 8 * k
        for _ in range(limit):
            r = self.step()
            if r is None:
                break
            out.append(r)
        return out

    # -- daemon thread (PR 7) -------------------------------------------------
    @property
    def daemon_alive(self) -> bool:
        return self._daemon is not None and self._daemon.is_alive()

    def start_daemon(self, idle: Optional[Callable[[], bool]] = None,
                     interval_s: float = 0.002):
        """Promote the scheduler to a background daemon thread.

        `idle` is an advisory back-pressure probe (return False while
        foreground requests are queued -- the serving front door passes
        its queue-empty check); `interval_s` is the poll cadence. Each
        quantum runs under `engine.lock`, so daemon repairs serialize
        with every other writer. Idempotent while alive."""
        if self.daemon_alive:
            return
        self._idle_fn = idle
        self._interval_s = float(interval_s)
        self._stop.clear()
        self._wake.clear()
        self._daemon = threading.Thread(
            target=self._daemon_loop, name="micronn-maintenance",
            daemon=True)
        self._daemon.start()

    def stop_daemon(self, timeout: Optional[float] = 10.0):
        """Stop the daemon and join it (no-op when not running). The
        in-flight quantum, if any, completes -- a step is never killed
        halfway through its durability ordering."""
        if self._daemon is None:
            return
        self._stop.set()
        self._wake.set()
        self._daemon.join(timeout)
        assert not self._daemon.is_alive(), \
            "maintenance daemon failed to stop within timeout"
        self._daemon = None

    def kick(self):
        """Wake the daemon early (a writer just enqueued likely work, or
        the serving queue went idle)."""
        self._wake.set()

    def _daemon_loop(self):
        """while alive: when the serving queue is idle (or foreground
        pressure has persisted past the starvation bound), take the
        engine write mutex and drain ONE bounded quantum; back off when
        the work queue is empty. Exceptions are recorded and swallowed
        -- a failed repair plan must not kill maintenance forever."""
        yielded = 0
        while not self._stop.is_set():
            self._c_wakeups.inc()
            if self.engine.index is None:
                self._wake.wait(self._interval_s * self._IDLE_BACKOFF)
                self._wake.clear()
                continue
            busy = self._idle_fn is not None and not self._idle_fn()
            if busy and yielded < self._BUSY_BACKOFF:
                yielded += 1
                self._c_busy_backoffs.inc()
                self._wake.wait(self._interval_s)
                self._wake.clear()
                continue
            yielded = 0
            report = None
            try:
                with self.engine.lock:
                    if not self._stop.is_set():
                        # step(daemon=True) counts daemon_steps itself,
                        # before the item's index swap becomes visible
                        report = self.step(daemon=True)
            except BaseException as e:  # noqa: BLE001 -- daemon must live
                self.daemon_errors += 1
                self.last_daemon_error = e
                self._emit("daemon_error", error=repr(e), daemon=True)
            if report is None:
                # queue idle (or errored): poll again after a beat,
                # woken early by kick()
                self._c_idle_probes.inc()
                self._wake.wait(self._interval_s * self._IDLE_BACKOFF)
                self._wake.clear()
