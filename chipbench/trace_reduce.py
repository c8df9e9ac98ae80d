"""Reduce a JAX profiler trace to the benchmark's device numbers.

`load(dir)` reads the newest `.xplane.pb` under a `jax.profiler` trace
directory into plain events; `reduce(events)` turns them into:

  window_s     length of the harness's `window` annotation on the host
  busy_s       union of the device-op intervals inside the window, per
               device plane, averaged over the devices
  idle_share   1 - busy_s / window_s
  ops          device time by op name inside the window, in seconds
  gaps         the longest idle intervals of the device inside the
               window, each named by the innermost harness annotation
               (`query`, `submit`, `session`, ...) that covers its
               midpoint on the host, or "other"

Device ops are the events on the "XLA Ops" line of each `/device:` plane;
host annotations are `jax.profiler.TraceAnnotation` spans on the host
plane. Both are on the profiler's own clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:"
DEVICE_OP_LINES = ("XLA Ops",)
WINDOW = "window"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def trace_file(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(trace_dir: str, host_names: Iterable[str]) -> List[Event]:
    """Device ops of every device plane, and the host events whose name
    is in `host_names` (plus the window), from the newest trace."""
    from jax.profiler import ProfileData
    keep = set(host_names) | {WINDOW}
    pd = ProfileData.from_file(trace_file(trace_dir))
    out = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if device and line.name not in DEVICE_OP_LINES:
                continue
            for ev in line.events:
                if device or ev.name in keep:
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns)))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s: float, e: float, t0: float, t1: float) -> Optional[tuple]:
    s, e = max(s, t0), min(e, t1)
    return (s, e) if e > s else None


def reduce(events: Sequence[Event], host_names: Iterable[str],
           n_gaps: int = 10) -> Dict:
    host_names = set(host_names)
    wins = [e for e in events if e.name == WINDOW
            and not e.plane.startswith(DEVICE_PLANE_PREFIX)]
    if not wins:
        raise ValueError("trace holds no `window` annotation")
    t0, t1 = wins[0].start_ns, wins[0].end_ns
    dev = [e for e in events if e.plane.startswith(DEVICE_PLANE_PREFIX)]
    planes = sorted({e.plane for e in dev})
    ops: Dict[str, float] = {}
    busy_ns = []
    merged_all = []
    for p in planes:
        iv = []
        for e in dev:
            if e.plane != p:
                continue
            c = _clip(e.start_ns, e.end_ns, t0, t1)
            if c is None:
                continue
            iv.append(c)
            ops[e.name] = ops.get(e.name, 0.0) + (c[1] - c[0]) * 1e-9
        merged = union(iv)
        busy_ns.append(sum(e - s for s, e in merged))
        merged_all.extend(merged)
    window_s = (t1 - t0) * 1e-9
    busy_s = (sum(busy_ns) / len(busy_ns)) * 1e-9 if busy_ns else 0.0
    # idle gaps of the devices taken together (a gap is time in which
    # no device ran an op)
    gaps = []
    cur = t0
    for s, e in union(merged_all) + [[t1, t1]]:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    spans = [e for e in events if e.name in host_names
             and not e.plane.startswith(DEVICE_PLANE_PREFIX)]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n_gaps]:
        mid = 0.5 * (s + e)
        cover = [h for h in spans if h.start_ns <= mid < h.end_ns]
        label = min(cover, key=lambda h: h.dur_ns).name if cover \
            else "other"
        named.append([label, (e - s) * 1e-9])
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "devices": len(planes), "ops": ops, "gaps": named}


def top_ops(ops: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])
            [:n]]


def kernel_seconds(ops: Dict[str, float], matches) -> float:
    """Device seconds of the ops for which `matches(name)` holds."""
    return sum(v for k, v in ops.items() if matches(k))


def idle_percent(red: dict) -> Optional[float]:
    """The reduced trace's idle share in %; None where no device op ran."""
    share = red["idle_share"]
    return None if share is None or not red["devices"] else 100.0 * share
