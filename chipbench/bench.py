"""`BENCHMARK.json` and the files it names, found by name.

Everything that belongs to one cell lives in files of its own, so a
later change adds a configuration, a traffic mix, a cell's limits or a
per-layer metric by adding files and entries, without editing these.
`validate` checks the file against the benchmark contract's shape rules.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES_E2E = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(root: str, *parts: str) -> dict:
    with open(os.path.join(root, "chipbench", *parts)) as f:
        return json.load(f)


def cell(bench: dict, name: str, root: str = ROOT) -> dict:
    """The cell's entry with its configuration, traffic mix and limits."""
    w = next((w for w in bench["workloads"] if w["name"] == name), None)
    if w is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    c = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, c["file"])) as f:
        config = json.load(f)
    return {"workload": w, "config": config,
            "traffic": _json(root, "traffic", w["traffic"] + ".json"),
            "limits": _json(root, "limits", name + ".json")}


def metrics_for(bench: dict, kind: str, workload: str) -> List[dict]:
    """The end-to-end or per-layer metrics a cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def reader(metric: str, root: str = ROOT) -> Callable:
    """`read(run)` of `layers/<metric>.py`."""
    path = os.path.join(root, "chipbench", "layers", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _line(s) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def validate(bench: dict, root: str = ROOT) -> List[str]:
    """Shape errors of a BENCHMARK.json (empty when it is sound)."""
    err: List[str] = []

    def need(cond, msg):
        if not cond:
            err.append(msg)

    need(set(bench) == KEYS["top"], f"top-level keys {sorted(bench)}")
    cmd = bench.get("command", [])
    need(isinstance(cmd, list) and 1 <= len(cmd) <= 32
         and all(_line(c) for c in cmd), "command")
    paths = bench.get("paths", [])
    need(1 <= len(paths) <= 16 and all(
        PATH.match(p) and not p.startswith("/") and ".." not in p
        for p in paths), "paths")
    rs = bench.get("run_seconds")
    need(isinstance(rs, int) and 1 <= rs <= 51, "run_seconds")
    seen: Dict[str, set] = {"configs": set(), "workloads": set(),
                            "metric": set()}
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        group = "metric" if kind in ("end_to_end", "per_layer") else kind
        for e in bench.get(kind, []):
            n = e.get("name", "")
            need(bool(NAME.match(n)), f"{kind} name {n!r}")
            need(n not in seen[group], f"duplicate {group} {n!r}")
            seen[group].add(n)
    need(1 <= len(bench.get("configs", [])) <= 24, "configs count")
    files = set()
    for c in bench.get("configs", []):
        need(set(c) == KEYS["config"], f"config keys {sorted(c)}")
        need(_line(c.get("source")) and _line(c.get("why")),
             f"config {c.get('name')} source/why")
        f = c.get("file", "")
        need(any(f.startswith(p.rstrip("/") + "/") for p in paths),
             f"config file {f} outside paths")
        need(os.path.exists(os.path.join(root, f)), f"missing {f}")
        need(f not in files, f"config file {f} shared")
        files.add(f)
        red = c.get("reduced", [])
        need(len(red) <= 16 and all(NAME.match(r) for r in red),
             f"reduced of {c.get('name')}")
    cfgs = {c["name"] for c in bench.get("configs", [])}
    wls = bench.get("workloads", [])
    need(1 <= len(wls) <= 24, "workloads count")
    pairs = set()
    for w in wls:
        need(set(w) == KEYS["workload"], f"workload keys {sorted(w)}")
        need(w.get("config") in cfgs, f"workload {w.get('name')} config")
        need(NAME.match(w.get("traffic", "")) is not None,
             f"traffic name {w.get('traffic')!r}")
        need(w.get("chips") in (1, 4), f"chips of {w.get('name')}")
        need(_line(w.get("why")), f"why of {w.get('name')}")
        pair = (w.get("config"), w.get("traffic"))
        need(pair not in pairs, f"config/traffic pair {pair} twice")
        pairs.add(pair)
        for part in (("traffic", w.get("traffic", "") + ".json"),
                     ("limits", w.get("name", "") + ".json")):
            need(os.path.exists(os.path.join(root, "chipbench", *part)),
                 f"missing chipbench/{'/'.join(part)}")
    need(sum(w.get("chips") == 4 for w in wls) <= max(1, len(wls) // 2),
         "too many four-chip cells")
    used = {w.get("config") for w in wls}
    need(cfgs <= used, f"configurations no cell uses: {cfgs - used}")
    wnames = {w.get("name") for w in wls}
    e2e = bench.get("end_to_end", [])
    need(1 <= len(e2e) <= 16, "end_to_end count")
    need(any(m.get("name") == "setup_s" for m in e2e), "setup_s missing")
    for m in e2e:
        need(set(m) - {"workloads"} == KEYS["end_to_end"],
             f"end_to_end keys {sorted(m)}")
        need(m.get("source") in SOURCES_E2E, f"source of {m.get('name')}")
        b = m.get("bound")
        need(isinstance(b, (int, float)) and 0.01 <= b <= 0.25,
             f"bound of {m.get('name')}")
    pl = bench.get("per_layer", [])
    need(1 <= len(pl) <= 128, "per_layer count")
    for m in pl:
        need(set(m) - {"workloads"} == KEYS["per_layer"],
             f"per_layer keys {sorted(m)}")
        need(m.get("source") in SOURCES, f"source of {m.get('name')}")
        need(_line(m.get("layer")), f"layer of {m.get('name')}")
        need(os.path.exists(os.path.join(
            root, "chipbench", "layers", m.get("name", "") + ".py")),
            f"missing reader of {m.get('name')}")
        mv = next((e for e in e2e if e.get("name") == m.get("moves")), None)
        need(mv is not None, f"{m.get('name')} moves {m.get('moves')}")
        for w in m.get("workloads", sorted(wnames)):
            need(w in wnames, f"{m.get('name')} names unknown cell {w}")
            if mv is not None:
                need(w in mv.get("workloads", [w]),
                     f"{w} does not report {m.get('moves')}")
    for m in e2e + pl:
        need(bool(UNIT.match(m.get("unit", ""))), f"unit of {m.get('name')}")
        need(m.get("better") in ("lower", "higher"),
             f"better of {m.get('name')}")
        for w in m.get("workloads", []):
            need(w in wnames, f"{m.get('name')} names unknown cell {w}")
    for w in wnames:
        rep = [m["name"] for m in e2e
               if w in m.get("workloads", [w])]
        need("setup_s" in rep and len(rep) >= 2,
             f"{w} reports too few end-to-end metrics")
        need(any(w in m.get("workloads", [w]) for m in pl),
             f"{w} reports no per-layer metric")
    need(len(json.dumps(bench)) <= 64 * 1024, "BENCHMARK.json over 64 KiB")
    return err
