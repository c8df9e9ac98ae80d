"""Each cell's generator and readers end to end at a tiny scale on the
CPU, and cells added by files and entries alone."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from chipbench import bench, run  # noqa: E402

# rows: 2,000 SIFT-shaped (20 partitions), 1,450 NYTimes-shaped (14)
TINY = {"sift1m-paged10mb": 0.002, "nytimes-int8": 0.005}
SEED = 2 ** 31 + 77
LIMITS = {"miss_rate": 0.1, "score_gap": 1e-5, "bad_ids": 0, "unanswered": 0,
          "retraces": 0}
# a reader and an open-loop writer committing 16 upserts and 16 deletes
# every 100 ms (a cell this benchmark does not hold yet)
RW_MIX = {"via": "frontdoor", "frontdoor": {}, "callers": 1, "q_rows": 1,
          "k": 10, "n_probe": 8, "query_pool": 4096, "warmup_rounds": 4,
          "sample": 64, "span_sample": 16, "writer": {"period_s": 0.1, "upserts": 16,
                                   "deletes": 16, "live_rows": 160}}
WRITE_P95 = {"name": "write_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.25, "source": "host_clock"}


def tiny(workload, trace, tmp_path, root=ROOT, **kw):
    cfg = bench.cell(bench.load(root), workload, root)["config"]
    return run.run_cell(workload, SEED, 1.0, trace, root=root,
                        require_tpu=False, scale=TINY[cfg["name"]],
                        work=str(tmp_path), **kw)


PAGED = {"name": "sift1m-paged10mb", "source": "a test",
         "file": "chipbench/configs/sift1m-paged10mb.json", "reduced": [],
         "why": "the 10 MB paged configuration, which has no cell yet"}
PAGER_METRICS = [
    {"name": n, "unit": u, "better": b, "source": s, "layer": "pager",
     "moves": "query_p50_ms"}
    for n, u, b, s in (("pager_hit_rate", "%", "higher", "program_counter"),
                       ("pager_fault_ms", "ms", "lower", "program_span"),
                       ("rerank_fetch_ms", "ms", "lower", "program_span"))]


FD_METRICS = [
    {"name": "fd_batch_occupancy", "unit": "rows/call", "better": "higher",
     "source": "program_counter", "layer": "front door", "moves": "qps"},
    {"name": "device_idle_share.fd", "unit": "%", "better": "lower",
     "source": "device_trace", "layer": "device", "moves": "qps"}]


def checkout_with(tmp_path, name, traffic, mix, limits, end_to_end=(),
                  config="nytimes-int8", per_layer=()):
    """A copy of the benchmark with one more cell, `config` under the
    mix `traffic`, made by adding files and entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = bench.load()
    if config not in {c["name"] for c in b["configs"]}:
        b["configs"].append(PAGED)
    b["workloads"].append({"name": name, "config": config,
                           "traffic": traffic, "chips": 1,
                           "why": "added by a test"})
    for m in end_to_end:
        b["end_to_end"].append(dict(m, workloads=[name]))
    for m in per_layer:
        b["per_layer"].append(dict(m, workloads=[name]))
    fill = next(m for m in b["per_layer"] if m["name"] == "partition_fill")
    fill["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    if mix is not None:
        (root / "chipbench" / "traffic" / f"{traffic}.json").write_text(
            json.dumps(mix))
    (root / "chipbench" / "limits" / f"{name}.json").write_text(
        json.dumps(limits))
    assert bench.validate(bench.load(str(root)), str(root)) == []
    return str(root)


@pytest.mark.parametrize("workload", [
    w["name"] for w in bench.load()["workloads"]])
def test_cell_end_to_end_traced(workload, tmp_path):
    r = tiny(workload, True, tmp_path)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    assert r["device"]["window_s"] > 0
    want = {m["name"] for m in bench.metrics_for(bench.load(), "per_layer",
                                                 workload)
            if m["source"] != "device_trace"}
    assert set(r["metrics"]) == want
    assert all(set(v) >= {"value", "unit"} for v in r["metrics"].values())
    assert not os.listdir(tmp_path / "trace")


def test_cell_end_to_end_untraced(tmp_path):
    r = tiny("nytimes-int8.solo", False, tmp_path)
    assert r["correct"], r["checks"]
    want = {m["name"] for m in bench.metrics_for(bench.load(), "end_to_end",
                                                 "nytimes-int8.solo")}
    assert set(r["metrics"]) == want
    assert r["metrics"]["setup_s"]["value"] > 0
    assert r["checks"]["miss_rate"]["limit"] == LIMITS["miss_rate"]
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".db")]


def fd32_checkout(tmp_path):
    """The 32-caller front-door mix (`traffic/fd32.json`), registered as a
    cell with its front-door readers."""
    return checkout_with(tmp_path, "nytimes-int8.fd32", "fd32", None, LIMITS,
                         per_layer=FD_METRICS)


def test_the_front_door_mix_runs_traced(tmp_path):
    r = tiny("nytimes-int8.fd32", True, tmp_path / "work",
             root=fd32_checkout(tmp_path))
    assert r["correct"], r["checks"]
    assert r["metrics"]["fd_batch_occupancy"]["value"] >= 1


def test_a_cell_added_by_files_alone(tmp_path):
    """A new traffic mix, its cell's limits and an entry in
    BENCHMARK.json are enough: no harness file changes."""
    root = checkout_with(
        tmp_path, "nytimes-int8.dummy", "dummy",
        {"via": "engine", "callers": 2, "q_rows": 4, "k": 5, "n_probe": 4,
         "query_pool": 512, "warmup_rounds": 2, "sample": 16,
         "span_sample": 4}, LIMITS)
    r = tiny("nytimes-int8.dummy", False, tmp_path / "work", root=root)
    assert r["correct"], r["checks"]
    assert r["window"]["requests"] > 0
    assert set(r["metrics"]) == {"query_p50_ms", "query_p95_ms", "qps",
                                 "setup_s"}


def test_a_read_write_cell_added_by_files_alone(tmp_path):
    """The writer: every acknowledged upsert is read back and every
    acknowledged delete stays away, while a reader is served."""
    root = checkout_with(tmp_path, "nytimes-int8.rw", "rw", RW_MIX,
                         dict(LIMITS, writes_lost=0), [WRITE_P95])
    r = tiny("nytimes-int8.rw", False, tmp_path / "work", root=root)
    assert r["correct"], r["checks"]
    assert r["window"]["sessions"] >= 5
    assert r["checks"]["writes_lost"]["value"] == 0
    assert r["metrics"]["write_p95_ms"]["value"] > 0


def test_the_paged_configuration_runs_traced(tmp_path):
    """The 10 MB paged configuration under the solo mix, with the pager
    and SQLite readers: ready for the cell a later change adds."""
    root = checkout_with(tmp_path, "sift1m-paged10mb.solo", "solo", None,
                         LIMITS, config="sift1m-paged10mb",
                         per_layer=PAGER_METRICS)
    r = tiny("sift1m-paged10mb.solo", True, tmp_path / "work", root=root)
    assert r["correct"], r["checks"]
    assert r["index"]["paged"]
    assert {m["name"] for m in PAGER_METRICS} <= set(r["metrics"])
