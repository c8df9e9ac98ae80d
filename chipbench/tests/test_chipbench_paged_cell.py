"""The 10 MB paged SIFT-1M cell and the index-build readers, on the CPU:
the entries validate, each reader reads a value from a tiny paged run
and a tiny resident one (and nothing from a program without the build's
counters), and a fault in the paged path turns `correct` false."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from chipbench import bench, run  # noqa: E402
from repro.core.types import FRAME_ROWS_MAX  # noqa: E402
from chipbench.tests.test_chipbench_cells import LIMITS, tiny  # noqa: E402

CELL = "sift1m-paged10mb.solo"
BOTH = ["nytimes-int8.solo", CELL]
READERS = {"partition_max_rows": ("partition_max_rows", "query_p50_ms",
                                   "rows", "program_counter"),
           "build_split_rows": ("build_rows_split", "setup_s", "rows",
                                "program_counter"),
           "build_split_ms": ("build_split_ms", "setup_s", "ms",
                              "program_span")}
# accepted metrics of the resident cell that read the paged cell too
SHARED = {"partition_fill", "sq_scan_ms", "sq_scan_roofline",
          "device_idle_share.solo"}


def test_benchmark_json_with_the_paged_cell_validates():
    b = bench.load()
    assert bench.validate(b) == []
    cfg = next(c for c in b["configs"] if c["name"] == "sift1m-paged10mb")
    assert cfg["file"] == "chipbench/configs/sift1m-paged10mb.json"
    assert cfg["reduced"] == []
    w = next(w for w in b["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == \
        ("sift1m-paged10mb", "solo", 1)
    c = bench.cell(b, CELL)
    assert c["limits"] == LIMITS
    assert c["config"]["engine"]["memory_budget_mb"] == 10
    assert {m["name"] for m in bench.metrics_for(b, "per_layer", CELL)} \
        == set(READERS) | SHARED
    for name, (_, moves, unit, source) in READERS.items():
        m = next(m for m in b["per_layer"] if m["name"] == name)
        assert (m["unit"], m["better"], m["source"], m["moves"]) == \
            (unit, "lower", source, moves)
        assert m["layer"].startswith("index build")
        assert m["workloads"] == BOTH
        assert callable(bench.reader(name))


@pytest.mark.parametrize("metric", sorted(READERS))
def test_readers_need_the_build_counters(metric):
    """A program without the counters (the parent of this cell) gives
    no reading, and no error."""
    key = READERS[metric][0]
    read = bench.reader(metric)
    assert read(run.RunRecord(before={"scheduler": {key: 37}})) == 37.0
    assert read(run.RunRecord(before={"scheduler": {"steps": 0}})) is None
    assert read(run.RunRecord(before={})) is None


@pytest.mark.parametrize("workload", BOTH)
def test_readers_read_a_tiny_run(workload, tmp_path):
    r = tiny(workload, True, tmp_path)
    assert r["correct"], r["checks"]
    assert r["index"]["paged"] == (workload == CELL)
    got = {n: r["metrics"][n]["value"] for n in READERS}
    assert 0 < got["partition_max_rows"] <= FRAME_ROWS_MAX
    assert r["index"]["p_max"] >= got["partition_max_rows"]
    assert got["build_split_rows"] >= 0 and got["build_split_ms"] > 0


def test_paged_rerank_candidates_moved_turns_correct_false(tmp_path,
                                                           monkeypatch):
    """The paged scan hands the SQLite rerank the wrong rows: each
    candidate moved to the row at the same place one partition over. The
    rerank scores those rows exactly, so only the recall against the
    reference sees it."""
    from repro.core import executor
    rerank = executor._rerank_from_store
    moved = {}

    def shifted(store, q, cand_ids, k_out, metric):
        if not moved:
            ids, parts, _ = store.all_rows()
            live = np.nonzero(parts >= 0)[0]
            ids, parts = ids[live], parts[live]
            order = np.lexsort((ids, parts))
            ids, parts = ids[order], parts[order]
            starts = np.searchsorted(parts, parts)
            blocks = np.unique(parts)
            nxt = np.searchsorted(blocks, parts) + 1
            to = blocks[nxt % len(blocks)]
            lo = np.searchsorted(parts, to)
            size = np.searchsorted(parts, to, side="right") - lo
            moved["map"] = dict(zip(ids.tolist(), ids[
                lo + (np.arange(len(ids)) - starts) % size].tolist()))
        c = np.asarray(cand_ids)
        c = np.vectorize(lambda i: moved["map"].get(int(i), int(i)))(c)
        return rerank(store, q, c.astype(np.int32), k_out, metric)

    monkeypatch.setattr(executor, "_rerank_from_store", shifted)
    r = tiny(CELL, False, tmp_path)
    failed = [n for n, c in r["checks"].items() if c["value"] > c["limit"]]
    assert not r["correct"] and "miss_rate" in failed, r["checks"]
    assert "score_gap" not in failed, r["checks"]
