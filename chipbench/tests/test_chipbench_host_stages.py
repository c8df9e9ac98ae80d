"""The per-layer readers of the resident query's host stages, on the CPU."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import bench, run  # noqa: E402

HOST_STAGES = {"stage_in_ms": ("stage_in", "engine + planner"),
               "dispatch_ms": ("dispatch", "executor"),
               "device_wait_ms": ("device_wait", "device"),
               "fetch_ms": ("fetch", "result transfer")}


def test_benchmark_json_with_host_stages_validates():
    b = bench.load()
    assert bench.validate(b) == []
    names = {m["name"] for m in b["per_layer"]}
    assert set(HOST_STAGES) <= names


@pytest.mark.parametrize("metric", sorted(HOST_STAGES))
def test_host_stage_readers(metric):
    """Each host-stage reader is its span's ms per span-traced request,
    and None where the program records no such span."""
    span, layer = HOST_STAGES[metric]
    entry = next(m for m in bench.load()["per_layer"] if m["name"] == metric)
    assert entry["source"] == "program_span" and entry["unit"] == "ms"
    assert entry["moves"] == "query_p50_ms"
    assert entry["layer"].startswith(layer)
    assert entry["workloads"] == ["nytimes-int8.solo"]
    read = bench.reader(metric)
    spans = {"plan": 0.04, span: 6.0}
    assert read(run.RunRecord(spans=spans, n_traced=4)) == \
        pytest.approx(1.5)
    assert read(run.RunRecord(spans={"plan": 0.04}, n_traced=4)) is None
    assert read(run.RunRecord(spans=spans, n_traced=0)) is None
