"""The benchmark's file, its generator, its kernel counts, its trace
reduction and its command line, on the CPU."""
import copy
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import bench, data, peaks, trace_reduce  # noqa: E402
from chipbench.kernels import ivf_scan_topk, sq_scan_topk  # noqa: E402


def test_benchmark_json_validates():
    assert bench.validate(bench.load()) == []


@pytest.mark.parametrize("edit, error", [
    (lambda b: b["end_to_end"][0].update(unit="queries per second"),
     "unit of"),
    (lambda b: b["end_to_end"][0].update(unit="a" * 17), "unit of"),
    (lambda b: b["workloads"][0].update(name="bad name"), "workloads name"),
    (lambda b: b["workloads"][0].update(traffic="no_such_mix"),
     "missing chipbench/traffic/no_such_mix.json"),
    (lambda b: b["per_layer"][0].update(moves="no_such_metric"),
     "moves no_such_metric"),
    (lambda b: b["end_to_end"][0].update(workloads=[]),
     "nytimes-int8.solo does not report query_p50_ms"),
    (lambda b: b["per_layer"][0].update(workloads=["no_such_cell"]),
     "names unknown cell no_such_cell"),
    (lambda b: b["end_to_end"][0].update(bound=0.5), "bound of"),
    (lambda b: b["configs"][0].update(file="configs/x.json"),
     "outside paths"),
])
def test_benchmark_json_refuses(edit, error):
    b = copy.deepcopy(bench.load())
    edit(b)
    errs = bench.validate(b)
    assert any(error in e for e in errs), errs


def test_every_cell_finds_its_files():
    b = bench.load()
    for w in b["workloads"]:
        c = bench.cell(b, w["name"])
        assert c["config"]["name"] == w["config"]
        assert set(c["limits"]) >= {"miss_rate", "score_gap", "bad_ids",
                                    "unanswered"}
        for m in bench.metrics_for(b, "per_layer", w["name"]):
            assert callable(bench.reader(m["name"]))


def test_generator_is_pinned():
    d = data.make("nytimes", scale=0.01, seed=7, n_queries=64)
    blob = (d.X.tobytes() + d.Q.tobytes()
            + data.attributes(100, 7).tobytes()
            + data.new_rows(d.X, 16, 7).tobytes())
    assert d.X.shape == (2900, 256) and d.Q.shape == (64, 256)
    assert hashlib.sha256(blob).hexdigest() == \
        "5bb569dc4dddac99d011603dd3a6d6af41b07cd2abd716862f8a5c8451332cda"


def test_kernel_counts_by_hand():
    # d=4; two queries whose own probe sets hold 6 and 4 valid rows, a
    # union of 10 rows, 3 candidates out
    ops, nbytes = sq_scan_topk.cost(4, 10, [6, 4], 3, with_norms=True)
    assert ops == 2 * 2 * 4 * 10            # two int8 terms per MAC
    assert nbytes == 10 * (4 + 4 + 4) + 2 * (8 + 12) + 2 * 3 * 8
    ops, nbytes = sq_scan_topk.cost(4, 10, [6, 4], 3, with_norms=False)
    assert nbytes == 10 * (4 + 4) + 2 * (8 + 12) + 2 * 3 * 8
    ops, nbytes = ivf_scan_topk.cost(4, 10, [6, 4], 3)
    assert ops == 2 * 4 * 10
    assert nbytes == 10 * (16 + 4) + 2 * 16 + 2 * 3 * 8


def test_kernels_are_told_apart_in_a_trace():
    tail = ', custom_call_target="tpu_custom_call", operand_layout=...'
    sq = ("%_run_spec.1 = (f32[32,400], s32[32,400]) custom-call("
          "s32[8] %a, s8[2,32,256] %pad.0, f32[2,32,1] %pad.2" + tail)
    f32 = ("%_run_spec.2 = (f32[32,400], s32[32,400]) custom-call("
           "s32[8] %a, f32[32,256] %q" + tail)
    topk = '%custom-call = custom-call(f32[1,8]), custom_call_target="TopK"'
    assert [sq_scan_topk.matches(n) for n in (sq, f32, topk)] == \
        [True, False, False]
    assert [ivf_scan_topk.matches(n) for n in (sq, f32, topk)] == \
        [False, True, False]


def test_roofline_and_unknown_device():
    p = peaks.peaks_for("TPU v5 lite")
    # 819 bytes at 819 GB/s take 1 ns; 1 ns measured is the whole roofline
    share, bound = peaks.roofline(1.0, 819.0, 1e-9, "TPU v5 lite",
                                  "int8_ops")
    assert bound == "memory" and share == pytest.approx(100.0)
    share, bound = peaks.roofline(p["int8_ops"], 0.0, 4.0, "TPU v5 lite",
                                  "int8_ops")
    assert bound == "compute" and share == pytest.approx(25.0)
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")


def _ev(plane, name, s, e, line="XLA Ops"):
    return trace_reduce.Event(plane, line, name, float(s), float(e - s))


def test_trace_reduce_known_answer():
    host, dev = "/host:CPU", "/device:TPU:0"
    ev = [_ev(host, "window", 0, 100, "python"),
          _ev(host, "query", 25, 55, "python"),
          _ev(host, "session", 60, 95, "python"),
          _ev(dev, "a", 10, 20), _ev(dev, "sq_scan.1", 15, 30),
          _ev(dev, "a", 50, 60), _ev(dev, "b", 90, 110),
          _ev(dev, "a", -20, -10)]
    r = trace_reduce.reduce(ev, ("query", "session"))
    assert r["window_s"] == pytest.approx(100e-9)
    # busy: [10, 30] + [50, 60] + [90, 100] inside the window
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["idle_share"] == pytest.approx(0.6)
    assert r["ops"]["a"] == pytest.approx(20e-9)
    assert trace_reduce.kernel_seconds(r["ops"], lambda n: "sq" in n) == \
        pytest.approx(15e-9)
    assert [g[0] for g in r["gaps"]] == ["session", "query", "other"]
    assert [g[1] for g in r["gaps"]] == pytest.approx([30e-9, 20e-9, 10e-9])
    # two devices: busy is their mean, a gap is time both are idle
    ev2 = ev + [_ev("/device:TPU:1", "a", 0, 100)]
    r2 = trace_reduce.reduce(ev2, ("query",))
    assert r2["busy_s"] == pytest.approx(70e-9)
    assert r2["gaps"] == []


def test_trace_reduce_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("query"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace_reduce.load(str(tmp_path), ("query",))
    assert sum(e.name == "query" for e in ev) == 3
    r = trace_reduce.reduce(ev, ("query",))
    assert r["window_s"] > 0 and r["devices"] == 0


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "nytimes-int8.solo", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _printed_result(stdout):
    for line in stdout.splitlines():
        try:
            json.loads(line)
            return True
        except ValueError:
            pass
    return False


def test_cli_refuses_without_a_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not _printed_result(p.stdout)


def test_cli_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(str(tmp_path))
    assert p.returncode != 0
    assert "No module named 'repro'" in p.stderr
    assert not _printed_result(p.stdout)
