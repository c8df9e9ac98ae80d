"""`correct` comes out false when the timed path is broken underneath a
run, once for each fault a cell can have (`chipbench/faults.py`), and
for the control: the reference itself, in bfloat16, in the program's
place. At a tiny scale on the CPU; the harness's look for a chip is
skipped."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from chipbench import bench, faults, reference  # noqa: E402
from chipbench.tests.test_chipbench_cells import (  # noqa: E402
    LIMITS, RW_MIX, WRITE_P95, checkout_with, fd32_checkout, tiny)

CELLS = [w["name"] for w in bench.load()["workloads"]]


def _failed(r):
    return [n for n, c in r["checks"].items() if c["value"] > c["limit"]]


@pytest.fixture
def plant(monkeypatch):
    """Plant a fault for one test; undo it and drop the traces of the
    faulted code afterwards."""
    import jax
    yield lambda name: faults.plant(name, monkeypatch.setattr)
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload, tmp_path):
    r = tiny(workload, False, tmp_path, control=True)
    assert r["correct"], r["checks"]
    limits = {n: c["limit"] for n, c in r["checks"].items()}
    ctl = r["control"]
    assert any(ctl[n] > limits[n] for n in limits if n in ctl), (ctl, limits)
    assert ctl["correct"] is False, ctl


@pytest.mark.parametrize("workload", CELLS)
def test_answer_altered_where_produced(workload, tmp_path, plant):
    """Every answer's best row is replaced by another row."""
    plant("answer_altered")
    r = tiny(workload, False, tmp_path)
    assert not r["correct"] and _failed(r), r["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_scan_candidates_offset(workload, tmp_path, plant):
    """The int8 scan hands the rerank the wrong rows: the rerank scores
    them exactly, so only the recall against the reference sees it."""
    plant("scan_offset")
    r = tiny(workload, False, tmp_path)
    assert not r["correct"] and "miss_rate" in _failed(r), r["checks"]
    assert "score_gap" not in _failed(r), r["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_every_answer_empty(workload, tmp_path, plant):
    plant("all_holes")
    r = tiny(workload, False, tmp_path)
    assert not r["correct"], r["checks"]
    assert r["checks"]["miss_rate"]["value"] == 1.0


def test_half_of_a_coalesced_batch_left_out(tmp_path, monkeypatch):
    """The front door's fused call serves only the first half of its
    callers and hands the rest the first half's answers."""
    from repro.core import executor
    coalesced = executor.run_coalesced

    def half(index, chunks, spec):
        keep = chunks[:max(1, len(chunks) // 2)]
        out = coalesced(index, keep, spec)
        return [out[i % len(out)] for i in range(len(chunks))]

    monkeypatch.setattr(executor, "run_coalesced", half)
    r = tiny("nytimes-int8.fd32", False, tmp_path / "work",
             root=fd32_checkout(tmp_path))
    assert not r["correct"] and "score_gap" in _failed(r), r["checks"]


def test_write_acknowledged_but_state_unchanged(tmp_path, monkeypatch):
    """Session commits return without applying their writes."""
    from repro.storage import MicroNN
    commit = MicroNN._commit_session
    state = {"calls": 0}

    def unchanged(self, ops):
        state["calls"] += 1
        if state["calls"] > 10:         # the set-up's rows land
            return None
        return commit(self, ops)

    monkeypatch.setattr(MicroNN, "_commit_session", unchanged)
    root = checkout_with(tmp_path, "nytimes-int8.rw", "rw", RW_MIX,
                         dict(LIMITS, writes_lost=0), [WRITE_P95])
    r = tiny("nytimes-int8.rw", False, tmp_path / "work", root=root)
    assert not r["correct"] and "writes_lost" in _failed(r), r["checks"]


def test_judge_counts_by_hand():
    X = np.array([[0, 0], [1, 0], [0, 2], [5, 5]], np.float32)
    q = np.array([0.1, 0.0], np.float32)
    exact = ((X[[0, 1]] - q) ** 2).sum(1)
    good = reference.Answer(q=q, ids=np.array([0, 1]), scores=exact)
    r = reference.judge([good], X, 4, 2, "l2")
    assert r == {"miss_rate": 0.0, "score_gap": pytest.approx(0.0, abs=1e-7),
                 "bad_ids": 0}
    dup = reference.Answer(q=q, ids=np.array([0, 0]), scores=exact)
    r = reference.judge([dup], X, 4, 2, "l2")
    assert r["bad_ids"] == 1 and r["miss_rate"] == 0.5
    short = reference.Answer(q=q, ids=np.array([0, -1]), scores=exact)
    r = reference.judge([short], X, 4, 2, "l2")
    assert r["bad_ids"] == 0 and r["miss_rate"] == 0.5
    off = reference.Answer(q=q, ids=np.array([0, 1]), scores=exact + 0.5)
    # 0.5 off against ||q||^2 + ||x||^2 = 0.01 + 0 for row 0
    assert reference.judge([off], X, 4, 2, "l2")["score_gap"] == \
        pytest.approx(0.5 / 0.01)
