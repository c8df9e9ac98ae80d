"""Faults planted in the program under a run, to show that `correct`
comes out false when the timed path is broken. Never planted by a
benchmark run: `control.py --fault <name>` reads one on the chip, and
`tests/test_chipbench_faults.py` plants each under a CPU run.

    scan_offset       the rerank gets the int8 scan's candidates one
                      partition over (each flat row moved by p_max), so
                      it rescores the wrong rows exactly
    all_holes         every answer comes back empty (-1 ids)
    answer_altered    every answer's best row is replaced by another row,
                      after the rerank

`plant(name, setattr)` patches the program's module attributes with the
given `setattr` (pytest's `monkeypatch.setattr` undoes it; the builtin
keeps it for the process). Faults inside the jitted entry point clear
JAX's caches, so the next call traces the faulted code; whoever undoes
one clears them again.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _scan_offset(setattr):
    from repro.core import executor
    rerank = executor._rerank_float32

    def offset(index, q, rows, k_out):
        kp, p_max, _ = index.vectors.shape
        moved = (rows + p_max) % (kp * p_max)
        return rerank(index, q, jnp.where(rows < 0, rows, moved), k_out)

    setattr(executor, "_rerank_float32", offset)
    jax.clear_caches()


def _wrap_run(setattr, alter):
    from repro.core import executor
    from repro.core.query import ResultSet
    run = executor.run

    def altered(index, queries, spec, **kw):
        rs = run(index, queries, spec, **kw)
        ids, scores = alter(np.array(rs.ids), np.array(rs.scores))
        return ResultSet(ids=ids, scores=scores, spec=rs.spec)

    setattr(executor, "run", altered)


def _all_holes(setattr):
    _wrap_run(setattr, lambda ids, s: (np.full_like(ids, -1), s))


def _answer_altered(setattr):
    def alter(ids, s):
        ids[:, 0] = (ids[:, 0] + 1) % 1000
        return ids, s
    _wrap_run(setattr, alter)


FAULTS = {"scan_offset": _scan_offset, "all_holes": _all_holes,
          "answer_altered": _answer_altered}


def plant(name: str, setattr=setattr) -> None:
    FAULTS[name](setattr)
