"""The plain reference that decides `correct`, and its control.

The reference imports nothing of the program: it takes the rows and the
queries the harness generated, and judges the answers the timed path
returned (ids and reported scores) by three numbers:

  miss_rate   1 - recall@k against a float32 brute-force top-k over the
              rows that were live for the whole flight of the request
  score_gap   the widest gap between a reported score and the exact
              score of the same row, computed in float64, relative to
              ||q||^2 + ||x||^2 (L2) or to 1 (cosine); the engine
              promises exact float32 scores from its rerank
  bad_ids     returned ids that are no row, a row not live while the
              request was in flight, or a duplicate in one answer

An answer may hold fewer than k rows (-1 holes) where the probed
partitions hold fewer: the reference's rows it lacks count as misses.

Rows whose state changed while a request was in flight (written or
deleted by a session committed between its submit and its answer) may
be returned or not: they are left out of both sides.

The control puts the reference itself in the program's place, computed
in bfloat16 (inputs rounded to bfloat16, float32 accumulation: what one
bf16 pass of the chip's matrix unit computes), the precision below the
float32 the engine states for its reported scores.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

from . import data


@dataclasses.dataclass
class Answer:
    """One request as the timed path answered it."""
    q: np.ndarray                  # [d] float32 query
    ids: np.ndarray                # [k] int returned ids
    scores: np.ndarray             # [k] float32 reported scores
    live: Optional[np.ndarray] = None      # inserted rows live all flight
    maybe: Optional[np.ndarray] = None     # inserted rows that changed


def exact_scores(q: np.ndarray, X: np.ndarray, metric: str) -> tuple:
    """(exact score in float64, its scale) of each row of X for q."""
    q = q.astype(np.float64)
    X = X.astype(np.float64)
    if metric == "cosine":
        qn = q / max(np.linalg.norm(q), 1e-12)
        xn = X / np.maximum(np.linalg.norm(X, axis=1, keepdims=True), 1e-12)
        return -(xn @ qn), np.ones(len(X))
    return ((X - q) ** 2).sum(1), (q @ q) + (X * X).sum(1)


def _prep(X: np.ndarray, metric: str, dtype) -> np.ndarray:
    """Rows or queries as the scorer sees them: normalised for cosine,
    then rounded to `dtype` and held in float32."""
    if metric == "cosine":
        X = data.normalize(X)
    return X.astype(dtype).astype(np.float32)


def _scores(Qp: np.ndarray, Rp: np.ndarray, metric: str) -> np.ndarray:
    dots = Qp @ Rp.T
    if metric == "cosine":
        return -dots
    return ((Qp * Qp).sum(1)[:, None] + (Rp * Rp).sum(1)[None, :]
            - 2.0 * dots)


def reference_topk(ans: Sequence[Answer], X: np.ndarray, n_base: int,
                   k: int, metric: str, dtype=np.float32,
                   chunk: int = 65536) -> List[tuple]:
    """Brute-force top-k (ids, scores) of each answer's query over the
    base rows plus the inserted rows live for its whole flight (row id =
    index in X), with inputs rounded to `dtype` and float32
    accumulation. The base rows go through one blocked pass."""
    Qp = _prep(np.stack([a.q for a in ans]), metric, dtype)
    best_s = np.full((len(ans), 0), np.inf, np.float32)
    best_i = np.zeros((len(ans), 0), np.int64)
    for s in range(0, n_base, chunk):
        e = min(s + chunk, n_base)
        sc = _scores(Qp, _prep(X[s:e], metric, dtype), metric)
        best_s = np.concatenate([best_s, sc], axis=1)
        best_i = np.concatenate(
            [best_i, np.broadcast_to(np.arange(s, e), sc.shape)], axis=1)
        if best_s.shape[1] > k:
            keep = np.argpartition(best_s, k - 1, axis=1)[:, :k]
            best_s = np.take_along_axis(best_s, keep, axis=1)
            best_i = np.take_along_axis(best_i, keep, axis=1)
    out = []
    for j, a in enumerate(ans):
        ids, sc = best_i[j], best_s[j]
        if a.live is not None and len(a.live):
            extra = _scores(Qp[j:j + 1], _prep(X[a.live], metric, dtype),
                            metric)[0]
            ids = np.concatenate([ids, a.live])
            sc = np.concatenate([sc, extra])
        order = np.argsort(sc, kind="stable")[:k]
        out.append((ids[order], sc[order]))
    return out


def judge(ans: Sequence[Answer], X: np.ndarray, n_base: int, k: int,
          metric: str, ref: Optional[List[tuple]] = None) -> dict:
    """The three numbers for a sample of answers."""
    if ref is None:
        ref = reference_topk(ans, X, n_base, k, metric)
    hits = want = bad = 0
    gap = 0.0
    for a, (rid, _) in zip(ans, ref):
        maybe = set() if a.maybe is None else set(a.maybe.tolist())
        live_extra = set() if a.live is None else set(a.live.tolist())
        ids = [int(i) for i in a.ids]
        got = [i for i in ids if i >= 0]
        bad += len(got) - len(set(got))
        ok_rows = [i for i in set(got)
                   if i < n_base or i in live_extra or i in maybe]
        bad += len(set(got)) - len(ok_rows)
        ref_ids = [int(i) for i in rid if int(i) not in maybe]
        want += len(ref_ids)
        hits += len(set(ref_ids) & set(got))
        if ok_rows:
            pos = [j for j, i in enumerate(ids) if i in ok_rows]
            rows = np.array([ids[j] for j in pos])
            ex, scale = exact_scores(a.q, X[rows], metric)
            rep = np.asarray(a.scores, np.float64)[pos]
            gap = max(gap, float(np.max(np.abs(rep - ex) / scale)))
    return {"miss_rate": 1.0 - hits / max(want, 1), "score_gap": gap,
            "bad_ids": bad}


def control_answers(ans: Sequence[Answer], X: np.ndarray, n_base: int,
                    k: int, metric: str) -> List[Answer]:
    """The control: the reference in the program's place, in bfloat16."""
    import ml_dtypes
    ref = reference_topk(ans, X, n_base, k, metric, dtype=ml_dtypes.bfloat16)
    return [dataclasses.replace(a, ids=ids, scores=s)
            for a, (ids, s) in zip(ans, ref)]
