"""First-class declarative query API: `QuerySpec` in, `ResultSet` out.

The public surface of MicroNN is two objects (after the Faiss library
paper's stable index/query object model, and the filtered-ANN argument
that hybrid predicates belong *in* the query object):

    spec = Q.knn(k=100).probe(8).where(Pred(0, "==", 3)).backend("xla")
    rs   = db.query(vecs, spec)          # ResultSet
    for hit in rs: ...                   # per-query iteration

`QuerySpec` is a frozen, hashable dataclass -- it IS the executor's jit
cache key (core/executor._run_spec takes the spec as its only static
argument), so two structurally-equal specs -- including structurally
equal `Pred` trees, which hash by value -- provably share one
compile-cache entry, and `executor.trace_count()` is pinned against the
spec rather than an ad-hoc kwarg tuple. Every fluent method returns a new
spec (dataclasses.replace), so specs can be built once, stored, and
shared across threads/sessions.

`ResultSet` is the typed result every path returns (resident, paged,
hybrid-optimized, sharded): ids + exact-f32 scores, optional gathered
attribute rows, per-query iteration, `merge()` for sharded/chunked top-k
reduction, and `to_numpy()` for host handoff.

Pipeline:  QuerySpec --(executor.run)--> QueryPlan --> fused scan -->
ResultSet.  Plan construction is an executor-internal detail; callers
never see plans.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterator, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace as obs_trace
from .hybrid import Node
from .topk import dedup_by_id, merge_topk
from .types import INVALID_ID, SearchResult

_KINDS = ("ann", "exact")
_HYBRID = ("auto", "pre", "post")
_BACKENDS = (None, "pallas", "xla")

# A predicate slot holds either a frozen Pred/And/Or tree (preferred:
# hashes structurally, so equal trees share a jit entry) or an already
# compiled filter callable (hashes by identity -- the escape hatch for
# hand-written filters).
Predicate = Union[Node, Any]


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """One declarative search. Frozen + hashable: the jit cache key.

    Fields (all static; the builder methods below are the intended API):
      kind          "ann" (probe n_probe partitions) | "exact" (oracle)
      k             top-k width
      n_probe       partitions probed per query (ann)
      u_max         optional cap on the batched shared-scan union (MQO)
      cap           prefilter gather budget (hybrid == "pre"); None lets
                    the engine's optimizer size it from selectivity
      predicate     attribute predicate tree (Pred/And/Or), fused into
                    the scan or routed to pre-filtering
      hybrid        predicate strategy: "auto" (optimizer decides) |
                    "pre" (filter-then-brute-force) | "post" (fused)
      use_quantized scan-tier override: None auto (codes when present),
                    False forces f32, True requires codes
      on_backend    None auto | "pallas" | "xla"
      gather_attrs  gather result rows' attribute columns into the
                    ResultSet (engine-level; needs the durable store)
    """

    kind: str = "ann"
    k: int = 10
    n_probe: int = 8
    u_max: Optional[int] = None
    cap: Optional[int] = None
    predicate: Optional[Predicate] = None
    hybrid: str = "auto"
    use_quantized: Optional[bool] = None
    on_backend: Optional[str] = None
    gather_attrs: bool = False

    def __post_init__(self):
        assert self.kind in _KINDS, self.kind
        assert self.hybrid in _HYBRID, self.hybrid
        assert self.on_backend in _BACKENDS, self.on_backend
        assert self.k >= 1, self.k
        assert self.n_probe >= 1, self.n_probe

    # -- fluent builder (each call returns a NEW frozen spec) ---------------
    def top(self, k: int) -> "QuerySpec":
        return dataclasses.replace(self, k=k)

    def probe(self, n_probe: int) -> "QuerySpec":
        return dataclasses.replace(self, n_probe=n_probe)

    def union_cap(self, u_max: Optional[int]) -> "QuerySpec":
        """Cap the batched shared-scan union (the MQO knob, paper §3.4)."""
        return dataclasses.replace(self, u_max=u_max)

    def where(self, *predicates: Predicate) -> "QuerySpec":
        """Attach an attribute predicate. Several arguments AND together,
        and chained `.where()` calls ACCUMULATE (AND with the spec's
        existing predicate) -- a fluent chain never silently drops an
        earlier filter. Accepts Pred/And/Or trees or a compiled filter
        callable (the tree is recovered from `fn.predicate` when
        present, keeping the spec structurally hashable). A bare
        callable without a tree can only stand alone -- it cannot be
        AND-combined with other predicates (no tree to compose)."""
        from .hybrid import And, Or, Pred
        nodes = tuple(getattr(p, "predicate", p) for p in predicates)
        if self.predicate is not None:
            nodes = (self.predicate,) + nodes
        if len(nodes) == 1:
            node = nodes[0]
        else:
            bare = [n for n in nodes if not isinstance(n, (Pred, And, Or))]
            if bare:
                raise TypeError(
                    "where() can AND-combine predicate trees only; a "
                    "hand-written filter callable must be the sole "
                    f"predicate (got {len(bare)} callable(s) among "
                    f"{len(nodes)} predicates)")
            # flatten top-level Ands so .where(a).where(b).where(c) and
            # .where(a, b, c) build the SAME tree -- structurally equal
            # specs must share one jit cache entry however they were
            # chained
            flat = []
            for n in nodes:
                flat.extend(n.children if isinstance(n, And) else (n,))
            node = And(tuple(flat))
        return dataclasses.replace(self, predicate=node)

    @property
    def predicate_tree(self) -> Optional[Node]:
        """The predicate as a Pred/And/Or tree, or None when the spec
        carries no predicate OR an opaque hand-written callable (which
        selectivity estimation cannot inspect)."""
        from .hybrid import And, Or, Pred
        p = self.predicate
        return p if isinstance(p, (Pred, And, Or)) else None

    def exact(self) -> "QuerySpec":
        """100%-recall oracle: probe every partition."""
        return dataclasses.replace(self, kind="exact")

    def ann(self) -> "QuerySpec":
        return dataclasses.replace(self, kind="ann")

    def prefilter(self, cap: Optional[int] = None) -> "QuerySpec":
        """Force pre-filtering (evaluate the predicate first, brute-force
        the qualifiers). `cap` is the static gather budget; None lets the
        engine's optimizer size it from the selectivity estimate."""
        return dataclasses.replace(self, hybrid="pre", cap=cap)

    def postfilter(self) -> "QuerySpec":
        """Force post-filtering (predicate fused into the ANN scan)."""
        return dataclasses.replace(self, hybrid="post")

    def quantized(self, flag: Optional[bool] = True) -> "QuerySpec":
        return dataclasses.replace(self, use_quantized=flag)

    def backend(self, name: Optional[str]) -> "QuerySpec":
        return dataclasses.replace(self, on_backend=name)

    def with_attrs(self, flag: bool = True) -> "QuerySpec":
        return dataclasses.replace(self, gather_attrs=flag)


class Q:
    """Entry points of the fluent builder: `Q.knn(...)`, `Q.exact(...)`."""

    @staticmethod
    def knn(k: int = 10, n_probe: int = 8) -> QuerySpec:
        return QuerySpec(kind="ann", k=k, n_probe=n_probe)

    @staticmethod
    def exact(k: int = 10) -> QuerySpec:
        return QuerySpec(kind="exact", k=k)


# ---------------------------------------------------------------------------
# ResultSet
# ---------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)     # array fields: no element-wise __eq__
class QueryResult:
    """One query's hits, trimmed of INVALID padding (host arrays)."""

    ids: np.ndarray                    # [m] int32
    scores: np.ndarray                 # [m] float32 (exact f32 distances)
    attrs: Optional[np.ndarray] = None  # [m, n_attr] if gathered

    def __len__(self) -> int:
        return len(self.ids)


@dataclasses.dataclass(eq=False)     # array fields: no element-wise __eq__
class ResultSet:
    """Typed top-k result batch -- what every search path returns.

    `ids`/`scores` keep the executor's device layout ([Q, k], INVALID_ID
    marks missing hits, scores are exact float32 distances -- smaller is
    better); iteration and `to_numpy()` move to host lazily. `merge()`
    is the associative top-k reduction used for sharded / chunked
    execution: merging per-shard ResultSets of the same query batch
    yields the global top-k (duplicate ids deduped, best score kept).
    """

    ids: jax.Array                      # [Q, k] int32
    scores: jax.Array                   # [Q, k] float32
    spec: Optional[QuerySpec] = None
    attrs: Optional[np.ndarray] = None  # [Q, k, n_attr] if gathered
    # obs.trace.QueryTrace when the query ran traced (engine.query(
    # trace=True) / explain() / a traced front-door submit); None on the
    # untraced hot path
    trace: Optional[Any] = dataclasses.field(
        default=None, repr=False, compare=False)
    # memoized host copy (one device->host transfer however often the
    # set is iterated/indexed)
    _np: Optional[Tuple[np.ndarray, np.ndarray]] = dataclasses.field(
        default=None, repr=False, compare=False)

    @staticmethod
    def of(res: SearchResult, spec: Optional[QuerySpec] = None,
           attrs: Optional[np.ndarray] = None) -> "ResultSet":
        return ResultSet(ids=res.ids, scores=res.scores, spec=spec,
                         attrs=attrs)

    @property
    def num_queries(self) -> int:
        return int(self.ids.shape[0])

    @property
    def k(self) -> int:
        return int(self.ids.shape[1])

    def __len__(self) -> int:
        return self.num_queries

    def __iter__(self) -> Iterator[QueryResult]:
        for qi in range(self.num_queries):
            yield self[qi]

    def __getitem__(self, qi: int) -> QueryResult:
        ids, scores = self.to_numpy()
        got = ids[qi] != INVALID_ID
        return QueryResult(
            ids=ids[qi][got], scores=scores[qi][got],
            attrs=None if self.attrs is None else self.attrs[qi][got])

    def to_numpy(self) -> Tuple[np.ndarray, np.ndarray]:
        """Host copies of ids and scores, made once: the `device_wait`
        stage (block until both are ready), then the `fetch` stage (the
        device->host copies). Both record into `self.trace` -- the
        query's own activation scope has closed by now -- and into the
        profiler when it collects."""
        if self._np is None:
            with obs_trace.stage(obs_trace.STAGE_DEVICE_WAIT, self.trace):
                jax.block_until_ready((self.ids, self.scores))
            with obs_trace.stage(obs_trace.STAGE_FETCH, self.trace):
                self._np = (np.asarray(self.ids), np.asarray(self.scores))
        return self._np

    def split(self, sizes: Sequence[int]) -> List["ResultSet"]:
        """Partition the batch dimension back into per-caller ResultSets
        -- the inverse of the serving front door's request coalescing
        (executor.run_coalesced concatenates per-caller chunks into one
        fused scan; this slices the [Q, k] result rows back out). Purely
        mechanical: each slice carries the same spec, its own row range
        of ids/scores, and its rows of any gathered attrs, so a
        coalesced execution followed by split() is indistinguishable
        from per-caller solo runs. `sizes` must sum to num_queries."""
        sizes = [int(s) for s in sizes]
        assert all(s >= 1 for s in sizes), sizes
        assert sum(sizes) == self.num_queries, \
            f"split sizes {sizes} != batch {self.num_queries}"
        out: List[ResultSet] = []
        off = 0
        for s in sizes:
            out.append(ResultSet(
                ids=self.ids[off:off + s], scores=self.scores[off:off + s],
                spec=self.spec,
                attrs=None if self.attrs is None
                else self.attrs[off:off + s]))
            off += s
        return out

    def merge(self, other: "ResultSet", k: Optional[int] = None
              ) -> "ResultSet":
        """Associative top-k merge of two candidate sets for the SAME
        query batch (sharded search / chunked streams). Duplicated ids
        (overlapping shards, re-sent chunks) are deduped keeping the
        best score."""
        assert self.ids.shape[0] == other.ids.shape[0], \
            "merge() needs the same query batch on both sides"
        k_out = k if k is not None else max(self.k, other.k)
        k_out = min(k_out, self.k + other.k)
        # merge at 2x width before deduping: an id appears at most once
        # per side, so 2*k_out candidates always cover the true top-k_out
        # even under full overlap
        k_wide = min(2 * k_out, self.k + other.k)
        s, i = merge_topk(jnp.asarray(self.scores), jnp.asarray(self.ids),
                          jnp.asarray(other.scores), jnp.asarray(other.ids),
                          k_wide)
        s, i = dedup_by_id(s, i)
        i, s = i[:, :k_out], s[:, :k_out]
        attrs = None
        if self.attrs is not None and other.attrs is not None:
            # realign gathered attr rows to the merged ids (id -> row,
            # per query; both sides must carry attrs or none survive)
            ids_m = np.asarray(i)
            n_attr = self.attrs.shape[-1]
            attrs = np.zeros(ids_m.shape + (n_attr,), np.float32)
            a_ids, _ = self.to_numpy()
            b_ids, _ = other.to_numpy()
            for qi in range(ids_m.shape[0]):
                lut = {int(r): self.attrs[qi, j]
                       for j, r in enumerate(a_ids[qi]) if r != INVALID_ID}
                lut.update({int(r): other.attrs[qi, j]
                            for j, r in enumerate(b_ids[qi])
                            if r != INVALID_ID})
                for j, r in enumerate(ids_m[qi]):
                    if r != INVALID_ID:
                        attrs[qi, j] = lut[int(r)]
        return ResultSet(ids=i, scores=s, spec=self.spec or other.spec,
                         attrs=attrs)
