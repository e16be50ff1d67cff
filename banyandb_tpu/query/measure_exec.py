"""Device executor for measure aggregation queries.

Pipeline (SURVEY.md §3.3 data-node hot loop, rebuilt TPU-first):

  host:   sources (memtable + part blocks) -> global tag dictionaries ->
          code remap -> version dedup (lexsort) -> 8192-row chunks
  device: one jitted program per plan signature (query/fused_exec):
          time/tag masks -> mixed-radix group key -> segment reduce
          (count/sum/min/max) -> [+ histogram for percentile], scanned
          over the stacked chunks inside the one program
  host:   combine tiny per-chunk partials, invert histograms, top-N, limit

The jit cache is keyed by a static PlanSpec, so repeated queries with the
same shape (the dashboard pattern) skip compilation entirely — predicate
*values* are traced arguments, not compile-time constants.

Precision contract: device kernels produce f32 partials whose f32
accumulation span is bounded (Kahan-compensated across tiles — see
ops/groupby.py); this host loop merges per-chunk partials in f64. Net
effect: per-group sums stay within ~1e-5 relative of exact f64 at any
row count (tests/test_precision.py).
"""

from __future__ import annotations

import operator
import os
import threading
import time
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from banyandb_tpu import ops
from banyandb_tpu.api.model import (
    Aggregation,
    Condition,
    Criteria,
    LogicalExpression,
    QueryRequest,
    QueryResult,
)
from banyandb_tpu.api.schema import Measure, TagType
from banyandb_tpu.obs import metrics as obs_metrics
from banyandb_tpu.obs import tracer
from banyandb_tpu.storage.part import ColumnData
from banyandb_tpu.utils import compile_cache, hostops
from banyandb_tpu.utils.envflag import env_int

# stage latency instruments (always on, spans or not): the attribution
# plane ROADMAP item 1's bench reads back as stage_breakdown.  Handles
# resolved once at import — observe() never touches the registry lock.
_H_GATHER = obs_metrics.stage_histogram("gather")
_H_DEVICE = obs_metrics.stage_histogram("device_execute")
_H_MERGE = obs_metrics.stage_histogram("merge")
# the pad/pack/ship half of the decode stage (ROADMAP item 3): host-side
# narrow packing + H2D transfer time; the device half (widen/remap/f32
# convert) is fused INSIDE the plan kernel and shows up in
# device_execute, which is exactly the point
_H_DECODE = obs_metrics.stage_histogram("decode")

CHUNK = 8192
# Scan chunks are much larger than storage blocks (8192 rows,
# banyand/measure/measure.go:46): the kernel is HBM-bound, so per-chunk
# dispatch + [G]-sized host accumulation dominate at small chunks (profiled
# ~330ms of a 372ms warm 100k-group scan at 8192).  Power-of-two buckets up
# to SCAN_CHUNK keep the compiled-shape set finite.
SCAN_CHUNK = env_int("BYDB_SCAN_CHUNK", 1 << 20)
_NUM_HIST_BUCKETS = 512


def _scan_bucket(n: int) -> int:
    b = 64
    while b < n:
        b <<= 1
    return min(b, SCAN_CHUNK)


@dataclass(frozen=True)
class _PredSpec:
    """Static shape of one predicate; its value(s) arrive as traced args.

    kinds:
    - "code": compare against a global dictionary code (eq/ne) or a padded
      code set (in/not_in).
    - "lut":  a bool lookup table over global codes — how numeric range
      predicates on INT tags evaluate without shipping 64-bit tag values
      to the device (the host computes op(dict_value, literal) per code).
    """

    kind: str  # "code" | "lut"
    name: str  # tag name
    op: str  # eq/ne/in/not_in (code) | lt/le/gt/ge (lut)
    nvals: int = 1  # in/not_in set size or LUT length (static shape)


@dataclass(frozen=True)
class PlanSpec:
    """Static jit key: everything that shapes the compiled kernel."""

    tags_code: tuple[str, ...]  # tag columns shipped as global codes
    fields: tuple[str, ...]
    preds: tuple[_PredSpec, ...]
    group_tags: tuple[str, ...]
    radices: tuple[int, ...]  # global dict size per group tag
    num_groups: int
    want_minmax: bool
    hist_field: str = ""  # non-empty -> also emit histogram partials
    nrows: int = CHUNK
    group_method: str = "auto"  # ops.group_reduce method override
    # scan-order tracking: emit per-group min (scan asc) or max (desc)
    # of (ts<<32 | row) — drives first-appearance group ordering AND the
    # representative row for projected-but-not-grouped tags
    want_rep: bool = False
    rep_desc: bool = False
    # predicate expression tree over `preds`: ("p", i) leaves combined by
    # ("and", l, r) / ("or", l, r) nodes — the device lowering of a full
    # model/v1 Criteria tree (pkg/query/logical analog). () = AND of all
    # preds (the common flat case keeps its original plan signature).
    expr: tuple = ()


class DeviceLeg:
    """Host-clock times at one reduction's accelerator boundaries: the
    jitted calls returning (``dispatch_s``) and the ``device_get`` waits
    (``get_s``) — a dispatch that blocks (a trace, a compile, a
    synchronous transfer) and a device that works read differently —
    the bytes those gets brought back (``get_bytes``: the stacked
    per-chunk partials, a padding chunk's zeros included; ``hist_bytes``
    of them a percentile plan's histogram), the most
    fused dispatches of other queries that were issued and not yet
    fetched when one of these was issued (``dispatches_ahead``: the
    device runs them first, and ``get_s`` holds them), plus what the
    dispatches traced or compiled on this thread."""

    __slots__ = (
        "dispatch_s", "get_s", "get_bytes", "hist_bytes", "dispatches_ahead",
        "paid",
    )

    def __init__(self):
        self.dispatch_s = 0.0
        self.get_s = 0.0
        self.get_bytes = 0
        self.hist_bytes = 0
        self.dispatches_ahead = 0
        # entered around the dispatches (`with leg.paid:`)
        self.paid = compile_cache.watch()

    @property
    def device_s(self) -> float:
        return self.dispatch_s + self.get_s

    def tag(self, span) -> None:
        """The ``reduce`` span's device tags; device_ms stays the sum."""
        span.tag("device_ms", round(self.device_s * 1000, 3)).tag(
            "dispatch_ms", round(self.dispatch_s * 1000, 3)
        ).tag("get_ms", round(self.get_s * 1000, 3)).tag(
            "partials_bytes", self.get_bytes
        ).tag("dispatches_ahead", self.dispatches_ahead)
        if self.paid.compiled:
            span.tag("compiled", self.paid.compiled).tag(
                "compile_ms", round(self.paid.seconds * 1000, 3)
            ).tag("program", self.paid.program)


def _kernel_body(spec: PlanSpec):
    """The un-jitted per-chunk partial computation for `spec`.

    query/fused_exec scans it over a stacked chunk batch inside ONE
    jitted program (each step first widens its compressed chunk with
    ops.decode.decode_chunk, and skips a chunk that holds no valid
    row): one trace graph per chunk however the scan is batched, which
    is what keeps partials byte-identical across batchings.  A
    percentile plan's ``hist`` is the scan's int32 histogram, flat
    [G * 512]: the chunk's rows are added into it and ``out["hist"]`` is
    the sum."""

    def kernel(chunk: dict, pred_vals: dict, hist_lo, hist_span, hist=None):
        valid = chunk["valid"]

        def pred_mask(i: int):
            p = spec.preds[i]
            col = chunk["tags_code"][p.name]
            v = pred_vals[f"p{i}"]
            if p.kind == "lut":
                return jnp.take(v, col, mode="clip")
            if p.op in ("in", "not_in"):
                m = ops.in_set_mask(col, v)
                return ~m if p.op == "not_in" else m
            return ops.cmp_mask(col, p.op, v)

        def eval_expr(node):
            if node[0] == "p":
                return pred_mask(node[1])
            left = eval_expr(node[1])
            right = eval_expr(node[2])
            return (left & right) if node[0] == "and" else (left | right)

        # named scopes are trace-time metadata only: the device trace's
        # op names carry them (obs/devtrace reads the innermost `bydb.`)
        with jax.named_scope("bydb.filter"):
            if spec.expr:
                mask = valid & eval_expr(spec.expr)
            else:  # flat AND of all preds (original plan shape)
                mask = ops.mask_and(
                    valid, *[pred_mask(i) for i in range(len(spec.preds))]
                )

        with jax.named_scope("bydb.group_key"):
            key_cols = [chunk["tags_code"][t] for t in spec.group_tags]
            if key_cols:
                key, _ = ops.mixed_radix_key(key_cols, spec.radices)
            else:
                key = jnp.zeros(valid.shape, jnp.int32)

        res = ops.group_reduce(
            key,
            mask,
            chunk["fields"],
            spec.num_groups,
            want_minmax=spec.want_minmax,
            method=spec.group_method,
        )
        out = {
            "count": res.count,
            "sums": res.sums,
            "mins": res.mins,
            "maxs": res.maxs,
        }
        if spec.hist_field:
            # added into the scan's one int32 histogram (`hist`), which
            # the fused program carries across chunks and batches
            out["hist"] = ops.group_histogram(
                key,
                mask,
                chunk["fields"][spec.hist_field],
                spec.num_groups,
                hist_lo,
                hist_span,
                _NUM_HIST_BUCKETS,
                counts=hist,
            )
        if spec.want_rep:
            # scan-order tracking, 32-bit friendly (device x64 stays
            # off): per-group min/max ts, then min/max row among rows AT
            # that ts — the first row of each group under a ts ASC scan
            # (DESC under ORDER BY time DESC), which drives both group
            # emission order and the representative row (reference
            # measure_plan_groupby.go first-appearance + aggregation
            # first-fed row semantics)
            with jax.named_scope("bydb.rep"):
                ts32 = chunk["ts"]
                row32 = chunk["row"]
                G1 = spec.num_groups + 1
                skey = jnp.where(mask, key, jnp.int32(spec.num_groups))
                if spec.rep_desc:
                    gts = jax.ops.segment_max(
                        jnp.where(mask, ts32, jnp.int32(-(2**31) + 1)),
                        skey, num_segments=G1,
                    )
                    at = mask & (ts32 == jnp.take(gts, skey, mode="clip"))
                    grow = jax.ops.segment_max(
                        jnp.where(at, row32, jnp.int32(-1)),
                        skey, num_segments=G1,
                    )
                else:
                    gts = jax.ops.segment_min(
                        jnp.where(mask, ts32, jnp.int32(2**31 - 1)),
                        skey, num_segments=G1,
                    )
                    at = mask & (ts32 == jnp.take(gts, skey, mode="clip"))
                    grow = jax.ops.segment_min(
                        jnp.where(at, row32, jnp.int32(2**31 - 1)),
                        skey, num_segments=G1,
                    )
                out["rep_ts"] = gts[: spec.num_groups]
                out["rep_row"] = grow[: spec.num_groups]
        return out

    return kernel


class GlobalDicts:
    """Union of per-source tag dictionaries -> stable global codes.

    Codes are append-only: once a value has a code it keeps it forever,
    which is what lets DictState persist dictionaries (and cached
    per-part remap LUTs) across queries.
    """

    def __init__(self, tag_names: Sequence[str]):
        self.maps: dict[str, dict[bytes, int]] = {t: {} for t in tag_names}

    def ensure(self, tag: str) -> None:
        self.maps.setdefault(tag, {})

    def add_source(self, tag: str, d: list[bytes]) -> np.ndarray:
        """-> LUT local_code -> global_code for one source."""
        m = self.maps[tag]
        return np.fromiter(
            (m.setdefault(v, len(m)) for v in d), dtype=np.int32, count=len(d)
        )

    def size(self, tag: str) -> int:
        return max(len(self.maps[tag]), 1)

    def code_of(self, tag: str, value: bytes) -> int:
        return self.maps[tag].get(value, -1)

    def absent_code(self, tag: str) -> int:
        """Global code for the empty value (rows from sources that predate
        the tag)."""
        m = self.maps[tag]
        return m.setdefault(b"", len(m))

    def values(self, tag: str) -> list[bytes]:
        m = self.maps[tag]
        out = [b""] * len(m)
        for v, c in m.items():
            out[c] = v
        return out


class _WaitTimedLock:
    """A lock for ``with`` that adds what each acquisition waited to the
    acquiring thread's total (``waited_s``).  Queries on other server
    threads hold DictState.lock while they fill dictionaries and build
    remap tables; compute_partials reads the total before and after to
    say how long THIS query waited for them (the ``gather`` span's
    ``dict_lock_wait_ms``)."""

    __slots__ = ("_lock", "_waited")

    def __init__(self):
        self._lock = threading.Lock()
        self._waited = threading.local()

    def __enter__(self):
        t0 = time.perf_counter()
        self._lock.acquire()
        self._waited.s = self.waited_s() + time.perf_counter() - t0

    def __exit__(self, *exc):
        self._lock.release()

    def waited_s(self) -> float:
        """Seconds this thread has waited for the lock, all told."""
        return getattr(self._waited, "s", 0.0)


class DictState:
    """Per-(engine, measure) persistent dictionary + remap state.

    The serving-cache companion (VERDICT r1 weak #5): global tag
    dictionaries grow monotonically across queries, per-part remap LUTs
    are cached by immutable part identity, and the token keys gathered
    chunks in the process serving cache so a repeat query skips
    _gather_rows entirely.  All access to `dicts` (reads included — dict
    iteration during insert raises) happens under `lock`; queries run
    concurrently on server threads.

    Growth bound: group cardinality is the product of all-time dict
    sizes, so tag churn under retention (values no live part holds any
    more) would inflate kernels without bound.  reset() discards the
    state (new token orphans old cache entries, which simply LRU out),
    rebounding cardinality to the live data on the next gather.
    BYDB_MAX_PERSISTENT_GROUPS is the group space above which
    compute_partials CHECKS the state, and it resets it for the dead
    values it holds, not for its size: a gather that leaves the group
    space over the bound counts, per group-by tag, the distinct global
    codes its sources' remap tables reference (note_live) and
    `live_seen` keeps the largest count since the last reset; the state
    is reset when its group space passes the bound AND passes
    _DEAD_FACTOR times the product of `live_seen`.  A store whose live
    group space is over the bound therefore keeps its dictionaries and
    tables, and G over the bound is never more than _DEAD_FACTOR x live.
    """

    def __init__(self):
        self.lock = _WaitTimedLock()
        self._reset_locked()

    def _reset_locked(self):
        import uuid

        self.dicts = GlobalDicts(())
        self.remaps: dict[tuple, np.ndarray] = {}
        self.token = uuid.uuid4().hex
        # snapshot caches, valid while their length still covers the
        # (append-only) dict: values = code -> bytes list; ranks = code ->
        # lexicographic position among dict values (canonical group order
        # without a per-query Python sort over 100k groups)
        self.values_cache: dict[str, list] = {}
        self.rank_cache: dict[str, np.ndarray] = {}
        # tag -> the most distinct codes one gather's sources referenced
        # since this reset (the largest, not the latest: a narrow query
        # between two wide ones must not make the state look bloated),
        # and tag -> (remaps keys, count) of the last count taken
        self.live_seen: dict[str, int] = {}
        self.live_memo: dict[str, tuple[tuple, int]] = {}

    def reset(self):
        with self.lock:
            self._reset_locked()

    def values_snapshot(self, tag: str) -> list:
        """code -> value list for `tag`; cached, caller holds self.lock.
        The returned list is immutable by convention (shared across
        queries): dict growth rebuilds a fresh list."""
        m = self.dicts.maps.get(tag, {})
        cached = self.values_cache.get(tag)
        if cached is None or len(cached) != len(m):
            cached = self.dicts.values(tag)
            self.values_cache[tag] = cached
        return cached

    def note_live(self, tables: dict[str, list]) -> None:
        """Record what one gather found live; caller holds self.lock and
        has checked that the gather's GlobalDicts is still self.dicts.

        `tables`: tag -> [(remaps key or None, local -> global LUT)] of
        the gather's sources.  The count is one boolean mark array of
        the dictionary's size and one vectorised pass a source; a query
        over the same parts as the last takes it from `live_memo`."""
        for tag, used in tables.items():
            keys = tuple(rk for rk, _ in used)
            memo = self.live_memo.get(tag)
            if memo is not None and None not in keys and memo[0] == keys:
                live = memo[1]
            else:
                mark = np.zeros(len(self.dicts.maps[tag]), dtype=bool)
                for _, lut in used:
                    mark[lut] = True
                live = int(np.count_nonzero(mark))
                self.live_memo[tag] = (keys, live)
            self.live_seen[tag] = max(self.live_seen.get(tag, 0), live)

    def rank_lut(self, tag: str, values: list) -> np.ndarray:
        """code -> bytes-lexicographic rank over at least `values`.

        Ranks from a larger (append-only) snapshot stay order-preserving
        over any older snapshot's codes, so a cached superset is reusable;
        callers only need relative order, not density.  The cache is
        guarded by snapshot identity — `values` must be the object
        values_snapshot currently hands out — so a query holding a
        pre-reset snapshot can neither reuse nor poison the post-reset
        cache (codes from the old dict generation rank differently).
        Takes self.lock.
        """
        with self.lock:
            current = self.values_cache.get(tag)
            if values is not current:
                return _build_rank_lut(values)  # stale/foreign: uncached
            lut = self.rank_cache.get(tag)
            if lut is None or len(lut) < len(values):
                lut = self.rank_cache[tag] = _build_rank_lut(values)
            return lut


def _build_rank_lut(values: list) -> np.ndarray:
    """code -> bytes-lexicographic rank among `values` (inverse argsort)."""
    order = sorted(range(len(values)), key=values.__getitem__)
    lut = np.empty(len(values), dtype=np.int64)
    lut[np.asarray(order, dtype=np.int64)] = np.arange(
        len(values), dtype=np.int64
    )
    return lut


_MAX_PERSISTENT_GROUPS = env_int("BYDB_MAX_PERSISTENT_GROUPS", 1 << 18)
# Over the bound a DictState is reset when its group space passes this
# many times what its queries found live: the reset then halves G at
# least and the next doubling pays for it (a doubling array's
# amortisation).  A constant, not a flag.
_DEAD_FACTOR = 2


def _group_space(dict_state: "DictState", group_tags) -> tuple[int, int]:
    """-> (product of the group-by tags' dictionary sizes, product of
    their `live_seen`, 0 while a tag has no reading); caller holds
    dict_state.lock."""
    size = live = 1
    for t in group_tags:
        size *= max(len(dict_state.dicts.maps.get(t, ())), 1)
        live *= dict_state.live_seen.get(t, 0)
    return size, live


def _tag_value_bytes(v) -> bytes:
    if isinstance(v, bytes):
        return v
    if isinstance(v, str):
        return v.encode()
    if isinstance(v, int):
        return v.to_bytes(8, "little", signed=True)
    raise TypeError(f"unsupported tag literal {type(v)}")


def _collect_conditions(c: Optional[Criteria]) -> list[Condition]:
    """Flatten an AND-tree; callers needing OR use _lower_criteria."""
    if c is None:
        return []
    if isinstance(c, Condition):
        return [c]
    assert isinstance(c, LogicalExpression)
    if c.op != "and":
        raise NotImplementedError(
            "AND-only path; OR criteria lower via _lower_criteria"
        )
    return _collect_conditions(c.left) + _collect_conditions(c.right)


def _lower_criteria(c: Optional[Criteria]) -> tuple[list[Condition], tuple]:
    """Full Criteria tree -> (predicate leaves, index expression tree).

    Pure-AND trees return expr=() so the common flat case keeps its
    original plan signature (jit-cache stability); OR anywhere produces
    a nested ("and"|"or", left, right) tree over ("p", i) leaves that
    the kernel evaluates as mask algebra (union of in-set masks — the
    device lowering of pkg/query/logical's OR nodes)."""
    conds: list[Condition] = []

    def walk(node):
        if isinstance(node, Condition):
            conds.append(node)
            return ("p", len(conds) - 1)
        assert isinstance(node, LogicalExpression), node
        if node.op not in ("and", "or"):
            raise ValueError(f"unknown logical op {node.op!r}")
        return (node.op, walk(node.left), walk(node.right))

    if c is None:
        return [], ()
    expr = walk(c)

    def pure_and(n) -> bool:
        return n[0] == "p" or (
            n[0] == "and" and pure_and(n[1]) and pure_and(n[2])
        )

    return conds, (() if pure_and(expr) else expr)


def _take(seq, ids: list):
    """[seq[i] for i in ids] as one C-level gather (`itemgetter` hands a
    single index back bare, so fewer than two take the comprehension)."""
    if len(ids) > 1:
        return operator.itemgetter(*ids)(seq)
    return [seq[i] for i in ids]


class Partials:
    """Per-node partial aggregates keyed by decoded tag-value tuples.

    The wire unit of distributed map-reduce aggregation (the reference's
    agg_return_partial InternalQueryResponse,
    docs/concept/distributed-measure-aggregation.md): nodes return these,
    the liaison combines by group tuple and finalizes.  Arrays cover only
    nonempty groups (dense [G] layouts never cross nodes).

    Group identity is dual-representation: either materialized value
    tuples (`groups`, the wire/combine form) or dense global-code rows
    (`codes` [K, T] + `group_values` dict snapshots, the standalone hot
    path).  Tuples materialize lazily on first `.groups` access — a
    standalone TopN over 100k groups never builds 100k Python tuples
    (profiled at ~130ms/query before this split).
    """

    __slots__ = (
        "group_tags", "count", "sums", "mins", "maxs", "hist", "hist_lo",
        "hist_span", "field_stats", "_groups", "codes", "group_values",
        "rep_key", "rep_desc", "rep_vals", "ranks", "ranks_q",
    )

    def __init__(
        self,
        group_tags: tuple[str, ...],
        groups: Optional[list] = None,  # tag-value tuple per nonempty group
        count: np.ndarray = None,  # f64 [K]
        sums: dict = None,  # field -> f64 [K]
        mins: dict = None,
        maxs: dict = None,
        hist: Optional[np.ndarray] = None,  # [K, B]
        hist_lo: float = 0.0,
        hist_span: float = 1.0,
        field_stats: dict = None,  # f -> (min, max)
        codes: Optional[np.ndarray] = None,  # int32 [K, T] global codes
        group_values: Optional[dict] = None,  # tag -> list[bytes] snapshot
        rep_key: Optional[np.ndarray] = None,  # int64 [K] scan-order key
        rep_desc: bool = False,
        rep_vals: Optional[dict] = None,  # tag -> list[bytes] [K] rep row
        # int32 [K, Q, 3] in place of `hist` where the device inverted it
        # for the quantiles `ranks_q`: (hit bucket, count below it, count
        # in it) per group and quantile; such a partial is final alone
        ranks: Optional[np.ndarray] = None,
        ranks_q: tuple = (),
    ):
        if groups is None and codes is None:
            raise TypeError("Partials needs groups or codes+group_values")
        self.group_tags = group_tags
        self._groups = groups
        self.codes = codes
        self.group_values = group_values
        self.count = count
        self.sums = sums
        self.mins = mins
        self.maxs = maxs
        self.hist = hist
        self.hist_lo = hist_lo
        self.hist_span = hist_span
        self.field_stats = {} if field_stats is None else field_stats
        self.rep_key = rep_key
        self.rep_desc = rep_desc
        self.rep_vals = rep_vals
        self.ranks = ranks
        self.ranks_q = ranks_q

    @property
    def groups(self) -> list[tuple[bytes, ...]]:
        if self._groups is None:
            k = self.codes.shape[0]
            if not self.group_tags:
                self._groups = [()] * k
            else:
                self._groups = list(zip(*self.group_columns(np.arange(k, dtype=np.int64))))
        return self._groups

    @groups.setter
    def groups(self, v: list) -> None:
        self._groups = v

    def group_key(self, i: int) -> tuple[bytes, ...]:
        """Decode ONE group's value tuple without materializing the rest."""
        if self._groups is not None:
            return self._groups[i]
        return tuple(
            self.group_values[t][int(self.codes[i, j])]
            for j, t in enumerate(self.group_tags)
        )

    def group_columns(self, ids: np.ndarray) -> list:
        """The value tuples of groups `ids` as columns, one sequence a
        group tag: `group_key` over many groups, one C-level gather a
        column instead of a Python step a group."""
        if self._groups is not None:
            if not len(ids):
                return [[] for _ in self.group_tags]
            return list(zip(*_take(self._groups, ids.tolist())))
        return [
            _take(self.group_values[t], col)
            for t, col in zip(self.group_tags, self.codes[ids].T.tolist())
        ]

    def content_bytes(self) -> bytes:
        """Canonical byte serialization of every numeric/representative
        component — THE byte-parity oracle the A/B contracts
        (``BYDB_PIPELINE``, one dispatch against chunk batches, zone
        skipping) are asserted against (tests/test_fused_exec.py,
        tests/test_decode.py, scripts/decode_smoke.py all compare this
        one serialization, so a new Partials field added here is
        parity-pinned everywhere at once)."""
        parts = [
            self.count.tobytes(),
            self.codes.tobytes() if self.codes is not None else b"",
        ]
        for d in (self.sums, self.mins, self.maxs):
            for k in sorted(d):
                parts.append(d[k].tobytes())
        if self.hist is not None:
            parts.append(self.hist.tobytes())
        if self.ranks is not None:
            parts.append(self.ranks.tobytes())
            parts.append(repr(self.ranks_q).encode())
        if self.rep_key is not None:
            parts.append(self.rep_key.tobytes())
        if self.rep_vals is not None:
            parts.append(repr(sorted(self.rep_vals.items())).encode())
        return b"".join(parts)


def execute_aggregate(
    measure: Measure,
    request: QueryRequest,
    sources: list[ColumnData],
    dict_state: Optional[DictState] = None,
    analyzers: Optional[dict] = None,
    span=None,
    plan_hints=None,
) -> QueryResult:
    """Run a group-by/aggregate/top-N/percentile query over decoded sources."""
    partial = compute_partials(
        measure, request, sources, dict_state=dict_state, analyzers=analyzers,
        span=span, plan_hints=plan_hints, final=True,
    )
    return finalize_partials(
        measure, request, [partial], dict_state=dict_state, span=span
    )


def compute_partials(
    measure: Measure,
    request: QueryRequest,
    sources: list[ColumnData],
    hist_range: Optional[tuple[float, float]] = None,
    dict_state: Optional[DictState] = None,
    analyzers: Optional[dict] = None,
    span=None,
    plan_hints=None,
    final: bool = False,
) -> Partials:
    """The 'map' phase: device scan+reduce over local sources.

    `hist_range` fixes the percentile histogram range (distributed
    two-pass: the liaison first combines field_stats, then re-requests
    with the global range so node histograms are combinable).

    `dict_state` (engine-owned) turns on the serving-cache fast path:
    persistent global dictionaries, cached per-part remaps, and cached
    gathered chunks keyed by part identities — repeat queries skip the
    whole host gather.

    `span` (obs.tracer.Span or None): tracing sink — gather/reduce child
    spans with cache hit/miss tags and device/host attribution.  None
    keeps the path span-free; the stage histograms observe either way.

    `plan_hints` (query/planner.PlanDecision or None): the cost-based
    planner's result-preserving refinements — a group-method override
    when the estimated distinct group count crosses the hash/sort
    crossover on the other side of the static radix product and a
    minimum fused chunk-count bucket (signature stability).
    ``actual_rows`` is written back for the planner
    span's est-vs-actual tag.

    `final`: the caller finalizes this partial alone
    (``finalize_partials([partial])``, the standalone path).  A
    percentile plan then inverts its histogram on the device for the
    request's quantiles: the partial holds their ranks (``Partials.ranks``)
    and the [G, 512] histogram never leaves the device.  A caller that
    combines partials (a data node's reply, streamagg's rescans, the
    two-pass range) leaves it False and gets the histogram, fetched once
    a query.
    """
    import time as _time
    conds, expr = _lower_criteria(request.criteria)
    group_tags = tuple(request.group_by.tag_names) if request.group_by else ()
    agg = request.agg

    # --- which columns ride to the device ---------------------------------
    range_ops = {"lt", "le", "gt", "ge"}
    tags_code: set[str] = set(group_tags)
    for c in conds:
        measure.tag(c.name)  # validate against schema (KeyError on typo)
        tags_code.add(c.name)
    # Representative tags: projected but not grouped — each output group
    # carries the first-scanned row's values for these (reference
    # aggregation copies the first fed row's TagFamilies).  Unknown
    # projected tags are schema errors (ref WantErr cases).
    rep_tags: tuple[str, ...] = ()
    if group_tags or agg is not None:
        schema_fields = {f.name for f in measure.fields}
        rep_list = []
        for t in request.tag_projection:
            if t in group_tags:
                continue
            if t in schema_fields:
                # bydbql puts the SELECT list into BOTH projections, so a
                # grouped `SELECT svc, value ...` names the field here;
                # fields are never representative tags
                continue
            measure.tag(t)  # KeyError -> INVALID_ARGUMENT on the wire
            rep_list.append(t)
            tags_code.add(t)
        rep_tags = tuple(dict.fromkeys(rep_list))
    # scan-order tracking runs for what reads the key: a listing's
    # first-appearance emission order (and its LIMIT / OFFSET pages), and
    # the representative row of projected-but-not-grouped tags (a
    # no-group aggregate's output row carries the first scanned row's).
    # A Top-N that projects no such tag reads neither: its ranking
    # replaces the order and ties at the cut resolve by group key
    # (_finalize_partials_inner), so its plan drops the tracking, and
    # ORDER BY time DESC does not split that one program into two
    want_rep = bool(rep_tags) or (bool(group_tags) and not request.top)
    rep_desc = want_rep and request.order_by_ts == "desc"
    # Projection names that aren't schema fields (e.g. tags from a QL
    # SELECT list) are dropped — they'd only materialize zero columns.
    # Raw (string/binary) fields never ride the device path either: they
    # are stored as '@f:' tag columns and only the raw-row path serves
    # them (models/measure._raw_fields).
    from banyandb_tpu.api.schema import FieldType as _FT

    known = {
        f.name
        for f in measure.fields
        if f.type not in (_FT.STRING, _FT.DATA_BINARY)
    }
    fields = {f for f in request.field_projection if f in known}
    if agg:
        fields.add(agg.field_name)
    if request.top:
        fields.add(request.top.field_name)

    # --- global dictionaries + remapped concatenated columns --------------
    # gd and token are captured atomically under the lock: a concurrent
    # cap-triggered reset swaps dict_state.dicts/token together, and all
    # cache writes below guard on `dict_state.dicts is gd` so an in-flight
    # query can never poison the post-reset caches with old codes.
    dict_reset = dict_over_bound = False
    lock_waited0 = dict_state.lock.waited_s() if dict_state is not None else 0.0
    if dict_state is None:
        gd = GlobalDicts(sorted(tags_code))
        token = None
    else:
        with dict_state.lock:
            # Growth bound (DictState): over the bound, reset a state
            # more than half of whose group space is dead for every
            # query it has served (tag churn under retention), so
            # cardinality re-bounds to live data; a tag with no reading
            # since the last reset counts as dead.  A state whose LIVE
            # group space is over the bound is kept: resetting it would
            # only rebuild the same dictionaries and tables.
            size, live = _group_space(dict_state, group_tags)
            dict_over_bound = size > _MAX_PERSISTENT_GROUPS
            if dict_over_bound and size > _DEAD_FACTOR * live:
                dict_state._reset_locked()
                dict_reset = True
            gd = dict_state.dicts
            token = dict_state.token
            for t in tags_code:
                gd.ensure(t)
    if dict_reset:
        # this query rebuilds every dictionary and remap LUT from nothing,
        # and its new token orphans what the caches hold under the old one
        obs_metrics.global_meter().counter_add("dict_state_resets")
    elif dict_over_bound:
        obs_metrics.global_meter().counter_add("dict_state_kept")

    gather_key = None
    if dict_state is not None and sources and all(
        s.cache_key is not None for s in sources
    ):
        gather_key = (
            "gather",
            token,
            tuple(s.cache_key for s in sources),
            request.time_range.begin_millis,
            request.time_range.end_millis,
            tuple(sorted(tags_code)),
            tuple(sorted(fields)),
        )

    # span tags of the gather that runs (none on a serving-cache hit),
    # and the remap tables it used for the group-by tags
    gather_tags: dict = {}
    gather_luts: dict = {t: [] for t in group_tags}

    def _do_gather():
        return _gather_rows(
            sources,
            sorted(tags_code),
            sorted(fields),
            gd,
            request.time_range.begin_millis,
            request.time_range.end_millis,
            dict_state=dict_state,
            tags_out=gather_tags,
            luts_out=gather_luts,
        )

    # opened BEFORE the work it covers; no child spans under it (its self
    # time is the benchmark's gather_ms): phases are tags + annotations
    g = span.child("gather") if span is not None else None
    t_gather0 = _time.perf_counter()
    if gather_key is not None:
        from banyandb_tpu.storage.cache import global_cache

        # hit / miss / refused (gathered, handed over, not retained)
        chunks_np, gather_cache = global_cache().fetch(gather_key, _do_gather)
    else:
        chunks_np, gather_cache = _do_gather(), "off"
    dict_live_share = None
    if dict_state is not None:
        with dict_state.lock:
            # judged from the sizes the gather LEFT (the first query of a
            # process and the one after a reset fill an empty state);
            # under the bound a few len() and a compare, no count taken
            size, live = _group_space(dict_state, group_tags)
            if dict_state.dicts is gd and size > _MAX_PERSISTENT_GROUPS:
                if gather_cache != "hit":  # a hit ran no gather: no reading
                    dict_state.note_live(gather_luts)
                    live = _group_space(dict_state, group_tags)[1]
                dict_live_share = round(100.0 * live / size, 3)
    gather_ms = (_time.perf_counter() - t_gather0) * 1000
    _H_GATHER.observe(gather_ms)
    n = chunks_np["ts"].shape[0]
    if g is not None:
        g.finish()
        g.tag("rows", int(n)).tag("sources", len(sources)).tag(
            "serving_cache", gather_cache
        ).tag("dict_reset", dict_reset).tag("dict_over_bound", dict_over_bound)
        if dict_live_share is not None:
            g.tag("dict_live_share", dict_live_share)
        for key, value in gather_tags.items():
            g.tag(key, value)
    # --- plan signature ---------------------------------------------------
    # everything between the gather and the reduce, under one span with no
    # child (its self time is the benchmark's signature_ms): the epoch,
    # the predicate values, the spec, the precompile registry, the
    # histogram range, the partials key; phases are tags
    sig = span.child("signature") if span is not None else None
    # epoch = global min ts keeps chunk-relative int32 offsets
    # nonnegative for the scan-order key; spans >= 2^31 ms (~24.8 days)
    # would wrap the int32 cast, so rep tracking degrades to canonical
    # ordering there instead of silently corrupting.  Only a plan that
    # tracks scan order ships ts (fused_exec.key_columns): no other
    # reads the epoch, and none pays its two passes over ts
    t_phase0 = _time.perf_counter()
    epoch = 0
    if want_rep and n:
        epoch = int(chunks_np["ts"].min())
        if int(chunks_np["ts"].max()) - epoch >= 2**31:
            want_rep = rep_desc = False
            rep_tags = ()
    epoch_ms = (_time.perf_counter() - t_phase0) * 1000

    # All gd reads happen under the DictState lock (concurrent queries
    # mutate the same dicts); group value lists are snapshotted here for
    # the decode step below.
    import contextlib

    pred_specs = []
    pred_vals: dict[str, jax.Array] = {}
    t_phase0 = _time.perf_counter()
    with dict_state.lock if dict_state is not None else contextlib.nullcontext():
        for i, c in enumerate(conds):
            if c.op in range_ops or c.op == "match":
                # LUT predicates (range / MATCH): op(dict_value, literal)
                # evaluated host-side per global code -> bool LUT gathered
                # on device (64-bit tag values and analyzer tokenization
                # never reach the int32 kernel).  Shared with the raw row
                # path (query/filter.py) so host and device semantics
                # cannot drift.
                from banyandb_tpu.query.filter import match_lut, range_lut

                vals = gd.values(c.name)
                if c.op == "match":
                    lut = match_lut(c, analyzers, vals)
                else:
                    lut = range_lut(
                        c.op, c.value, vals, measure.tag(c.name).type
                    )
                if not len(lut):
                    lut = np.zeros(1, dtype=bool)
                pred_specs.append(_PredSpec("lut", c.name, c.op, nvals=len(lut)))
                pred_vals[f"p{i}"] = jnp.asarray(lut)
            elif c.op in ("in", "not_in"):
                vals = [gd.code_of(c.name, _tag_value_bytes(v)) for v in c.value]
                arr = np.asarray(vals or [-1], dtype=np.int32)
                pred_specs.append(_PredSpec("code", c.name, c.op, nvals=len(arr)))
                pred_vals[f"p{i}"] = jnp.asarray(arr)
            else:
                code = gd.code_of(c.name, _tag_value_bytes(c.value))
                pred_specs.append(_PredSpec("code", c.name, c.op))
                pred_vals[f"p{i}"] = jnp.int32(code)

        radices = tuple(gd.size(t) for t in group_tags)
        if dict_state is not None and dict_state.dicts is gd:
            group_values = {
                t: dict_state.values_snapshot(t) for t in group_tags
            }
        else:
            group_values = {t: gd.values(t) for t in group_tags}
    preds_ms = (_time.perf_counter() - t_phase0) * 1000
    if g is not None:
        # what this query waited for DictState.lock up to here: the three
        # acquisitions above and the gather's (one a source a tag)
        lock_waited = (
            dict_state.lock.waited_s() - lock_waited0
            if dict_state is not None
            else 0.0
        )
        g.tag("dict_lock_wait_ms", round(lock_waited * 1000, 3))
    num_groups = 1
    for r in radices:
        num_groups *= r

    want_percentile = bool(agg and agg.function == "percentile")
    hist_field = agg.field_name if want_percentile else ""
    # the quantiles the device inverts the histogram for, where the
    # partial is final alone; () keeps the histogram
    ranks_q = tuple(agg.quantiles or (0.5,)) if want_percentile and final else ()
    # min/max always computed when percentile (field_stats feed the
    # distributed two-pass range agreement).
    want_minmax = not agg or agg.function in ("min", "max") or want_percentile

    nrows = SCAN_CHUNK if n > SCAN_CHUNK else _scan_bucket(max(n, 1))
    # planner group-method override (query/planner): applied ONLY when
    # the estimate lands on the other side of the hash/sort crossover
    # from the static radix product — the common case keeps "auto" so
    # the plan signature (jit cache, precompile store, kernel budgets)
    # is exactly the pre-planner one.  Methods are bit-identical within
    # the span bound (ops/groupby contract), so BYDB_PLANNER=0/1 result
    # JSON stays byte-identical.
    group_method = "auto"
    if plan_hints is not None and plan_hints.group_method:
        group_method = plan_hints.group_method
    if plan_hints is not None:
        plan_hints.actual_rows = int(n)
    spec = PlanSpec(
        tags_code=tuple(sorted(tags_code)),
        fields=tuple(sorted(fields)),
        preds=tuple(pred_specs),
        group_tags=group_tags,
        radices=radices,
        num_groups=max(num_groups, 1),
        want_minmax=want_minmax,
        hist_field=hist_field,
        nrows=nrows,
        group_method=group_method,
        expr=expr,
        want_rep=want_rep,
        rep_desc=rep_desc,
    )
    # function-local import: precompile imports this module's builders
    from banyandb_tpu.query.precompile import default_registry

    # the (group, measure) context turns this anonymous signature into
    # autoreg evidence (query/planner.signature_from_spec)
    default_registry().record(
        "measure", spec, context=(measure.group, measure.name)
    )

    # --- histogram range from host stats (two-pass percentile) ------------
    if hist_range is not None:
        hist_lo, hist_span = hist_range
    elif want_percentile and n:
        fv = chunks_np["fields"][hist_field]
        hist_lo = float(fv.min())
        hist_span = max(float(fv.max()) - hist_lo, 1e-6)
    else:
        hist_lo, hist_span = 0.0, 1.0

    # --- partials-level serving cache -------------------------------------
    # Repeat queries over unchanged sources (the dashboard pattern) skip
    # the whole reduction: the cache key pins the gathered snapshot
    # (gather_key covers source identities + time range + dict token),
    # the compiled plan signature, and every predicate VALUE.
    partials_key = None
    if gather_key is not None:
        import hashlib as _hl

        h = _hl.blake2b(digest_size=16)
        for pk in sorted(pred_vals):
            h.update(pk.encode())
            h.update(np.asarray(pred_vals[pk]).tobytes())
        partials_key = (
            "partials",
            gather_key,
            spec,
            # rep_tags are NOT part of the kernel signature (the kernel
            # only tracks the representative ROW; decode happens host-
            # side), so they must pin the cache entry separately — a
            # projection-free query must never serve a projecting one
            # cached partials with rep_vals=None
            rep_tags,
            round(hist_lo, 9),
            round(hist_span, 9),
            # a partial that holds ranks answers these quantiles alone
            # and no caller that combines
            ranks_q,
            h.hexdigest(),
        )

    if sig is not None:
        sig.tag("epoch_ms", round(epoch_ms, 3)).tag(
            "preds_ms", round(preds_ms, 3)
        ).finish()
    rspan = span.child("reduce") if span is not None else None
    reduce_loaded: list = []

    def _reduce() -> Partials:
        reduce_loaded.append(1)
        return _reduce_partials(
            measure, chunks_np, conds, expr, pred_vals, spec,
            group_values, rep_tags, gd, dict_state,
            hist_lo, hist_span, want_percentile, epoch, gather_key, agg,
            span=rspan, plan_hints=plan_hints, ranks_q=ranks_q,
        )

    try:
        if partials_key is not None:
            from banyandb_tpu.storage.cache import global_cache

            return global_cache().get_or_load(partials_key, _reduce)
        return _reduce()
    finally:
        if rspan is not None:
            rspan.tag(
                "partials_cache",
                ("off" if partials_key is None else "miss")
                if reduce_loaded
                else "hit",
            )
            if not reduce_loaded:  # replayed: no device leg ran
                rspan.tag("device_ms", 0.0).tag(
                    "host_ms", round(rspan.duration_ms, 3)
                )
            rspan.finish()
            # the gathered rows go back to the allocator under a span of
            # their own: left to this frame's teardown, a gather the
            # serving cache did not keep (~95 MB) costs 2.5 - 4.3 ms between
            # `reduce` and `merge`, in no span (PERF.md section 6, PR 37)
            with span.child("release"):
                chunks_np = None


def _reduce_partials(
    measure,
    chunks_np,
    conds,
    expr,
    pred_vals,
    spec,
    group_values,
    rep_tags,
    gd,
    dict_state,
    hist_lo,
    hist_span,
    want_percentile,
    epoch,
    gather_key,
    agg,
    span=None,
    plan_hints=None,
    ranks_q: tuple = (),
):
    """The reduction tail of compute_partials (cacheable unit).

    `span` gets the device/host attribution tags: device_ms is the time
    spent at the two accelerator boundaries (dispatch_ms: the jitted
    calls returning; get_ms: the batched device_get, partials_bytes what
    it brought back), host_ms the rest of the reduction (absorb_ms of
    it the f64 fold of the chunks' partials), all summed over the
    scan's chunk batches.  Its
    `decode` child is open while chunks are padded and shipped
    (pack_ms + h2d_ms = its host_ms).  A percentile plan's tags say
    where its histogram was: hist_groups (G), hist_device_bytes (the
    int32 [G, 512] the program carried) and hist_fetched_bytes (of it,
    what the gets brought back: all of it once where partials combine,
    none where ``ranks_q`` names the quantiles it is inverted for)."""
    import contextlib
    import time as _time

    t_reduce0 = _time.perf_counter()
    n = chunks_np["ts"].shape[0]
    group_tags = spec.group_tags
    radices = spec.radices
    want_minmax = spec.want_minmax
    want_rep, rep_desc = spec.want_rep, spec.rep_desc
    # --- exact-f64 host path for FLOAT-field aggregation ------------------
    # The reference aggregates float64 fields in full f64 and its goldens
    # compare exactly (852.0409999999999 etc.); the device kernel's f32
    # partials cannot reproduce that.  Float aggregates therefore reduce
    # on host in f64 (vectorized bincount — still columnar, just not on
    # the accelerator); INT fields keep the device path (f32 partials
    # are exact to 2^24 per chunk and merge in f64).
    agg_is_float = False
    if agg and agg.function != "percentile":
        try:
            agg_is_float = measure.field(agg.field_name).type.name == "FLOAT"
        except KeyError:
            agg_is_float = False
    if agg_is_float and n:
        out = _host_float_partials(
            measure, None, _materialize_tag_codes(chunks_np, spec.tags_code),
            conds, expr, pred_vals, spec,
            group_values, rep_tags, gd, dict_state,
        )
        if span is not None:
            # exact-f64 host reduction: no device leg by design
            span.tag("path", "host_f64").tag("device_ms", 0.0).tag(
                "host_ms",
                round((_time.perf_counter() - t_reduce0) * 1000, 3),
            )
        return out

    # --- run chunks, combine partials ------------------------------------
    G = spec.num_groups
    count = np.zeros(G, dtype=np.float64)
    sums = {f: np.zeros(G, dtype=np.float64) for f in spec.fields}
    mins = {f: np.full(G, np.inf, dtype=np.float64) for f in spec.fields}
    maxs = {f: np.full(G, -np.inf, dtype=np.float64) for f in spec.fields}
    rep_ts_acc = rep_row_acc = None
    if want_rep:
        sentinel = -(2**62) if rep_desc else 2**62
        rep_ts_acc = np.full(G, sentinel, dtype=np.int64)
        rep_row_acc = np.full(G, sentinel, dtype=np.int64)

    # device scalars hoisted out of the chunk loop: rebuilding them per
    # chunk costs two convert_element_type dispatches each iteration
    # (~profiled third of warm query latency on many-chunk scans)
    hist_lo_dev = jnp.float32(hist_lo)
    hist_span_dev = jnp.float32(hist_span)
    dev_cache = None
    if gather_key is not None:
        from banyandb_tpu.storage.cache import device_cache

        dev_cache = device_cache()

    def _absorb(out: dict) -> None:
        """Fold ONE chunk's partials (already on host) into the f64
        accumulators — the host half of the precision contract."""
        nonlocal count, rep_ts_acc, rep_row_acc
        count += out["count"].astype(np.float64)
        for f in spec.fields:
            sums[f] += out["sums"][f].astype(np.float64)
            if want_minmax:
                mins[f] = np.minimum(mins[f], out["mins"][f])
                maxs[f] = np.maximum(maxs[f], out["maxs"][f])
        if rep_ts_acc is not None:
            rts = out["rep_ts"].astype(np.int64) + epoch
            rrow = out["rep_row"].astype(np.int64)
            if rep_desc:
                better = (rts > rep_ts_acc) | (
                    (rts == rep_ts_acc) & (rrow > rep_row_acc)
                )
            else:
                better = (rts < rep_ts_acc) | (
                    (rts == rep_ts_acc) & (rrow < rep_row_acc)
                )
            rep_ts_acc = np.where(better, rts, rep_ts_acc)
            rep_row_acc = np.where(better, rrow, rep_row_acc)

    # pack (pad) / h2d (ship) accumulation crosses into the pad worker
    # thread (fused_exec._stacked_chunks): plain list appends
    # (GIL-atomic), summed by the owner below — Span objects themselves
    # are single-owner and never touched off-thread
    pack_s: list = []
    h2d_s: list = []
    # whether the pad thunks ran or waited, one (off-CPU seconds, minor
    # faults) pair a thunk: read when this query's spans read their clocks
    pack_use: list | None = [] if span is not None and span.usage else None
    # (shipped, packed) bytes per batch: the tag and field columns, and
    # every array the batch padded and shipped (its key columns too)
    ship_stats: list = []

    chunk_spans = []
    for start in range(0, max(n, 1), spec.nrows):
        end = min(start + spec.nrows, n)
        if end <= start:
            break
        chunk_spans.append((start, end))

    # The one executor (query/fused_exec): the per-chunk body scans over
    # a stacked [C, nrows] batch inside ONE program — one dispatch in,
    # one batched device_get out — and the per-chunk partials come back
    # stacked for the f64 absorb loop.  A scan over the device budget
    # runs the same program in consecutive chunk batches, strictly one
    # after another (pad, ship, dispatch, get, absorb, next): two
    # resident batches would be twice the budget the split respects.
    from banyandb_tpu.query import fused_exec

    leg = DeviceLeg()  # time at the accelerator boundaries, all batches
    # opened BEFORE the pad + ship work it covers, tagged after; finished
    # when the last batch's inputs are on the device
    dspan = span.child("decode") if span is not None else None
    # planner hint (query/planner): min_bucket rounds the chunk-count
    # bucket UP to the estimate's bucket (padding chunks are fully
    # invalid and skipped on the device — byte-identical, one compiled
    # program for a part population oscillating around a bucket
    # boundary)
    bucket, batches = fused_exec.plan_batches(
        spec,
        chunk_spans,
        min_bucket=plan_hints.chunk_bucket if plan_hints is not None else None,
    )
    cache_tags = []
    absorb_s = 0.0  # the host fold of the chunks' partials, all batches
    # a percentile plan's histogram stays on the device from batch to
    # batch; the last batch brings it back, or inverts it for ranks_q
    hist_dev = None
    whole: dict = {}
    quantiles_dev = (
        jnp.asarray(np.asarray(ranks_q, np.float32)) if ranks_q else None
    )
    for i, batch in enumerate(batches):
        last = i == len(batches) - 1
        moved_chunks, whole, hist_dev, cache_tag = fused_exec.run_fused(
            chunks_np,
            batch,
            spec,
            pred_vals,
            hist_lo_dev,
            hist_span_dev,
            epoch,
            num_chunks=bucket,
            leg=leg,
            gather_key=gather_key,
            dev_cache=dev_cache,
            pack_s=pack_s,
            h2d_s=h2d_s,
            ship_stats=ship_stats,
            decode_span=dspan if last else None,
            pack_use=pack_use,
            hist=hist_dev,
            quantiles=quantiles_dev if last else None,
            fetch_hist=last and not ranks_q,
        )
        cache_tags.append(cache_tag)
        t_absorb0 = _time.perf_counter()
        for moved in moved_chunks:
            _absorb(moved)
        absorb_s += _time.perf_counter() - t_absorb0
    if dspan is not None and not batches:
        dspan.finish()  # an empty scan pads and ships nothing
    device_s = leg.device_s
    _H_DEVICE.observe(device_s * 1000)
    # the group-by method every chunk of this reduction ran (never "auto")
    # and the rows it was given: gathered rows, before the predicate
    group_method = ops.groupby.resolve_group_method(
        spec.group_method, spec.nrows, spec.num_groups
    )
    # chunks the device branched past: the padding of the chunk-count
    # bucket, still padded and shipped by the host (fused_exec)
    chunks_skipped = bucket * len(batches) - len(chunk_spans)
    if chunk_spans:
        meter = obs_metrics.global_meter()
        meter.counter_add(
            "group_reduce_rows", float(n), labels={"method": group_method}
        )
        meter.counter_add(
            "fused_chunks", float(len(chunk_spans)), labels={"kind": "run"}
        )
        meter.counter_add(
            "fused_chunks", float(chunks_skipped), labels={"kind": "skipped"}
        )
        # plans by whether their program tracked scan order (bydb.rep)
        meter.counter_add(
            "plans_scan_order",
            labels={"mode": "tracked" if want_rep else "skipped"},
        )
        # the per-row key columns the batches padded and shipped, and
        # those they left out because the program reads none of them
        shipped_keys = fused_exec.key_columns(spec)
        for column in ("ts", "series", "row"):
            meter.counter_add(
                "fused_key_columns",
                labels={
                    "column": column,
                    "mode": "shipped" if column in shipped_keys else "skipped",
                },
            )
    # -- decode stage attribution (ROADMAP item 3) ------------------------
    # host half = narrow pack + pad (pack_s) + H2D ship (h2d_s): column
    # j+1 pads while column j ships under BYDB_PIPELINE; the device half
    # (widen/remap/f32 convert) is fused into the plan dispatch and is
    # deliberately part of device_execute.
    pack_ms = sum(pack_s) * 1000
    h2d_ms = sum(h2d_s) * 1000
    decode_ms = pack_ms + h2d_ms
    shipped_bytes = sum(s for s, _ in ship_stats)
    packed_bytes = sum(p for _, p in ship_stats)
    _H_DECODE.observe(decode_ms)
    if ship_stats:
        obs_metrics.global_meter().counter_add(
            "decode_ship_bytes", float(shipped_bytes)
        )
    if dspan is not None:
        dspan.tag("host_ms", round(decode_ms, 3)).tag(
            "pack_ms", round(pack_ms, 3)
        ).tag("h2d_ms", round(h2d_ms, 3)).tag(
            "shipped_bytes", shipped_bytes
        ).tag("packed_bytes", packed_bytes)
        if pack_use is not None:
            # of pack_ms, what the pad thread did not run (it waited for
            # the interpreter), and its minor page faults in the thunks
            dspan.tag(
                "pack_off_cpu_ms", round(sum(w for w, _ in pack_use) * 1000, 3)
            ).tag("pack_minflt", sum(f for _, f in pack_use))
    # --- dense [G] arrays -> nonempty-group records (codes stay dense
    # int32 rows; value tuples materialize lazily, Partials.groups) -------
    if group_tags:
        nz = np.nonzero(count > 0)[0]
        codes = (
            np.stack(np.unravel_index(nz, radices), axis=1).astype(np.int32)
            if len(nz)
            else np.zeros((0, len(group_tags)), np.int32)
        )
    else:
        nz = np.asarray([0])
        codes = np.zeros((1, 0), np.int32)
    hist = ranks = None
    if want_percentile and ranks_q:
        ranks = (
            whole["ranks"][nz]
            if "ranks" in whole
            else np.zeros((len(nz), len(ranks_q), 3), np.int32)
        )
        _settle_ranks(ranks, count[nz], ranks_q, nz, hist_dev, leg)
    elif want_percentile:
        hist = (
            whole["hist"].reshape(G, _NUM_HIST_BUCKETS)[nz].astype(np.float64)
            if "hist" in whole
            else np.zeros((len(nz), _NUM_HIST_BUCKETS), np.float64)
        )
    hist_dev = None
    if want_percentile and chunk_spans:
        device_bytes = G * _NUM_HIST_BUCKETS * 4
        meter = obs_metrics.global_meter()
        meter.counter_add(
            "percentile_hist_bytes", float(leg.hist_bytes),
            labels={"where": "fetched"},
        )
        meter.counter_add(
            "percentile_hist_bytes", float(device_bytes - leg.hist_bytes),
            labels={"where": "kept"},
        )
        if span is not None:
            span.tag("hist_groups", G).tag(
                "hist_device_bytes", device_bytes
            ).tag("hist_fetched_bytes", leg.hist_bytes)
    if span is not None:
        total_ms = (_time.perf_counter() - t_reduce0) * 1000
        leg.tag(span)
        span.tag(
            "host_ms", round(max(total_ms - device_s * 1000, 0.0), 3)
        ).tag("absorb_ms", round(absorb_s * 1000, 3)).tag(
            "chunks", len(chunk_spans)
        ).tag(
            "chunks_skipped", chunks_skipped
        ).tag("path", "fused").tag("dispatches", len(batches)).tag(
            "group_method", group_method
        ).tag("groups", spec.num_groups).tag(
            "scan_order_tracked", int(want_rep)
        )
        if device_s > 0:
            span.tag("rows_per_ms", round(n / (device_s * 1000), 3))
        if dev_cache is not None and batches:
            span.tag(
                "device_cache", "built" if "built" in cache_tags else "hit"
            )

    rep_key = None
    if rep_ts_acc is not None:
        # [K, 2] (absolute ts, row) scan-order key, compared
        # lexicographically; row is only a local tie-break (cross-node
        # combines compare ts first, which is what first-appearance
        # ordering needs)
        rep_key = np.stack([rep_ts_acc[nz], rep_row_acc[nz]], axis=1)
    rep_vals = None
    if rep_tags and rep_key is not None and len(nz):
        # decode each group's representative row into the gathered cols
        rows = np.clip(rep_key[:, 1], 0, max(n - 1, 0))
        with dict_state.lock if dict_state is not None else contextlib.nullcontext():
            rep_vals = {}
            for t in rep_tags:
                vals_list = gd.values(t)
                varr = np.asarray(vals_list, dtype=object)
                rep_codes_t = _host_tag_codes(chunks_np, t, rows)
                rep_vals[t] = varr[rep_codes_t].tolist()
    elif rep_tags:
        rep_vals = {t: [] for t in rep_tags}
    field_stats = {}
    if want_minmax:
        for f in spec.fields:
            valid_groups = count > 0
            if valid_groups.any():
                field_stats[f] = (
                    float(mins[f][valid_groups].min()),
                    float(maxs[f][valid_groups].max()),
                )
    return Partials(
        group_tags=group_tags,
        codes=codes,
        group_values=group_values,
        count=count[nz],
        sums={f: sums[f][nz] for f in spec.fields},
        mins={f: mins[f][nz] for f in spec.fields},
        maxs={f: maxs[f][nz] for f in spec.fields},
        hist=hist,
        hist_lo=hist_lo,
        hist_span=hist_span,
        field_stats=field_stats,
        rep_key=rep_key,
        rep_desc=rep_desc,
        rep_vals=rep_vals,
        ranks=ranks,
        ranks_q=ranks_q if ranks is not None else (),
    )


def _settle_ranks(ranks, count, qs, rows, hist_dev, leg) -> None:
    """Hold the device's ranks [K, Q, 3] to the host's own: the host
    takes each rank ceil(q*N) in f64 from the group's count, as
    ``_invert_histogram`` does, and the device took it in f32, which can
    land one rank off where q*N lies within f32's rounding of a whole
    number.  A triple is the host's iff its bucket holds the host's rank
    (count below < rank <= count below + count in it); the groups whose
    triple does not are fetched as histogram rows (``rows`` index the
    device histogram ``hist_dev``, flat [G * 512]) and inverted here, in
    place."""
    total = count[:, None]
    target = _quantile_targets(total, qs)
    before, at = ranks[..., 1], ranks[..., 2]
    off = ((target <= before) | (target > before + at)) & (total > 0)
    redo = np.nonzero(off.any(axis=1))[0]
    if not redo.size:
        return
    at = jnp.asarray(rows[redo].astype(np.int32))
    # bdlint: disable=host-sync -- the histogram rows of the few groups
    # whose f32 rank missed the f64 one; no other query fetches any
    counts = jax.device_get(hist_dev.reshape(-1, _NUM_HIST_BUCKETS)[at])
    leg.hist_bytes += counts.nbytes
    _, _, hit, below, inside = _histogram_ranks(counts.astype(np.float64), qs)
    ranks[redo] = np.stack([hit, below, inside], axis=-1).astype(np.int32)


def _host_float_partials(
    measure,
    request,
    chunks: dict,
    conds,
    expr,
    pred_vals: dict,
    spec: PlanSpec,
    group_values: dict,
    rep_tags: tuple,
    gd: GlobalDicts,
    dict_state,
) -> Partials:
    """Exact-f64 reduction over the gathered columns (float agg fields).

    Mirrors the device kernel's semantics — same predicate LUT/code
    masks, same mixed-radix group keys, same scan-order representative —
    with numpy f64 arithmetic so float goldens compare exactly."""
    n = chunks["ts"].shape[0]
    G = spec.num_groups
    want_rep, rep_desc = spec.want_rep, spec.rep_desc

    def pred_mask(i: int) -> np.ndarray:
        p = spec.preds[i]
        col = chunks["tags_code"][p.name]
        v = np.asarray(pred_vals[f"p{i}"])
        if p.kind == "lut":
            m = len(v)
            ok = (col >= 0) & (col < m)
            return np.where(ok, v[np.clip(col, 0, m - 1)], False)
        if p.op in ("in", "not_in"):
            m = np.isin(col, v)
            return ~m if p.op == "not_in" else m
        return (col == v) if p.op == "eq" else (col != v)

    def eval_expr(node) -> np.ndarray:
        if node[0] == "p":
            return pred_mask(node[1])
        left, right = eval_expr(node[1]), eval_expr(node[2])
        return (left & right) if node[0] == "and" else (left | right)

    if spec.expr:
        mask = eval_expr(spec.expr)
    else:
        mask = np.ones(n, dtype=bool)
        for i in range(len(spec.preds)):
            mask &= pred_mask(i)

    if spec.group_tags:
        key = np.zeros(n, dtype=np.int64)
        for t, r in zip(spec.group_tags, spec.radices):
            key = key * r + chunks["tags_code"][t].astype(np.int64)
    else:
        key = np.zeros(n, dtype=np.int64)

    sel = np.nonzero(mask)[0]
    k = key[sel]
    count = np.bincount(k, minlength=G).astype(np.float64)
    sums = {}
    mins = {}
    maxs = {}
    for f in spec.fields:
        vals = chunks["fields"][f][sel].astype(np.float64)
        sums[f] = np.bincount(k, weights=vals, minlength=G)
        mn = np.full(G, np.inf, dtype=np.float64)
        mx = np.full(G, -np.inf, dtype=np.float64)
        np.minimum.at(mn, k, vals)
        np.maximum.at(mx, k, vals)
        mins[f] = mn
        maxs[f] = mx

    rep_ts_acc = rep_row_acc = None
    if want_rep:
        # sentinels ALWAYS initialized when rep is on — a zero-match
        # node must still ship rep arrays or combine_partials would
        # drop rep for the whole cluster result
        sentinel = -(2**62) if rep_desc else 2**62
        rep_ts_acc = np.full(G, sentinel, dtype=np.int64)
        rep_row_acc = np.full(G, sentinel, dtype=np.int64)
        if sel.size:
            ts_sel = chunks["ts"][sel]
            order = (
                np.lexsort((-sel, -ts_sel))
                if rep_desc
                else np.lexsort((sel, ts_sel))
            )
            uk, first = np.unique(k[order], return_index=True)
            rep_ts_acc[uk] = ts_sel[order][first]
            rep_row_acc[uk] = sel[order][first]

    if spec.group_tags:
        nz = np.nonzero(count > 0)[0]
        codes = (
            np.stack(np.unravel_index(nz, spec.radices), axis=1).astype(np.int32)
            if len(nz)
            else np.zeros((0, len(spec.group_tags)), np.int32)
        )
    else:
        nz = np.asarray([0])
        codes = np.zeros((1, 0), np.int32)
    rep_key = None
    if rep_ts_acc is not None:
        rep_key = np.stack([rep_ts_acc[nz], rep_row_acc[nz]], axis=1)
    rep_vals = None
    if rep_tags and rep_key is not None and len(nz):
        rows = np.clip(rep_key[:, 1], 0, max(n - 1, 0))
        import contextlib as _cl

        with dict_state.lock if dict_state is not None else _cl.nullcontext():
            rep_vals = {}
            for t in rep_tags:
                varr = np.asarray(gd.values(t), dtype=object)
                rep_vals[t] = varr[chunks["tags_code"][t][rows]].tolist()
    elif rep_tags:
        rep_vals = {t: [] for t in rep_tags}

    field_stats = {}
    nonempty = count > 0
    if nonempty.any():
        for f in spec.fields:
            field_stats[f] = (
                float(mins[f][nonempty].min()),
                float(maxs[f][nonempty].max()),
            )
    return Partials(
        group_tags=spec.group_tags,
        codes=codes,
        group_values=group_values,
        count=count[nz],
        sums={f: sums[f][nz] for f in spec.fields},
        mins={f: mins[f][nz] for f in spec.fields},
        maxs={f: maxs[f][nz] for f in spec.fields},
        hist=None,
        field_stats=field_stats,
        rep_key=rep_key,
        rep_desc=rep_desc,
        rep_vals=rep_vals,
    )


def _source_lut(
    src: ColumnData, tag: str, gd: GlobalDicts, dict_state: Optional[DictState]
) -> tuple[np.ndarray, int, Optional[tuple]]:
    """-> (local-code -> global-code LUT, cached by immutable part
    identity; the dictionary entries ``add_source`` walked to build it,
    0 when it came from ``dict_state.remaps``; its key there, None for a
    table ``remaps`` does not hold)."""
    d = src.dicts.get(tag, ())
    if dict_state is None:
        return gd.add_source(tag, list(d)), len(d), None
    if src.cache_key is None:
        with dict_state.lock:
            return gd.add_source(tag, list(d)), len(d), None
    # (source identity, tag, dict length): part dicts are immutable, but
    # memtable snapshots reuse one generation id while their dict grows
    # append-only — the length pins WHICH prefix this LUT covers, so a
    # grown dict gets a fresh (longer) LUT instead of a stale short one
    rk = (src.cache_key[1], tag, len(d))
    with dict_state.lock:
        if dict_state.dicts is not gd:
            # state was reset mid-query: codes from the old gd must not
            # enter the new remap cache
            return gd.add_source(tag, list(d)), len(d), None
        lut = dict_state.remaps.get(rk)
        if lut is not None:
            return lut, 0, rk
        lut = dict_state.remaps[rk] = gd.add_source(tag, list(d))
        return lut, len(d), rk


def _dedup_components(spans: list) -> list[list[int]]:
    """The groups of sources (ordinals into ``spans``, ascending) whose
    rows must go through the version dedup together.

    Two sources can hold the same (series, ts) key only if they have the
    same scope and their intervals intersect (storage/part.py KeySpan);
    a source with no span may collide with any other.  So the connected
    components of that relation partition the keys, and a component of
    ONE source that is ``unique`` has nothing to dedup: it is left out.
    Pairs are tested inside one scope only, a shard's handful of parts."""
    if any(sp is None for sp in spans):
        return [list(range(len(spans)))]
    root = list(range(len(spans)))

    def find(i: int) -> int:
        while root[i] != i:
            root[i] = i = root[root[i]]
        return i

    by_scope: dict[str, list[int]] = {}
    for i, sp in enumerate(spans):
        by_scope.setdefault(sp.scope, []).append(i)
    for members in by_scope.values():
        for k, a in enumerate(members):
            for b in members[k + 1 :]:
                if spans[a].interval.intersects(spans[b].interval):
                    root[find(a)] = find(b)
    groups: dict[int, list[int]] = {}
    for i in range(len(spans)):
        groups.setdefault(find(i), []).append(i)
    return [
        g for g in groups.values() if len(g) > 1 or not spans[g[0]].unique
    ]


def _gather_rows(
    sources: list[ColumnData],
    tags_code: list[str],
    fields: list[str],
    gd: GlobalDicts,
    begin_millis: int,
    end_millis: int,
    dict_state: Optional[DictState] = None,
    tags_out: Optional[dict] = None,
    luts_out: Optional[dict] = None,
) -> dict:
    """Concatenate sources with row-exact time filtering, global-code remap
    and version dedup (block pruning upstream is only block-granular).

    The dedup (max version per (series, ts)) runs only over sources whose
    keys may collide (_dedup_components); the rows and their order are
    those a dedup over everything gives.  ``gather_rows{dedup=skipped|
    sorted}`` counts the rows either way.

    ``tags_out`` (dict or None) receives the ``gather`` span's tags: the
    milliseconds of the four phases, each also a bare
    ``bydb:gather.<phase>`` annotation — ``select_ms`` (per-source time
    filter, the interval tests, column reads, remap), ``concat_ms``,
    ``dedup_ms`` (the component dedups: 0 when none ran) and ``take_ms``
    (the ``[keep]`` takes, skipped when no row was dropped, and the
    narrow-dtype scan) — ``proven_unique_share``, the percent of the
    selected rows that skipped the dedup, and, of ``select_ms``, the
    remap tables: ``lut_ms`` (time inside ``_source_lut``) and
    ``lut_entries`` (dictionary entries walked to build tables that
    ``dict_state.remaps`` did not hold; ``source_lut_entries`` counts the
    same entries on /metrics).

    ``luts_out`` (dict or None): for each tag it names, receives the
    ``(dict_state.remaps key or None, table)`` of every source's remap
    table, the input of ``DictState.note_live``.

    The snapshot keeps tag columns compressed: per-row narrow LOCAL
    codes (``tags_enc``), the per-source local->global LUTs
    (``tags_lut``) and a per-row source ordinal (``src_ord``); the
    widen + remap run on device inside the plan kernel
    (ops.decode.decode_chunk), and host paths that need global codes
    compose them with ``_host_tag_codes``.  Fields stay host-f64 (the
    exact host paths need them) but carry a ``fields_narrow`` dtype
    decision so the pad/ship stage can ship exact-int columns at
    i8/i16."""
    import time as _time

    from banyandb_tpu.storage import encoded as enc_mod

    ts_l, series_l = [], []
    ver_l: dict[int, np.ndarray] = {}  # of the sources that dedup
    tc_l: dict[str, list] = {t: [] for t in tags_code}
    lut_l: dict[str, list] = {t: [] for t in tags_code}
    ord_l: list = []
    f_l: dict[str, list] = {f: [] for f in fields}
    lut_s, lut_entries = 0.0, 0
    if luts_out is None:
        luts_out = {}
    t_select0 = _time.perf_counter()
    with tracer.annotate("gather.select"):
        selected = []  # (source, its rows in range, how many)
        for src in sources:
            if src.ts.size == 0:
                continue
            rng = (src.ts >= begin_millis) & (src.ts < end_millis)
            nsel = int(np.count_nonzero(rng))
            if nsel:
                selected.append((src, rng, nsel))
        comps = _dedup_components([sel[0].key_span for sel in selected])
        dedups = {i for comp in comps for i in comp}
        for n_src, (src, rng, nsel) in enumerate(selected):
            ts_l.append(src.ts[rng])
            series_l.append(src.series[rng])
            if n_src in dedups:
                ver_l[n_src] = src.version[rng]
            ord_l.append(np.full(nsel, n_src, dtype=enc_mod.SRC_ORD_DTYPE))
            for t in tags_code:
                col = src.tags.get(t)
                if col is None:
                    # Source predates this tag (schema evolution): its rows all
                    # carry the empty value, same convention as merge/raw
                    # paths.
                    if dict_state is not None:
                        with dict_state.lock:
                            absent = gd.absent_code(t)
                    else:
                        absent = gd.absent_code(t)
                    # a one-entry LUT row and local code 0 everywhere:
                    # the remap lands the global absent code
                    absent_lut = np.asarray([absent], dtype=np.int32)
                    tc_l[t].append(np.zeros(nsel, dtype=np.int8))
                    lut_l[t].append(absent_lut)
                    if t in luts_out:
                        luts_out[t].append((None, absent_lut))
                else:
                    t_lut0 = _time.perf_counter()
                    lut, walked, rk = _source_lut(src, t, gd, dict_state)
                    lut_s += _time.perf_counter() - t_lut0
                    lut_entries += walked
                    if t in luts_out:
                        luts_out[t].append((rk, lut))
                    if lut.size:
                        w = enc_mod.code_dtype(lut.size)
                        tc_l[t].append(col[rng].astype(w, copy=False))
                        lut_l[t].append(lut)
                    else:
                        tc_l[t].append(np.zeros(nsel, dtype=np.int8))
                        lut_l[t].append(np.zeros(1, dtype=np.int32))
            for f in fields:
                col = src.fields.get(f)
                if col is None:
                    f_l[f].append(np.zeros(nsel, dtype=np.float64))
                else:
                    f_l[f].append(col[rng])
        del selected  # the masks
    select_s = _time.perf_counter() - t_select0
    obs_metrics.global_meter().counter_add(
        "source_lut_entries", float(lut_entries)
    )
    if tags_out is not None:
        tags_out["lut_ms"] = round(lut_s * 1000, 3)
        tags_out["lut_entries"] = lut_entries

    if not ts_l:
        if tags_out is not None:
            tags_out["select_ms"] = round(select_s * 1000, 3)
        return dict(
            ts=np.zeros(0, np.int64),
            series=np.zeros(0, np.int64),
            fields={f: np.zeros(0, np.float64) for f in fields},
            tags_enc={t: np.zeros(0, np.int8) for t in tags_code},
            tags_lut={t: () for t in tags_code},
            src_ord=np.zeros(0, enc_mod.SRC_ORD_DTYPE),
            fields_narrow={f: np.dtype(np.int8) for f in fields},
        )

    # Same order of allocations as ever: each column is concatenated and
    # taken in one expression, so its temporary is freed before the next
    # is made.  Holding every concatenated column across the dedup cost
    # 88 ms a query on the chip host (PERF.md, PR 25): the dedup's own
    # temporaries then came from fresh pages instead of reused ones.
    concat_s = take_s = 0.0

    def cat(parts: list) -> np.ndarray:
        nonlocal concat_s
        t0 = _time.perf_counter()
        with tracer.annotate("gather.concat"):
            out = np.concatenate(parts)
        concat_s += _time.perf_counter() - t0
        return out

    def take(col: np.ndarray) -> np.ndarray:
        # no row dropped: `col` is cat's fresh array, not a copy of it
        nonlocal take_s
        t0 = _time.perf_counter()
        with tracer.annotate("gather.take"):
            out = col if keep is None else col[keep]
        take_s += _time.perf_counter() - t0
        return out

    ts = cat(ts_l)
    series = cat(series_l)
    n = ts.shape[0]
    # every source in one component: the dedup over everything, as ever
    whole = len(comps) == 1 and len(comps[0]) == len(ts_l)
    version = cat([ver_l[i] for i in comps[0]]) if whole else None
    t0 = _time.perf_counter()
    with tracer.annotate("gather.dedup"):
        if whole:
            keep = hostops.dedup_max_version(series, ts, version)
            if keep.shape[0] == n:
                keep = None
        else:
            offs = np.cumsum([0] + [p.shape[0] for p in ts_l])
            kept = None  # row mask, made when a component drops a row
            for comp in comps:
                k = hostops.dedup_max_version(
                    np.concatenate([series_l[i] for i in comp]),
                    np.concatenate([ts_l[i] for i in comp]),
                    np.concatenate([ver_l[i] for i in comp]),
                )
                if k.shape[0] == sum(ts_l[i].shape[0] for i in comp):
                    continue
                if kept is None:
                    kept = np.ones(n, dtype=bool)
                # the component's rows, as positions in the concat
                pos = np.concatenate(
                    [
                        np.arange(offs[i], offs[i + 1], dtype=np.int64)
                        for i in comp
                    ]
                )
                kept[pos] = False
                kept[pos[k]] = True
            keep = None if kept is None else np.flatnonzero(kept)
    dedup_s = _time.perf_counter() - t0
    del version
    n_dedup = sum(ts_l[i].shape[0] for i in dedups)

    out = dict(
        ts=take(ts),
        series=take(series),
        fields={f: take(cat(f_l[f])) for f in fields},
        # mixed per-source widths promote to the widest (np.concatenate's
        # int promotion), values untouched
        tags_enc={t: take(cat(tc_l[t])) for t in tags_code},
        tags_lut={t: tuple(lut_l[t]) for t in tags_code},
        src_ord=take(cat(ord_l)),
    )
    t0 = _time.perf_counter()
    out["fields_narrow"] = {
        f: enc_mod.narrow_int_dtype(out["fields"][f]) for f in fields
    }
    take_s += _time.perf_counter() - t0
    meter = obs_metrics.global_meter()
    meter.counter_add("gather_rows", n - n_dedup, labels={"dedup": "skipped"})
    meter.counter_add("gather_rows", n_dedup, labels={"dedup": "sorted"})
    if tags_out is not None:
        for phase, seconds in (
            ("select", select_s),
            ("concat", concat_s),
            ("dedup", dedup_s),
            ("take", take_s),
        ):
            tags_out[f"{phase}_ms"] = round(seconds * 1000, 3)
        tags_out["proven_unique_share"] = round(
            100.0 * (n - n_dedup) / n, 3
        )
    return out


def _host_tag_codes(
    cols: dict, tag: str, rows: Optional[np.ndarray] = None
) -> np.ndarray:
    """Global i32 codes for `tag` from a gathered snapshot (of its
    `rows`, or all), for the host paths that need values — the exact-f64
    float path, per-group representative rows and the mesh pack — by
    the same local->global LUT composition the device kernel applies."""
    codes = cols["tags_enc"][tag]
    src_ord = cols["src_ord"]
    if rows is not None:
        codes = codes[rows]
        src_ord = src_ord[rows]
    luts = cols["tags_lut"][tag]
    if not luts:
        return np.zeros(codes.shape[0], dtype=np.int32)
    offs = np.zeros(len(luts), dtype=np.int64)
    np.cumsum([len(lu) for lu in luts[:-1]], out=offs[1:])
    flat = np.concatenate([np.asarray(lu, np.int32) for lu in luts])
    return flat[offs[src_ord] + codes].astype(np.int32)


def _materialize_tag_codes(cols: dict, tags: Sequence[str]) -> dict:
    """Snapshot with global i32 ``tags_code`` of `tags` added (host-path
    input)."""
    out = dict(cols)
    out["tags_code"] = {t: _host_tag_codes(cols, t) for t in tags}
    return out


def combine_partials(partials: list[Partials]) -> Partials:
    """The 'reduce' phase: merge node partials by group tuple.

    Vectorized (VERDICT r1 weak #4): the only per-group Python work is
    the group-tuple -> union-index dict build (one dict op per incoming
    group); all numeric accumulation is ufunc scatter (np.add.at /
    minimum.at / maximum.at) over whole arrays — at 100k groups this is
    C-speed instead of 5+ Python float ops per group per field per node.

    Histograms only combine when every contributing partial used the same
    (hist_lo, hist_span) — the distributed two-pass guarantees this.
    """
    if any(p.ranks is not None for p in partials):
        raise ValueError("inverted percentile partials do not combine")
    base = partials[0]
    want_hist = base.hist is not None
    want_rep = all(p.rep_key is not None for p in partials)
    rep_desc = base.rep_desc
    rep_tags = (
        sorted(base.rep_vals.keys())
        if all(p.rep_vals is not None for p in partials)
        else None
    )
    fields = sorted(base.sums.keys())

    index: dict[tuple, int] = {}
    maps: list[np.ndarray] = []
    for p in partials:
        if want_hist and (p.hist_lo != base.hist_lo or p.hist_span != base.hist_span):
            raise ValueError("histogram partials with mismatched ranges")
        idx = np.empty(len(p.groups), dtype=np.int64)
        for k, g in enumerate(p.groups):
            i = index.get(g)
            if i is None:
                i = index[g] = len(index)
            idx[k] = i
        maps.append(idx)

    K = len(index)
    count = np.zeros(K, dtype=np.float64)
    sums = {f: np.zeros(K, dtype=np.float64) for f in fields}
    mins = {f: np.full(K, np.inf, dtype=np.float64) for f in fields}
    maxs = {f: np.full(K, -np.inf, dtype=np.float64) for f in fields}
    hist = (
        np.zeros((K, _NUM_HIST_BUCKETS), dtype=np.float64)
        if want_hist
        else None
    )
    field_stats: dict[str, tuple[float, float]] = {}
    rep_key = (
        np.full((K, 2), -(2**62) if rep_desc else 2**62, dtype=np.int64)
        if want_rep
        else None
    )
    rep_vals = (
        {t: [None] * K for t in rep_tags} if rep_tags is not None else None
    )

    for p, idx in zip(partials, maps):
        np.add.at(count, idx, p.count)
        for f in fields:
            np.add.at(sums[f], idx, p.sums[f])
            np.minimum.at(mins[f], idx, p.mins[f])
            np.maximum.at(maxs[f], idx, p.maxs[f])
        if want_hist and p.hist is not None:
            np.add.at(hist, idx, p.hist)
        if rep_key is not None and p.rep_key is not None:
            # the scan-order winner's representative values follow its key
            for k, i in enumerate(idx.tolist()):
                pk = (int(p.rep_key[k, 0]), int(p.rep_key[k, 1]))
                cur = (int(rep_key[i, 0]), int(rep_key[i, 1]))
                better = pk > cur if rep_desc else pk < cur
                if better:
                    rep_key[i] = pk
                    if rep_vals is not None:
                        for t in rep_tags:
                            rep_vals[t][i] = p.rep_vals[t][k]
        for f, (lo, hi) in p.field_stats.items():
            old = field_stats.get(f)
            field_stats[f] = (
                min(lo, old[0]) if old else lo,
                max(hi, old[1]) if old else hi,
            )

    return Partials(
        group_tags=base.group_tags,
        groups=list(index.keys()),
        count=count,
        sums=sums,
        mins=mins,
        maxs=maxs,
        hist=hist,
        hist_lo=base.hist_lo,
        hist_span=base.hist_span,
        field_stats=field_stats,
        rep_key=rep_key,
        rep_desc=rep_desc,
        rep_vals=rep_vals,
    )


def finalize_partials(
    measure: Measure,
    request: QueryRequest,
    partials: list[Partials],
    dict_state: Optional[DictState] = None,
    span=None,
) -> QueryResult:
    """Combine + select + decode: the liaison-side tail of the query.

    `dict_state` (standalone fast path only) caches the per-tag rank LUTs
    that vectorize canonical group ordering."""
    import time as _time

    t_merge0 = _time.perf_counter()
    mspan = span.child("merge") if span is not None else None
    try:
        return _finalize_partials_inner(
            measure, request, partials, dict_state, mspan
        )
    finally:
        _H_MERGE.observe((_time.perf_counter() - t_merge0) * 1000)
        if mspan is not None:
            mspan.tag("partials", len(partials)).finish()


def _finalize_partials_inner(
    measure: Measure,
    request: QueryRequest,
    partials: list[Partials],
    dict_state: Optional[DictState],
    mspan,
) -> QueryResult:
    p = combine_partials(partials) if len(partials) != 1 else partials[0]
    if mspan is not None:
        mspan.tag("groups", len(p.count) if p.count is not None else 0)
    agg = request.agg
    group_tags = p.group_tags
    count = p.count
    nonempty = count > 0

    def agg_values(fn: str, field: str) -> np.ndarray:
        if fn == "count":
            return count
        if fn == "sum":
            return p.sums[field]
        if fn == "mean":
            return p.sums[field] / np.maximum(count, 1)
        if fn == "min":
            return p.mins[field]
        if fn == "max":
            return p.maxs[field]
        raise ValueError(f"unknown aggregate {fn}")

    result = QueryResult()
    if not group_tags:
        # One logical group, reported even when empty (global count == 0).
        group_ids = np.asarray([0]) if len(p.groups) else np.zeros(0, int)
        if not len(p.groups):
            p.groups = [()]
            count = np.zeros(1, dtype=np.float64)
            group_ids = np.asarray([0])
    else:
        # Canonical lexicographic order for group lists.  The dense
        # group-id layout is topology-dependent (dict-code order
        # standalone vs combine order in the cluster), so positional
        # order would (a) keep different groups per topology once LIMIT
        # truncates and (b) break prefix-stability between pages issued
        # with different limits.  A total order fixes both.  Top-N
        # queries skip it outright — selection below rebuilds group_ids
        # from the ranking metric.  The standalone codes path orders via
        # per-tag rank LUTs + np.lexsort (identical bytes order, no
        # O(G log G) Python compares); combined tuple partials keep the
        # Python key sort (the distributed combine plane's group count
        # crossed the wire already).
        group_ids = np.nonzero(nonempty)[0]
        if request.top:
            pass  # order irrelevant: Top-N selection replaces group_ids
        elif p.rep_key is not None and group_ids.size:
            # First-appearance scan order (the reference's groupLst:
            # groups emit in the order their first row appears in the
            # ts-asc — or ts-desc under ORDER BY time DESC — scan, i.e.
            # by per-group min/max (ts, row) key).
            k = p.rep_key[group_ids]
            if p.rep_desc:
                order = np.lexsort((-k[:, 1], -k[:, 0]))
            else:
                order = np.lexsort((k[:, 1], k[:, 0]))
            group_ids = group_ids[order]
        elif p.codes is not None and group_ids.size:
            keys = []
            for i, t in enumerate(group_tags):
                vals = p.group_values[t]
                lut = (
                    dict_state.rank_lut(t, vals)
                    if dict_state is not None
                    else _build_rank_lut(vals)
                )
                keys.append(lut[p.codes[group_ids, i]])
            group_ids = group_ids[np.lexsort(tuple(reversed(keys)))]
        else:
            group_ids = np.asarray(
                sorted(group_ids.tolist(), key=lambda i: p.groups[i]),
                dtype=int,
            )

    # Top-N selection narrows the group id set.  Ranking field is
    # top.field_name; the ranking function is the request's aggregate when
    # it composes (sum/count/min/max/mean), else mean (percentile ranks
    # don't compose across groups — reference TopN is mean-of-field too).
    if request.top:
        fn = (
            agg.function
            if agg and agg.function != "percentile" and agg.field_name == request.top.field_name
            else "mean"
        )
        metric = agg_values(fn, request.top.field_name)
        k = min(request.top.number, int(nonempty.sum()))
        if k <= 0 or metric.size == 0:
            group_ids = np.zeros(0, dtype=int)
        else:
            asc = request.top.field_value_sort == "asc"
            metric = np.where(nonempty, metric, np.inf if asc else -np.inf)
            order = np.argsort(metric if asc else -metric, kind="stable")[:k]
            # Only the k-th-value boundary ties decide MEMBERSHIP of the
            # top set; resolve exactly those by group key so selection is
            # replay-identical across topologies without paying a Python
            # sort over all G groups (vectorized argsort does the bulk).
            # Ties above the cut are ordered by group key too: the stable
            # argsort leaves them in code order, which depends on the
            # order the sources' dictionaries were met.
            kth_val = metric[order[k - 1]]
            head = sorted(
                (int(i) for i in order if metric[i] != kth_val),
                key=lambda i: (metric[i] if asc else -metric[i], p.group_key(i)),
            )
            tied = sorted(
                (
                    int(i)
                    for i in np.nonzero((metric == kth_val) & nonempty)[0]
                ),
                key=p.group_key,
            )
            group_ids = np.asarray(head + tied[: k - len(head)], dtype=int)

    # offset/limit paging over the (possibly top-N-ranked) group list —
    # offset semantics match the reference's QueryRequest.offset
    off = request.offset or 0
    if off:
        group_ids = group_ids[off:]
    group_ids = group_ids[: request.limit] if request.limit else group_ids

    # Decode group tuples (bytes) to client values via the schema types,
    # a column at a time: one gather and one comprehension a group tag.
    from banyandb_tpu.query import filter as qfilter

    ids = group_ids.tolist()
    if not group_tags:
        result.groups = [()] * len(ids)
    elif ids:
        cols = [
            qfilter.decode_tag_column(raw, measure.tag(t).type)
            for t, raw in zip(group_tags, p.group_columns(group_ids))
        ]
        result.groups = list(zip(*cols))
    if mspan is not None:
        mspan.tag("decoded_groups", len(result.groups))
    if p.rep_vals:
        # representative (first-scanned row) values for projected-but-
        # not-grouped tags, aligned with result.groups; None where a group
        # has none
        for t, vals in p.rep_vals.items():
            raw = _take(vals, ids)
            present = [v for v in raw if v is not None]
            decoded = iter(
                qfilter.decode_tag_column(present, measure.tag(t).type)
                if present
                else ()
            )
            result.rep_tags[t] = [None if v is None else next(decoded) for v in raw]

    if agg:
        if agg.function == "percentile":
            qs = list(agg.quantiles or (0.5,))
            # the host's part of the inversion: the whole of it over a
            # combined histogram, the f64 estimate over the device's ranks
            ispan = mspan.child("invert") if mspan is not None else None
            if p.ranks is not None:
                if tuple(qs) != tuple(p.ranks_q):
                    raise ValueError("ranks inverted for other quantiles")
                values = _invert_ranks(
                    p.ranks, count, group_ids, qs, p.hist_lo, p.hist_span
                )
            else:
                values = _invert_histogram(
                    p.hist, group_ids, qs, p.hist_lo, p.hist_span
                )
            if ispan is not None:
                ispan.tag("groups", len(group_ids)).finish()
            result.values[f"percentile({agg.field_name})"] = values
        else:
            v = agg_values(agg.function, agg.field_name)[group_ids]
            result.values[f"{agg.function}({agg.field_name})"] = v.tolist()
    result.values["count"] = count[group_ids].tolist()
    return result


def _quantile_targets(total: np.ndarray, qs) -> np.ndarray:
    """[K, 1] group counts -> [K, Q] f64 ranks of the quantiles:
    ceil(q*N) clamped to [1, N] so q=0 lands on the min-value bucket."""
    q = np.asarray(qs, dtype=np.float64)[None, :]  # [1, Q]
    return np.clip(np.ceil(q * total), 1.0, np.maximum(total, 1.0))


def _histogram_ranks(counts: np.ndarray, qs):
    """f64 [K, B] histograms -> (total [K, 1], target, hit bucket, count
    below it, count in it; [K, Q] each): the host half of
    ops.invert_histogram, in f64."""
    cdf = np.cumsum(counts, axis=1)  # [G, B]
    total = cdf[:, -1:]  # [G, 1]
    target = _quantile_targets(total, qs)
    hit = np.argmax(cdf[:, None, :] >= target[:, :, None], axis=2)  # [G, Q]
    cdf_at = np.take_along_axis(cdf, hit, axis=1)
    cnt_at = np.take_along_axis(counts, hit, axis=1)
    return total, target, hit, cdf_at - cnt_at, cnt_at


def _estimate(total, target, hit, prev, cnt_at, lo: float, span: float) -> list:
    """The quantile estimate, linear inside the hit bucket, as a list
    [K][Q]; an empty group reads `lo`."""
    width = span / _NUM_HIST_BUCKETS
    frac = np.where(cnt_at > 0, (target - prev) / np.maximum(cnt_at, 1.0), 0.0)
    est = lo + (hit + np.clip(frac, 0.0, 1.0)) * width
    est = np.where(total > 0, est, lo)
    return est.tolist()


def _invert_ranks(
    ranks: np.ndarray,
    count: np.ndarray,
    group_ids: np.ndarray,
    qs: list[float],
    lo: float,
    span: float,
) -> list[list[float]]:
    """The estimate from the device's ranks (``Partials.ranks``): the
    same f64 arithmetic as ``_invert_histogram``, on the same integers,
    so the same bytes."""
    ids = np.asarray(group_ids, dtype=np.int64)
    if ids.size == 0:
        return []
    total = count[ids][:, None]
    r = ranks[ids]
    return _estimate(
        total,
        _quantile_targets(total, qs),
        r[..., 0].astype(np.int64),
        r[..., 1].astype(np.float64),
        r[..., 2].astype(np.float64),
        lo,
        span,
    )


def _invert_histogram(
    hist: Optional[np.ndarray],
    group_ids: np.ndarray,
    qs: list[float],
    lo: float,
    span: float,
) -> list[list[float]]:
    """Vectorized CDF inversion over all selected groups at once — the
    same interpolation the device kernel uses
    (ops/percentile.py group_percentile_histogram), on [G, B] arrays
    instead of a per-group per-quantile Python loop."""
    ids = np.asarray(group_ids, dtype=np.int64)
    if ids.size == 0:
        return []
    if hist is None:
        return [[lo] * len(qs) for _ in range(ids.size)]
    valid = ids < len(hist)
    counts = np.zeros((ids.size, hist.shape[1]), dtype=np.float64)
    counts[valid] = hist[ids[valid]]
    return _estimate(*_histogram_ranks(counts, qs), lo, span)
