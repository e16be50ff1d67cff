"""Measure engine: metrics with tags + numeric fields per series.

Analog of banyand/measure (measure.go:81, write path tstable.go:333,
query path query.go:88) over the TPU-first substrate: writes land in
per-shard memtables routed by entity hash; queries gather memtable +
part columns and run the device executor (query/measure_exec.py).
"""

from __future__ import annotations

import dataclasses
import os
import time
from pathlib import Path
from typing import Optional

import numpy as np

from banyandb_tpu.api.model import (
    QueryRequest,
    QueryResult,
    WriteRequest,
)
from banyandb_tpu.api.schema import (
    FieldType,
    Measure,
    SchemaRegistry,
    TagType,
)
from banyandb_tpu.obs import metrics as obs_metrics
from banyandb_tpu.obs.tracer import NOOP_TRACER, Tracer
from banyandb_tpu.query import filter as qfilter
from banyandb_tpu.query import measure_exec
from banyandb_tpu.storage.memtable import MemTable
from banyandb_tpu.storage.part import ColumnData, KeySpan
from banyandb_tpu.storage.tsdb import TSDB
from banyandb_tpu.utils import hashing


_RAW_FIELD_TYPES = (FieldType.STRING, FieldType.DATA_BINARY)
_RAW_FIELD_PREFIX = "@f:"

# engine-level latency instrument (one per query engine; the other
# three live in their models/ modules) + part-gather stage attribution
_H_QUERY = obs_metrics.global_meter().histogram(
    "query_ms", {"engine": "measure"}
)
_H_PART_GATHER = obs_metrics.stage_histogram("part_gather")

# Server-assigned write versions are MONOTONIC per process (the
# reference assigns nanosecond timestamps per point): two writes of the
# same (series, ts) must resolve to the later one, even within one
# batch/millisecond.  A plain now()-per-batch ties and dedup picks
# arbitrarily.
import threading as _threading

_version_lock = _threading.Lock()
_version_base = time.time_ns()


def _next_versions(n: int) -> int:
    """Reserve n consecutive monotonic versions; returns the first."""
    global _version_base
    with _version_lock:
        start = _version_base
        _version_base += n
        return start


def _numeric_fields(m: Measure):
    return [f for f in m.fields if f.type not in _RAW_FIELD_TYPES]


def _tag_col_names(m: Measure) -> list[str]:
    """Schema tags + reserved raw-field columns, the storage tag layout."""
    return [t.name for t in m.tags] + [
        _RAW_FIELD_PREFIX + f.name for f in _raw_fields(m)
    ]


def _raw_fields(m: Measure):
    """STRING / DATA_BINARY fields: stored, projected, never aggregated.

    They ride the dictionary-encoded tag machinery under reserved
    '@f:<name>' column names (the part/memtable formats already handle
    arbitrary byte columns there), mirroring the reference's non-numeric
    field columns (FIELD_TYPE_STRING in pkg/test/measure/testdata)."""
    return [f for f in m.fields if f.type in _RAW_FIELD_TYPES]


def _raw_field_bytes(v) -> bytes:
    if v is None:
        return b""
    if isinstance(v, bytes):
        return v
    if isinstance(v, str):
        return v.encode()
    return str(v).encode()


class DictColumn:
    """A dictionary-encoded tag column: `values` (distinct tag values)
    + int `codes` per row.  The wire's columnar write envelope ships tag
    columns this way; keeping the encoding end-to-end (client -> bus ->
    engine -> memtable) means per-row Python work never happens on the
    ingest hot path — only per-DISTINCT-value work does."""

    __slots__ = ("values", "codes")

    def __init__(self, values: list, codes: np.ndarray):
        self.values = values
        self.codes = np.asarray(codes)

    def __len__(self) -> int:
        return len(self.codes)

    def __getitem__(self, i):
        # row-shaped access for the slow paths (index-mode, series docs)
        return self.values[int(self.codes[i])]

    def take(self, idx: np.ndarray) -> "DictColumn":
        return DictColumn(self.values, self.codes[idx])

    def row_values(self) -> list:
        """Materialized per-row value list (compat escape hatch)."""
        return np.asarray(self.values, dtype=object)[self.codes].tolist()


def series_ids_for_columns(
    name: str, ent_cols: list, n: int
) -> "tuple[np.ndarray, np.ndarray]":
    """Vectorized series-id assignment for columnar ingest: hash each
    DISTINCT entity tuple once.  ``ent_cols`` holds one column per
    entity tag, each a ``DictColumn`` of canonical bytes or a per-row
    bytes list.  -> (per-row series ids [n], unique-inverse index [n]).

    Shared by ``MeasureEngine.write_columns`` and the worker pool's
    shard router (cluster/workers.py) so in-process and multi-process
    ingest route every row to the same shard."""
    radix_prod = 1
    for c in ent_cols:
        if isinstance(c, DictColumn):
            radix_prod *= max(len(c.values), 1)
    if all(isinstance(c, DictColumn) for c in ent_cols) and (
        radix_prod < 2**62  # int64 mixed-radix key must not wrap
    ):
        # all-encoded fast lane: distinct entities are distinct
        # mixed-radix code keys — int unique, zero per-row Python
        key = np.zeros(n, dtype=np.int64)
        for c in ent_cols:
            key = key * len(c.values) + np.asarray(c.codes, dtype=np.int64)
        uk, inv = np.unique(key, return_inverse=True)
        radices = [len(c.values) for c in ent_cols]
        digits: list[np.ndarray] = []
        rem = uk
        for r in reversed(radices):
            digits.append(rem % r)
            rem = rem // r
        digits.reverse()  # per-entity-tag unique codes aligned with uk
        uniq_sids = np.fromiter(
            (
                hashing.series_id(
                    [name.encode()]
                    + [
                        ent_cols[j].values[int(digits[j][i])]
                        for j in range(len(ent_cols))
                    ]
                )
                for i in range(len(uk))
            ),
            dtype=np.int64,
            count=len(uk),
        )
    else:
        rowed = [
            c.row_values() if isinstance(c, DictColumn) else c
            for c in ent_cols
        ]
        ent_rows = np.empty(n, dtype=object)
        for i in range(n):
            ent_rows[i] = tuple(c[i] for c in rowed)
        uniq, inv = np.unique(ent_rows, return_inverse=True)
        uniq_sids = np.fromiter(
            (hashing.series_id([name.encode(), *e]) for e in uniq),
            dtype=np.int64,
            count=len(uniq),
        )
    return uniq_sids[inv], inv


class MeasureEngine:
    """All measure resources of all groups, one TSDB per group."""

    def __init__(self, registry: SchemaRegistry, root: str | Path):
        from banyandb_tpu.models.topn import TopNProcessorManager

        import threading

        self.registry = registry
        self.root = Path(root) / "measure"
        self._tsdbs: dict[str, TSDB] = {}
        self._tsdb_lock = threading.Lock()
        self._loops = None
        self.topn = TopNProcessorManager(self)
        # Serving-cache companions: persistent dictionaries + remaps per
        # measure (measure_exec.DictState), created lazily under the lock.
        self._dict_states: dict[tuple[str, str], measure_exec.DictState] = {}
        # Continuous streaming aggregation (query/streamagg.py): rolling
        # materialized windows for registered dashboard signatures,
        # updated at ingest and reloaded (with a deterministic part
        # backfill) across restarts.  Function-local import: the engines
        # layer reaches the executor layer lazily, like flush()'s
        # precompile hook.
        from banyandb_tpu.query.streamagg import StreamAggRegistry

        self.streamagg = StreamAggRegistry(self)

    def _dict_state(self, group: str, name: str) -> "measure_exec.DictState":
        key = (group, name)
        with self._tsdb_lock:
            st = self._dict_states.get(key)
            if st is None:
                st = self._dict_states[key] = measure_exec.DictState()
            return st

    def start_lifecycle(self, extra_tsdbs=None, **kw) -> None:
        """Start background flush/merge/retention (svc_standalone analog).

        extra_tsdbs: optional callable returning MORE TSDBs to manage —
        the stream/trace engines' trees, so parts installed there (e.g.
        via the liaison write queue) merge and retention-sweep too."""
        from banyandb_tpu.storage.loops import LifecycleLoops

        if self._loops is None:

            def all_tsdbs():
                out = list(self._tsdbs.values())
                if extra_tsdbs is not None:
                    out.extend(extra_tsdbs())
                return out

            self._loops = LifecycleLoops(all_tsdbs, **kw)
            self._loops.start()

    def stop_lifecycle(self) -> None:
        if self._loops is not None:
            self._loops.stop()
            self._loops = None

    def close(self) -> None:
        """Deterministic shutdown: stop the loops and release every
        TSDB's index memory and file handles (bdsan fd hygiene)."""
        self.stop_lifecycle()
        with self._tsdb_lock:
            dbs = list(self._tsdbs.values())
        for db in dbs:
            db.close()

    # -- plumbing ----------------------------------------------------------
    def _tsdb(self, group: str) -> TSDB:
        # Locked get-or-create: two racing creators would own duplicate
        # Shard objects over one directory (epoch collisions, lost writes).
        with self._tsdb_lock:
            db = self._tsdbs.get(group)
            if db is None:
                g = self.registry.get_group(group)
                # One memtable schema per group would be wrong — tag/field
                # sets differ per measure — so shards key their memtables
                # per measure.
                db = TSDB(
                    self.root,
                    group,
                    g.resource_opts,
                    mem_factory=lambda: _MultiMeasureMemtable(),
                )
                self._tsdbs[group] = db
            return db

    # -- write path (write_standalone.go analog) ---------------------------
    def write(self, req: WriteRequest, _internal: bool = False) -> int:
        m = self.registry.get_measure(req.group, req.name)
        db = self._tsdb(req.group)
        shard_num = self.registry.get_group(req.group).resource_opts.shard_num
        n = 0
        # streaming-aggregation hook rows (query/streamagg.py): only
        # collected when a materialized signature is registered for this
        # measure — the common case pays one frozenset lookup
        sa_rows = (
            []
            if not m.index_mode
            and self.streamagg.active(req.group, req.name)
            else None
        )
        # ingest gate (query/streamagg.py): ticket in before rows
        # become memtable-visible, out after the window observe — a
        # concurrent registration backfill drains these tickets before
        # it stops buffering, so pre-snapshot rows never double-apply
        self.streamagg.ingest_enter()
        try:
            for p in req.points:
                # Series identity is (measure, entity values) — two measures
                # sharing an entity tuple must not collide in the series index.
                entity = [req.name.encode()] + [
                    hashing.entity_bytes(p.tags[t]) for t in m.entity.tag_names
                ]
                sid = hashing.series_id(entity)
                seg = db.segment_for(p.ts_millis)
                version = p.version or _next_versions(1)
                tag_bytes = {
                    t.name: _tag_to_bytes(p.tags.get(t.name), t.type)
                    for t in m.tags
                }
                for f in _raw_fields(m):
                    tag_bytes[_RAW_FIELD_PREFIX + f.name] = _raw_field_bytes(
                        p.fields.get(f.name)
                    )
                field_vals = {
                    f.name: float(p.fields.get(f.name, 0))
                    for f in _numeric_fields(m)
                }
                if m.index_mode:
                    # Index-mode measures live entirely in the series index —
                    # one doc per data point (handleIndexMode,
                    # banyand/measure/write_standalone.go:348).
                    _index_mode_write(
                        seg, m, sid, p.ts_millis, version, tag_bytes, field_vals
                    )
                    n += 1
                    continue
                shard = hashing.shard_id(sid, shard_num)
                entity_tags = {t: tag_bytes[t] for t in m.entity.tag_names}
                entity_tags["@measure"] = req.name.encode()
                seg.series_index.insert_series(sid, entity_tags)
                seg.shards[shard].ingest(
                    lambda mem: mem.append_measure(
                        m.name,
                        _tag_col_names(m),
                        [f.name for f in _numeric_fields(m)],
                        p.ts_millis,
                        sid,
                        version,
                        tag_bytes,
                        field_vals,
                    )
                )
                n += 1
                if sa_rows is not None:
                    sa_rows.append(
                        (p.ts_millis, sid, version, shard, tag_bytes, field_vals)
                    )
                if not _internal:
                    self.topn.observe(m, p, sid=sid, version=version)
            if sa_rows:
                self._observe_streamagg_rows(m, sa_rows)
        finally:
            self.streamagg.ingest_exit()
        return n

    def _observe_streamagg_rows(self, m: Measure, rows: list) -> None:
        """Row-path bridge onto the columnar streamagg observe: rows are
        (ts, sid, version, shard, tag_bytes dict, field_vals dict)."""
        n = len(rows)
        ts = np.fromiter((r[0] for r in rows), np.int64, count=n)
        sids = np.fromiter((r[1] for r in rows), np.int64, count=n)
        vers = np.fromiter((r[2] for r in rows), np.int64, count=n)
        shards = np.fromiter((r[3] for r in rows), np.int64, count=n)
        self.streamagg.observe(
            m.group,
            m.name,
            ts=ts,
            series=sids,
            versions=vers,
            shards=shards,
            tag_col=lambda t: np.asarray(
                [r[4].get(t, b"") for r in rows], dtype=object
            ),
            field_col=lambda f: np.fromiter(
                (r[5].get(f, 0.0) for r in rows), np.float64, count=n
            ),
        )

    def write_points_bulk(self, req: WriteRequest) -> int:
        """Row-shaped request -> columnar ingest: the wire handlers'
        bridge onto write_columns.  One decode pass over the points
        builds columns; entity-tag presence is validated with the row
        path's strictness (missing entity tag raises KeyError rather
        than silently writing the empty value)."""
        m = self.registry.get_measure(req.group, req.name)
        pts = req.points
        n = len(pts)
        if n == 0:
            return 0
        ts = np.fromiter((p.ts_millis for p in pts), np.int64, count=n)
        v0 = _next_versions(n)
        versions = np.fromiter(
            ((p.version or (v0 + i)) for i, p in enumerate(pts)),
            np.int64,
            count=n,
        )
        tags = {t.name: [p.tags.get(t.name) for p in pts] for t in m.tags}
        for t in m.entity.tag_names:
            if any(v is None for v in tags[t]):
                raise KeyError(t)
        fields: dict[str, object] = {
            f.name: np.fromiter(
                (float(p.fields.get(f.name, 0)) for p in pts),
                np.float64,
                count=n,
            )
            for f in _numeric_fields(m)
        }
        for f in _raw_fields(m):
            fields[f.name] = [p.fields.get(f.name) for p in pts]
        return self.write_columns(
            req.group,
            req.name,
            ts_millis=ts,
            tags=tags,
            fields=fields,
            versions=versions,
        )

    def write_columns(
        self,
        group: str,
        name: str,
        *,
        ts_millis: np.ndarray,
        tags: dict[str, list],
        fields: dict[str, np.ndarray],
        versions: Optional[np.ndarray] = None,
    ) -> int:
        """Vectorized bulk ingest (the high-throughput write path).

        Row-oriented write() parses point protos one by one (the
        reference's gRPC streaming shape); collectors that already hold
        columns use this path: unique entities are hashed once, routing
        and interning are NumPy passes, and memtable appends are bulk
        extends.  Semantics match write() exactly — TopN rules observe
        bulk writes (topn.observe_columns) and index-mode measures take
        the per-doc index path — one write path, two decode shapes
        (ref single path banyand/measure/write_standalone.go:348).
        """
        m = self.registry.get_measure(group, name)
        db = self._tsdb(group)
        opts = self.registry.get_group(group).resource_opts
        shard_num = opts.shard_num
        iv_millis = opts.segment_interval.millis
        n = len(ts_millis)
        if n == 0:
            return 0
        versions = (
            versions
            if versions is not None
            else _next_versions(n) + np.arange(n, dtype=np.int64)
        )
        tag_bytes: dict[str, object] = {}
        for t in m.tags:
            vals = tags.get(t.name)
            # None elements map to the empty value, matching the row path.
            # DictColumn stays encoded: only its DISTINCT values pay the
            # bytes conversion.  Columns are validated here (lengths,
            # code bounds) because a ragged or out-of-range column that
            # reached the memtable would corrupt it permanently — the
            # wire envelope hands us client-controlled codes.
            if vals is None:
                tag_bytes[t.name] = None
            elif isinstance(vals, DictColumn):
                codes = np.asarray(vals.codes)
                if len(codes) != n:
                    raise ValueError(
                        f"tag {t.name}: {len(codes)} codes for {n} rows"
                    )
                if codes.size and (
                    int(codes.min()) < 0
                    or int(codes.max()) >= len(vals.values)
                ):
                    raise ValueError(
                        f"tag {t.name}: code out of range for dict of "
                        f"{len(vals.values)}"
                    )
                tag_bytes[t.name] = DictColumn(
                    [
                        hashing.entity_bytes(v) if v is not None else b""
                        for v in vals.values
                    ],
                    codes,
                )
            else:
                if len(vals) != n:
                    raise ValueError(
                        f"tag {t.name}: {len(vals)} values for {n} rows"
                    )
                tag_bytes[t.name] = [
                    hashing.entity_bytes(v) if v is not None else b""
                    for v in vals
                ]
        for f in m.fields:
            col = fields.get(f.name)
            if col is not None and len(col) != n:
                raise ValueError(
                    f"field {f.name}: {len(col)} values for {n} rows"
                )
        if len(versions) != n:
            raise ValueError(f"{len(versions)} versions for {n} rows")
        # raw (string/binary) fields ride the tag machinery ('@f:' cols)
        for f in _raw_fields(m):
            vals = fields.get(f.name)
            key = _RAW_FIELD_PREFIX + f.name
            if vals is None:
                tag_bytes[key] = None
            elif isinstance(vals, DictColumn):
                tag_bytes[key] = DictColumn(
                    [_raw_field_bytes(v) for v in vals.values], vals.codes
                )
            else:
                tag_bytes[key] = [_raw_field_bytes(v) for v in vals]
        num_fields = {
            f.name: fields.get(f.name) for f in _numeric_fields(m)
        }
        for t in m.entity.tag_names:
            if tag_bytes.get(t) is None:
                # row-path strictness: a missing entity tag is a client
                # error, not an empty value
                raise KeyError(t)

        # --- series ids: hash each DISTINCT entity tuple once -------------
        ent_cols = [tag_bytes[t] for t in m.entity.tag_names]
        sids, inv = series_ids_for_columns(name, ent_cols, n)
        shards = sids % shard_num

        seg_cache: dict[int, object] = {}

        def seg_for(start: int):
            seg = seg_cache.get(start)
            if seg is None:
                seg = seg_cache[start] = db.segment_for(start)
            return seg

        # --- route per (segment, shard) with boolean masks ----------------
        seg_starts = ts_millis - (ts_millis % iv_millis)
        if m.index_mode:
            # One index doc per point (handleIndexMode analog, same
            # semantics as the row path): the inverted index takes docs
            # one at a time, so the win here is upstream decode only.
            # Index-mode rows never feed TopN (row-path parity).
            for start in np.unique(seg_starts).tolist():
                seg = seg_for(int(start))
                for i in np.nonzero(seg_starts == start)[0].tolist():
                    _index_mode_write(
                        seg,
                        m,
                        int(sids[i]),
                        int(ts_millis[i]),
                        int(versions[i]),
                        {
                            t: (
                                tag_bytes[t][i]
                                if tag_bytes[t] is not None
                                else b""
                            )
                            for t in tag_bytes
                        },
                        {
                            f.name: (
                                float(np.asarray(num_fields[f.name])[i])
                                if num_fields.get(f.name) is not None
                                else 0.0
                            )
                            for f in _numeric_fields(m)
                        },
                    )
            return n
        self.streamagg.ingest_enter()  # see write(): backfill drain gate
        try:
            for start in np.unique(seg_starts).tolist():
                seg = seg_for(int(start))
                seg_mask = seg_starts == start
                # series registration is PER SEGMENT (each segment owns its own
                # series index, same as the row path): one doc per distinct
                # entity appearing in this segment
                seg_rows = np.nonzero(seg_mask)[0]
                first = np.unique(inv[seg_mask], return_index=True)[1]
                for row in seg_rows[first].tolist():
                    doc = {t: tag_bytes[t][row] for t in m.entity.tag_names}
                    doc["@measure"] = name.encode()
                    seg.series_index.insert_series(int(sids[row]), doc)
                for shard_idx in np.unique(shards[seg_mask]).tolist():
                    mask = seg_mask & (shards == shard_idx)
                    idx = np.nonzero(mask)[0]
                    sel_tags = {}
                    for t, col in tag_bytes.items():
                        if col is None:
                            sel_tags[t] = None
                        elif isinstance(col, DictColumn):
                            sel_tags[t] = col.take(idx)
                        else:
                            sel_tags[t] = [col[i] for i in idx]
                    sel_fields = {}
                    for f in _numeric_fields(m):
                        v = num_fields.get(f.name)
                        sel_fields[f.name] = (
                            np.asarray(v)[idx] if v is not None else None
                        )
                    shard_obj = seg.shards[int(shard_idx)]
                    shard_obj.ingest(
                        lambda mem: mem.append_measure_bulk(
                            name,
                            _tag_col_names(m),
                            [f.name for f in _numeric_fields(m)],
                            ts_millis[idx],
                            sids[idx],
                            versions[idx],
                            sel_tags,
                            sel_fields,
                        )
                    )
            self.topn.observe_columns(
                m, ts_millis, tags, num_fields,
                sids=sids, versions=versions,
            )
            if self.streamagg.active(group, name):

                def _sa_tag(t: str) -> np.ndarray:
                    col = tag_bytes.get(t)
                    if col is None:
                        return np.full(n, b"", dtype=object)
                    if isinstance(col, DictColumn):
                        return np.asarray(col.values, dtype=object)[
                            np.asarray(col.codes)
                        ]
                    return np.asarray(col, dtype=object)

                def _sa_field(f: str) -> np.ndarray:
                    col = num_fields.get(f)
                    if col is None:
                        return np.zeros(n, dtype=np.float64)
                    return np.asarray(col, dtype=np.float64)

                self.streamagg.observe(
                    group, name,
                    ts=ts_millis, series=sids, versions=versions,
                    shards=shards, tag_col=_sa_tag, field_col=_sa_field,
                )
        finally:
            self.streamagg.ingest_exit()
        return n

    def ensure_result_measure(self, group: str) -> None:
        """Auto-register the shared _top_n_result measure for a group."""
        from banyandb_tpu.models.topn import RESULT_MEASURE, result_measure_schema

        try:
            self.registry.get_measure(group, RESULT_MEASURE)
        except KeyError:
            self.registry.create_measure(result_measure_schema(group))

    def flush(self, group: Optional[str] = None) -> list[str]:
        out = []
        for name, db in self._tsdbs.items():
            if group is None or name == group:
                out.extend(db.flush_all())
        if out:
            # first-flush hook: parts now exist on disk, so the next
            # query is the cold one — warm recorded plan kernels in the
            # background (no-op unless BYDB_PRECOMPILE; lazy import keeps
            # the engines layer from depending upward on query/)
            from banyandb_tpu.query.precompile import default_registry

            default_registry().note_flush()
        return out

    # -- query path (query.go:88 analog) -----------------------------------
    def query(
        self, req: QueryRequest, shard_ids=None, tracer=None
    ) -> QueryResult:
        """Execute; when req.trace is set, attach in-band trace spans
        (pkg/query/tracer.go analog: spans ride back in the response).

        `tracer` (obs.tracer.Tracer): caller-owned span sink — servers
        pass one so the tree also feeds the slow-query flight recorder;
        when None and req.trace is set the engine owns a local one and
        attaches its tree as res.trace["span_tree"].

        Routing decisions come off the logical plan tree
        (query/logical.py, measure_analyzer.go:70 analog): the analyzer
        is the single owner of index-mode short-circuit and aggregate-vs-
        raw selection; this method lowers the tree onto the fused
        executors."""
        from banyandb_tpu.query import logical, planner

        own_tracer = tracer is None and req.trace
        if own_tracer:
            tracer = Tracer("measure:query", usage=True)
        t = tracer if tracer is not None else NOOP_TRACER

        t_start = time.perf_counter()
        group = req.groups[0]
        m = self.registry.get_measure(group, req.name)
        db = self._tsdb(group)
        with t.span("analyze"):
            plan = logical.analyze_measure(m, req)
        # Materialized-window rewrite (query/streamagg.py): an aggregate
        # whose (signature, time range, group-by) is covered by rolling
        # windows folds states instead of rescanning parts; partial
        # head/tail windows rescan ONLY the uncovered sub-ranges.
        is_agg = plan.find("GroupByAggregate") is not None
        if is_agg and not m.index_mode:
            cover = self.streamagg.plan_cover(m, req)
            if cover is not None:
                res = self._query_materialized(
                    m, req, db, plan, cover, shard_ids, tracer, t,
                    t_start, own_tracer,
                )
                if res is not None:
                    if planner.enabled():
                        planner.record_decision("materialized")
                    return res
                # coverage lost (window evicted mid-plan): full rescan
        # Cost-based scan planning (query/planner, BYDB_PLANNER): the
        # pre-gather estimate decides group-by strategy, the fused chunk
        # schedule, and whether the zone-map pre-pass is worth running.
        # All decisions are result-preserving — BYDB_PLANNER=0 restores
        # the fixed thresholds with byte-identical output.
        decision = None
        pspan = None
        if (
            is_agg
            and not m.index_mode
            and plan.leaf().kind != "IndexModeScan"
            and planner.enabled()
        ):
            with t.span("planner") as pspan:
                decision = planner.plan_scan(
                    self, db, m, req,
                    span=pspan if tracer is not None else None,
                )
        # hidden (indexed non-entity) tags resolve BEFORE the gather:
        # their per-row stored values are superseded by the latest-
        # write-wins series join (_join_hidden_tags), so block pruning
        # must never use them — a block whose stored values all fail a
        # hidden-tag predicate may still hold rows whose JOINED value
        # matches (and vice versa its rows may carry the series' newest
        # value that other blocks need)
        hidden = (
            self._hidden_index_tags(group, req.name, m)
            if not is_agg and not m.index_mode
            else set()
        )
        t_pg = time.perf_counter()  # stage metric covers ONLY part gather
        with t.span("part_gather") as gs:
            read_stats: dict = {}
            if plan.leaf().kind == "IndexModeScan":
                # Short-circuit: whole measure lives in the series index
                # (SearchWithoutSeries, measure/query.go:506,559).
                sources = self._index_sources(db, m, req, shard_ids)
            else:
                sources = _retry_merged_away(
                    lambda: self._gather_sources(
                        db, m, req, shard_ids=shard_ids,
                        zone_prepass=(
                            decision.zone_prepass
                            if decision is not None
                            else True
                        ),
                        zone_exclude=hidden,
                        read_stats=read_stats,
                    )
                )
            gs.tag("sources", len(sources)).tag(
                "rows", sum(int(s.ts.size) for s in sources)
            )
            for k, v in read_stats.items():
                gs.tag(k, v)
        t_gather = time.perf_counter()
        _H_PART_GATHER.observe((t_gather - t_pg) * 1000)
        analyzers = self._tag_analyzers(group, req.name)
        try:
            if is_agg:
                with t.span("execute") as es:
                    res = measure_exec.execute_aggregate(
                        m, req, sources,
                        dict_state=self._dict_state(group, req.name),
                        analyzers=analyzers,
                        span=es if tracer is not None else None,
                        plan_hints=decision,
                    )
                if decision is not None:
                    # est-vs-actual on the (already closed) planner span:
                    # tags serialize when the tree renders, at query end
                    if decision.actual_rows is not None and pspan is not None:
                        pspan.tag("actual_rows", decision.actual_rows)
                    planner.record_decision(decision.path)
            else:
                with t.span("execute") as es:
                    es.tag("path", "raw_rows")
                    if hidden:
                        sources = _join_hidden_tags(sources, hidden)
                    res = _raw_rows(m, req, sources, analyzers=analyzers)
        finally:
            # observed on error paths too (stream/trace/property parity:
            # per-engine latency must not go dark when queries fail)
            _H_QUERY.observe((time.perf_counter() - t_start) * 1000)
        if req.trace:
            res.trace = {"plan": plan.explain()}
            if own_tracer:
                res.trace["span_tree"] = tracer.finish()
        return res

    def _query_materialized(
        self, m, req, db, plan, cover, shard_ids, tracer, t, t_start,
        own_tracer,
    ) -> QueryResult:
        """Answer a covered aggregate from materialized rolling windows
        (query/streamagg.py): fold window states into partials, rescan
        only the uncovered head/tail ranges, then run the ordinary
        combine/finalize tail — `BYDB_STREAMAGG=0` byte-parity rides on
        the finalize path being shared."""
        analyzers = self._tag_analyzers(m.group, req.name)
        with t.span("streamagg") as ss:
            span = ss if tracer is not None else None
            parts = self.streamagg.answer(
                cover,
                shard_ids=shard_ids,
                rescan=lambda b, e: self._rescan_partials(
                    db, m, req, b, e, shard_ids, analyzers, span
                ),
                span=span,
            )
            if parts is None:
                return None  # coverage lost: caller runs the rescan
            try:
                res = measure_exec.finalize_partials(
                    m, req, parts, span=span
                )
            finally:
                _H_QUERY.observe(
                    (time.perf_counter() - t_start) * 1000
                )
        if req.trace:
            res.trace = {"plan": plan.explain()}
            if own_tracer:
                res.trace["span_tree"] = tracer.finish()
        return res

    def _rescan_partials(
        self, db, m, req, begin, end, shard_ids, analyzers, span
    ):
        """Bounded rescan of one uncovered sub-range through the normal
        gather+compute path (block selection prunes to the range; the
        merged-part retry lives in gather_query_sources)."""
        from banyandb_tpu.api.model import TimeRange as _TR

        sub = dataclasses.replace(req, time_range=_TR(begin, end))
        sources = self.gather_query_sources(
            sub, shard_ids=shard_ids, serial=True
        )
        return measure_exec.compute_partials(
            m, sub, sources,
            dict_state=self._dict_state(m.group, req.name),
            analyzers=analyzers,
            span=span,
        )

    def query_partials(
        self,
        req: QueryRequest,
        shard_ids=None,
        hist_range=None,
        tracer=None,
    ):
        """Data-node map phase: partial aggregates over (a subset of) local
        shards (banyand/query processor + agg_return_partial analog).

        `tracer`: the data node's own span sink — its finished tree rides
        the RPC reply back to the liaison for the cluster-wide merge."""
        from banyandb_tpu.query import planner

        t = tracer if tracer is not None else NOOP_TRACER
        t0 = time.perf_counter()
        group = req.groups[0]
        m = self.registry.get_measure(group, req.name)
        # Materialized-window map phase: a covered node folds its local
        # shard subset's window states into one Partials (merged across
        # shards/nodes by the liaison exactly like scan partials).  The
        # percentile second round pins hist_range and must rescan —
        # windows hold no histograms.
        if hist_range is None and not m.index_mode:
            cover = self.streamagg.plan_cover(m, req)
            if cover is not None:
                analyzers = self._tag_analyzers(group, req.name)
                with t.span("streamagg") as ss:
                    span = ss if tracer is not None else None
                    parts = self.streamagg.answer(
                        cover,
                        shard_ids=shard_ids,
                        rescan=lambda b, e: self._rescan_partials(
                            self._tsdb(group), m, req, b, e,
                            shard_ids, analyzers, span,
                        ),
                        span=span,
                    )
                    if parts is not None:
                        try:
                            out = (
                                parts[0]
                                if len(parts) == 1
                                else measure_exec.combine_partials(parts)
                            )
                        finally:
                            _H_QUERY.observe(
                                (time.perf_counter() - t0) * 1000
                            )
                        if planner.enabled():
                            planner.record_decision("materialized")
                        return out
                # coverage lost mid-plan: fall through to the rescan
        # the data-node side of cost-based planning: same estimate, same
        # result-preserving hints, per-node planner span in the graft
        decision = None
        pspan = None
        if not m.index_mode and planner.enabled():
            with t.span("planner") as pspan:
                decision = planner.plan_scan(
                    self, self._tsdb(group), m, req,
                    span=pspan if tracer is not None else None,
                )
        t_pg = time.perf_counter()  # stage metric covers ONLY part gather
        with t.span("part_gather") as gs:
            read_stats: dict = {}
            sources = self.gather_query_sources(
                req, shard_ids=shard_ids,
                zone_prepass=(
                    decision.zone_prepass if decision is not None else True
                ),
                read_stats=read_stats,
            )
            gs.tag("sources", len(sources)).tag(
                "rows", sum(int(s.ts.size) for s in sources)
            ).tag("shards", sorted(shard_ids) if shard_ids else "all")
            for k, v in read_stats.items():
                gs.tag(k, v)
        _H_PART_GATHER.observe((time.perf_counter() - t_pg) * 1000)
        analyzers = self._tag_analyzers(group, req.name)
        try:
            with t.span("compute_partials") as cs:
                span = cs if tracer is not None else None
                if m.index_mode:
                    out = measure_exec.compute_partials(
                        m, req, sources, hist_range=hist_range,
                        analyzers=analyzers, span=span,
                    )
                else:
                    out = measure_exec.compute_partials(
                        m,
                        req,
                        sources,
                        hist_range=hist_range,
                        dict_state=self._dict_state(group, req.name),
                        analyzers=analyzers,
                        span=span,
                        plan_hints=decision,
                    )
            if decision is not None:
                if decision.actual_rows is not None and pspan is not None:
                    pspan.tag("actual_rows", decision.actual_rows)
                planner.record_decision(decision.path)
        finally:
            _H_QUERY.observe((time.perf_counter() - t0) * 1000)
        return out

    def _hidden_index_tags(self, group: str, name: str, m: Measure) -> set:
        """Indexed NON-ENTITY tags (the reference's 'hidden' tags): the
        reference stores them as series-level metadata docs where the
        latest-ts write wins and joins them onto every row of the
        series (write_standalone.go metadataDocs).  This engine stores
        tags per row, so the raw retrieval path applies the same
        latest-write-wins join explicitly (_join_hidden_tags)."""
        out: set = set()
        try:
            rules = {r.name: r for r in self.registry.list_index_rules(group)}
            for b in self.registry.list_index_rule_bindings(group):
                if b.subject_name != name:
                    continue
                for rn in b.rules:
                    r = rules.get(rn)
                    if r is not None:
                        out.update(r.tags)
        except Exception:  # noqa: BLE001 — registries without bindings
            return set()
        return out - set(m.entity.tag_names)

    def _tag_analyzers(self, group: str, name: str) -> dict[str, str]:
        """tag -> analyzer from index rules BOUND to this measure (the
        MATCH op's mandatory context, ref inverted/query.go:371).  Rules
        without an analyzer map to 'keyword' (exact-term match)."""
        out: dict[str, str] = {}
        try:
            rules = {r.name: r for r in self.registry.list_index_rules(group)}
            for b in self.registry.list_index_rule_bindings(group):
                if b.subject_name != name:
                    continue
                for rn in b.rules:
                    r = rules.get(rn)
                    if r is None:
                        continue
                    for t in r.tags:
                        out[t] = r.analyzer or "keyword"
        except Exception:  # noqa: BLE001 — registries without bindings
            pass
        return out

    def gather_query_sources(
        self, req, shard_ids=None, serial=False, zone_prepass=True,
        read_stats=None,
    ):
        """Source selection for the map phase, shared by the host partial
        path, the mesh fast path (parallel/mesh_query.py) and the
        streamagg bounded rescans (`serial=True` skips the part
        prefetch thread): same segment/series pruning, same retry on
        concurrently-merged parts.  ``zone_prepass=False`` (planner
        decision: estimated selectivity ~1) skips the zone-map block
        pre-pass — identical rows, no per-part predicate lowering."""
        group = req.groups[0]
        m = self.registry.get_measure(group, req.name)
        db = self._tsdb(group)
        if m.index_mode:
            return self._index_sources(db, m, req, shard_ids)
        return _retry_merged_away(
            lambda: self._gather_sources(
                db, m, req, shard_ids=shard_ids, serial=serial,
                zone_prepass=zone_prepass, read_stats=read_stats,
            )
        )

    def _index_sources(self, db, m, req, shard_ids):
        """Index-mode sources, optionally restricted to a shard subset
        (distributed scatter: shard = seriesID % shard_num)."""
        sources = _index_mode_sources(db, m, req)
        if shard_ids is None:
            return sources
        shard_num = self.registry.get_group(m.group).resource_opts.shard_num
        out = []
        for src in sources:
            mask = np.isin(src.series % shard_num, list(shard_ids))
            if not mask.any():
                continue
            out.append(
                ColumnData(
                    ts=src.ts[mask],
                    series=src.series[mask],
                    version=src.version[mask],
                    tags={t: c[mask] for t, c in src.tags.items()},
                    fields={f: v[mask] for f, v in src.fields.items()},
                    dicts=src.dicts,
                )
            )
        return out

    def _gather_sources(
        self,
        db: TSDB,
        m: Measure,
        req: QueryRequest,
        shard_ids=None,
        serial: bool = False,
        zone_prepass: bool = True,
        zone_exclude: set = frozenset(),
        read_stats: Optional[dict] = None,
    ) -> list[ColumnData]:
        """Collect per-source decode thunks (metadata-only work: segment
        selection, series-index pruning, block selection), then evaluate
        them through the prefetchable chunk stream — part *k+1* decodes
        on the prefetch thread while part *k*'s rows series-filter and
        append on this one.  Thunk order is the serial iteration order,
        so the concatenation (and every downstream dedup/accumulation)
        is byte-identical to the strict-serial path (BYDB_PIPELINE=0).
        Each part read comes back with its serving-cache outcome;
        ``read_stats`` (the `part_gather` span's numeric tags) sums them."""
        from banyandb_tpu.storage.chunk_stream import prefetched

        from banyandb_tpu.storage import encoded as enc_mod

        read_ops = []
        tag_names = _tag_col_names(m)  # incl. '@f:' raw-field columns
        field_names = [f.name for f in _numeric_fields(m)]
        entity_conds = _entity_eq_conditions(m, req)
        # Zone-map skipping (ROADMAP item 3 / arXiv 2104.12815):
        # conjunctive eq/in tag predicates prune at BLOCK granularity
        # against the per-block code zone maps written at flush/merge —
        # a skipped block is never read, let alone decoded.
        # ``zone_prepass=False`` is the planner's ~1-selectivity call:
        # nothing would skip, so the per-part dict lowering + per-block
        # interval checks are pure overhead (results identical — zone
        # skipping only ever removes reads of non-matching blocks)
        zone_conds = (
            _conjunctive_eq_conditions(req)
            if (enc_mod.zone_skip_enabled() and zone_prepass)
            else []
        )
        if zone_exclude:
            # hidden-tag predicates evaluate against the JOINED series
            # value, never the stored per-row one — block pruning on
            # them would drop rows the join makes match
            zone_conds = [
                (name, vals)
                for name, vals in zone_conds
                if name not in zone_exclude
            ]
        for seg in db.select_segments(
            req.time_range.begin_millis, req.time_range.end_millis
        ):
            # Series pruning: entity-tag equality conditions resolve to a
            # candidate seriesID set via the segment's series index
            # (searchSeriesList, measure/query.go:314); part blocks outside
            # the candidate series range are skipped.
            series_ids = None
            if entity_conds and len(seg.series_index):
                # An empty index means "no information" (legacy parts, lost
                # sidx file) — skip pruning rather than prune everything.
                from banyandb_tpu.index.inverted import And, Or, TermQuery

                clauses = [TermQuery("@measure", m.name.encode())]
                for name, values in entity_conds:
                    terms = tuple(TermQuery(name, v) for v in values)
                    clauses.append(terms[0] if len(terms) == 1 else Or(terms))
                series_ids = np.sort(
                    seg.series_index.search(And(tuple(clauses)))
                )
            # Row-level series filter companion to block pruning: blocks
            # are 8192 rows, so a one-series query over small (young)
            # parts still decodes ~everything — dropping non-candidate
            # ROWS here shrinks the whole downstream pipeline (remap,
            # dedup lexsort, device transfer, kernel) by the selectivity
            # factor.  Hash digest keys the derived source for the
            # serving cache (same parts + same series set => same rows).
            sfilter_key = None
            if series_ids is not None:
                sfilter_key = hash(series_ids.tobytes())

            # evaluation is DEFERRED to the prefetch stream below, and
            # series_ids/sfilter_key are reassigned per segment — bind
            # this segment's values as defaults, not closure cells
            def _series_rows(
                src: ColumnData, ckey, sids=series_ids, skey=sfilter_key
            ) -> Optional[ColumnData]:
                if sids is None:
                    return src
                keep = np.zeros(src.series.shape[0], dtype=bool)
                if sids.size:
                    pos = np.searchsorted(sids, src.series)
                    pos[pos >= sids.size] = 0
                    keep = sids[pos] == src.series
                if not keep.any():
                    return None
                if keep.all():
                    return src
                return ColumnData(
                    ts=src.ts[keep],
                    series=src.series[keep],
                    version=src.version[keep],
                    tags={t: c[keep] for t, c in src.tags.items()},
                    fields={f: v[keep] for f, v in src.fields.items()},
                    dicts=src.dicts,
                    cache_key=(
                        (*ckey, "sfilter", skey) if ckey else None
                    ),
                    key_span=src.key_span,  # holds for any row subset
                )

            def _read_part(part, blocks, filt):
                outcome: list = []
                src = part.read(
                    blocks,
                    tags=[t for t in tag_names if t in part.meta["tags"]],
                    fields=[f for f in field_names if f in part.meta["fields"]],
                    narrow_codes=True,
                    outcome=outcome,
                )
                return filt(src, src.cache_key), outcome[0]

            for shard_idx, shard in enumerate(seg.shards):
                if shard_ids is not None and shard_idx not in shard_ids:
                    continue
                # live memtable + any in-flight flush snapshot (rows
                # between flush's two commit points stay visible;
                # version dedup collapses a racing double-expose).
                # Nothing proves their keys unique, and their min/max
                # rect is all that is known of where the keys lie.
                hot_cols = [
                    dataclasses.replace(
                        mc,
                        key_span=KeySpan.unproven(
                            str(shard.root), mc.series, mc.ts
                        ),
                    )
                    for mc in shard.hot_columns(m.name)
                ]
                for mem_cols in hot_cols:
                    read_ops.append(
                        lambda mc=mem_cols, filt=_series_rows: (
                            filt(mc, mc.cache_key), None
                        )
                    )
                shard_parts = [
                    p for p in shard.parts if p.meta.get("measure") == m.name
                ]
                # Zone skipping is dedup-safety-gated: a block whose
                # zones exclude every predicate value may still hold the
                # NEWEST version of a (series, ts) row whose older,
                # matching copy lives in a kept source — dropping it
                # would resurrect the stale row.  So first collect every
                # kept source's key interval across the whole shard
                # (version dedup is scoped to a shard: series hash to
                # exactly one, segments partition time), then let
                # select_blocks drop only overlap-free marked blocks.
                plans: list = []  # (part, candidate blocks, marked set)
                kept_intervals: list = []
                if zone_conds and shard_parts:
                    kept_intervals.extend(
                        mc.key_span.interval for mc in hot_cols
                    )
                for part in shard_parts:
                    cands = part.select_blocks(
                        req.time_range.begin_millis,
                        req.time_range.end_millis,
                        series_ids=series_ids,
                    )
                    marked: set = set()
                    if zone_conds:
                        marked = part.zone_marked(
                            cands, _part_zone_preds(part, zone_conds)
                        )
                        kept_intervals.extend(
                            part.block_interval(i)
                            for i in cands
                            if i not in marked
                        )
                    plans.append((part, cands, marked))
                for part, cands, marked in plans:
                    blocks = (
                        part.finalize_zone_skip(cands, marked, kept_intervals)
                        if marked
                        else cands
                    )
                    if blocks:
                        read_ops.append(
                            lambda p=part, b=blocks, filt=_series_rows,
                            rd=_read_part: rd(p, b, filt)
                        )
        # a mid-stream decode error (e.g. a part merged away under us)
        # re-raises here exactly as the serial loop would — query()'s
        # FileNotFoundError retry still applies.  `serial` (bounded
        # streamagg head/tail rescans) skips the prefetch thread
        # entirely: results are byte-identical by the pipeline contract,
        # and at a few blocks of work the thread handoffs cost more
        # than the overlap buys — especially under write-saturated GIL
        sources = []
        stats = {"cache_hits": 0, "cache_misses": 0, "decoded_bytes": 0}
        for src, outcome in prefetched(
            read_ops,
            name="bydb-part-prefetch",
            enabled=False if serial else None,
        ):
            if outcome is not None:  # a part read, not a memtable
                how, decoded = outcome
                stats["cache_hits" if how == "hit" else "cache_misses"] += 1
                stats["decoded_bytes"] += decoded
            if src is not None:
                sources.append(src)
        if read_stats is not None:
            read_stats.update(stats)
        return sources


def _retry_merged_away(read):
    """Run ``read()`` (a gather over a snapshot of the part list) until
    no part it names was merged away under it.

    A concurrent merge can GC a part dir after the read snapshots the
    part list; the read then raises FileNotFoundError and is made again
    against the fresh snapshot, which holds the same rows in the merged
    part (the reference's epoch contract).  Every retry is explained by
    a DIFFERENT vanished directory — a part is merged away once — so the
    loop ends when the merges do, however many run (a fixed count of
    three gave up on a 24 h query right after a 10M-point load, while
    four shards were still merging: chip run, PR 30).  The same
    directory missing twice is not a merge: the fresh snapshot still
    lists it, so the loss is real and is raised (an error that names no
    file, after the old three attempts)."""
    gone: set = set()
    unnamed = 0
    while True:
        try:
            return read()
        except FileNotFoundError as e:
            where = os.path.dirname(e.filename or "")
            if where in gone:
                raise
            if where:
                gone.add(where)
            else:
                unnamed += 1
                if unnamed == 3:
                    raise


def _tag_to_bytes(value, tag_type: TagType) -> bytes:
    if value is None:
        return b""
    return hashing.entity_bytes(value)


class _MultiMeasureMemtable:
    """Shard memtable keyed by measure name (one MemTable each).

    The reference keeps one tstable per (group, shard) with rows of all
    measures distinguished by series; here hot rows stay per-measure so a
    flush produces one part per measure with that measure's columns.
    """

    def __init__(self):
        self._tables: dict[str, MemTable] = {}

    def __len__(self) -> int:
        return sum(len(t) for t in self._tables.values())

    def append_measure(
        self, measure, tag_names, field_names, ts, sid, version, tags, fields
    ) -> None:
        tbl = self._tables.get(measure)
        if tbl is None:
            tbl = self._tables[measure] = MemTable(tag_names, field_names)
        tbl.append(ts, sid, version, tags, fields)

    def append_measure_bulk(
        self, measure, tag_names, field_names, ts, sids, versions, tags, fields
    ) -> None:
        tbl = self._tables.get(measure)
        if tbl is None:
            tbl = self._tables[measure] = MemTable(tag_names, field_names)
        tbl.append_bulk(ts, sids, versions, tags, fields)

    def drain(self) -> list:
        return [
            (name, tbl.snapshot_columns(), {"measure": name})
            for name, tbl in self._tables.items()
        ]

    def columns_for(self, measure: str) -> Optional[ColumnData]:
        tbl = self._tables.get(measure)
        return tbl.snapshot_columns() if tbl else None

    def per_measure(self) -> dict[str, MemTable]:
        return dict(self._tables)


def _join_hidden_tags(
    sources: list[ColumnData], hidden: set
) -> list[ColumnData]:
    """Latest-write-wins join for hidden (indexed non-entity) tags:
    compute each series' newest value per hidden tag across the
    gathered sources — (ts, version)-max, the write path's own
    ordering — and rewrite every row of that series to carry it, so
    filters AND projections see the joined value exactly like the
    reference's series-metadata docs.  Scoped to the gathered (time-
    pruned) sources: a rewrite outside the queried range is invisible
    here, which matches block pruning's visibility everywhere else."""
    latest: dict[str, dict[int, tuple]] = {t: {} for t in hidden}
    for src in sources:
        for t in hidden:
            col = src.tags.get(t)
            if col is None:
                continue
            d = src.dicts[t]
            for i in range(src.ts.shape[0]):
                sid = int(src.series[i])
                stamp = (int(src.ts[i]), int(src.version[i]))
                cur = latest[t].get(sid)
                if cur is None or stamp > cur[0]:
                    latest[t][sid] = (stamp, d[int(col[i])])
    if not any(latest[t] for t in hidden):
        return sources
    out = []
    for src in sources:
        tags = dict(src.tags)
        dicts = dict(src.dicts)
        changed = False
        for t in hidden:
            by_sid = latest[t]
            if not by_sid and t not in tags:
                continue
            vals = sorted({v for _, v in by_sid.values()} | {b""})
            vidx = {v: i for i, v in enumerate(vals)}
            codes = np.fromiter(
                (
                    vidx[by_sid[int(s)][1]] if int(s) in by_sid else 0
                    for s in src.series
                ),
                dtype=np.int32,
                count=src.series.shape[0],
            )
            tags[t] = codes
            dicts[t] = vals
            changed = True
        if not changed:
            out.append(src)
            continue
        out.append(
            dataclasses.replace(src, tags=tags, dicts=dicts, cache_key=None)
        )
    return out


def _raw_rows(
    m: Measure,
    req: QueryRequest,
    sources: list[ColumnData],
    analyzers: Optional[dict] = None,
) -> QueryResult:
    """Projection/limit query without aggregation: host-side assembly.

    The aggregate path is the TPU hot loop; raw row retrieval is IO-bound
    and stays on host (the reference's row iterator, query.go:594).
    """
    res = QueryResult()
    conds, _expr = measure_exec._lower_criteria(req.criteria)
    for c in conds:
        m.tag(c.name)  # schema validation: typo'd tag -> KeyError, matching
        # the aggregate path instead of silently returning unfiltered rows
    rows: list[tuple] = []
    for src in sources:
        if src.ts.size == 0:
            continue
        mask = qfilter.criteria_mask(
            src, req.criteria, req.time_range.begin_millis,
            req.time_range.end_millis, analyzers=analyzers,
            tag_types={t.name: t.type for t in m.tags},
        )
        raw_types = {
            _RAW_FIELD_PREFIX + f.name: f.type for f in _raw_fields(m)
        }
        for i in np.nonzero(mask)[0]:
            tags = {}
            fields = {}
            for t in src.tags:
                raw = src.dicts[t][src.tags[t][i]]
                ftype = raw_types.get(t)
                if ftype is not None:
                    # reserved '@f:' column: a stored raw field value
                    fields[t[len(_RAW_FIELD_PREFIX):]] = (
                        raw
                        if ftype == FieldType.DATA_BINARY
                        else raw.decode(errors="replace")
                    )
                else:
                    tags[t] = qfilter.decode_tag_value(raw, m.tag(t).type)
            for f in src.fields:
                fields[f] = float(src.fields[f][i])
            rows.append(
                (
                    int(src.ts[i]),
                    int(src.version[i]),
                    tags,
                    fields,
                    int(src.series[i]),
                )
            )

    # Version dedup then ordering: by an indexed tag's value when
    # order_by_tag is set (order-by-index analog), else by ts.
    # Index-mode measures dedup PER SERIES across segments (docs are
    # series-keyed upserts; an older segment may still hold a replaced
    # doc) — row measures dedup per (series, ts): a rewrite of the same
    # series at the same timestamp REPLACES the row even when non-entity
    # tags changed (want/duplicated_part.yaml keeps only the last write)
    best: dict[tuple, tuple] = {}
    for row in rows:
        key = (row[4],) if m.index_mode else (row[4], row[0])
        if key not in best or best[key][1] < row[1]:
            best[key] = row
    if req.top:
        # row-level top-N (measure_top.go): rank raw points by the
        # field's value, emit in ranking order
        fname = req.top.field_name
        desc = req.top.field_value_sort != "asc"
        ranked = sorted(
            (r for r in best.values() if fname in r[3]),
            key=lambda r: r[3][fname],
            reverse=desc,
        )
        ordered = ranked[: req.top.number]
    elif req.order_by_tag:
        have = [r for r in best.values() if r[2].get(req.order_by_tag) is not None]
        miss = [r for r in best.values() if r[2].get(req.order_by_tag) is None]
        have.sort(
            key=lambda r: r[2][req.order_by_tag],
            reverse=(req.order_by_dir == "desc"),
        )
        ordered = have + miss  # missing-tag rows last under either order
    else:
        # default (no order_by) is timestamp ASC — pinned by the
        # reference's limit/offset golden (want/limit.yaml: offset 3
        # lands on the 4th-written row)
        ordered = sorted(
            best.values(), key=lambda r: r[0], reverse=(req.order_by_ts == "desc")
        )
    off = req.offset or 0
    for ts, _ver, tags, fields, _sid in ordered[off : off + (req.limit or 100)]:
        res.data_points.append({"timestamp": ts, "tags": tags, "fields": fields})
    return res


# -- series pruning helpers -------------------------------------------------


def _entity_eq_conditions(m: Measure, req: QueryRequest):
    """[(entity_tag, [candidate byte values])] from AND'ed eq/in conditions."""
    try:
        conds = measure_exec._collect_conditions(req.criteria)
    except NotImplementedError:
        return []
    entity = set(m.entity.tag_names)
    out = []
    for c in conds:
        if c.name not in entity:
            continue
        if c.op == "eq":
            out.append((c.name, [measure_exec._tag_value_bytes(c.value)]))
        elif c.op == "in":
            out.append(
                (c.name, [measure_exec._tag_value_bytes(v) for v in c.value])
            )
    return out


# Moved into the query layer (the cost-based planner estimates from the
# same lowering); lazily re-exported here for the gather path + existing
# tests (function-local import per the layering policy — models sits
# BELOW query in the layer map).


def _conjunctive_eq_conditions(req: QueryRequest):
    from banyandb_tpu.query.planner import conjunctive_eq_conditions

    return conjunctive_eq_conditions(req)


def _part_zone_preds(part, zone_conds) -> list:
    from banyandb_tpu.query.planner import part_zone_preds

    return part_zone_preds(part, zone_conds)


# -- index-mode measures (doc-per-point in the series index) ---------------


def _series_doc_id(measure: str, sid: int) -> int:
    """Index-mode doc identity = the SERIES (ref DocID: uint64(series.ID),
    write_standalone.go:89): a new point for the same series REPLACES the
    doc — index-mode measures hold each series' latest state, not a
    point history."""
    import hashlib

    h = hashlib.blake2b(
        measure.encode() + b"\x00" + sid.to_bytes(8, "little", signed=True),
        digest_size=8,
    )
    return int.from_bytes(h.digest(), "little", signed=True)


def _index_mode_write(seg, m: Measure, sid, ts_millis, version, tag_bytes, field_vals):
    from banyandb_tpu.index.inverted import Doc

    idx = seg.series_index._idx
    payload = np.asarray(
        [field_vals.get(f.name, 0.0) for f in m.fields], dtype=np.float64
    ).tobytes()
    keywords = dict(tag_bytes)
    keywords["@measure"] = m.name.encode()
    # check-and-insert under the index lock (dedup-by-version contract);
    # series-keyed doc id => a newer point REPLACES the series' doc
    idx.insert_if_newer(
        Doc(
            doc_id=_series_doc_id(m.name, sid),
            keywords=keywords,
            numerics={"@ts": ts_millis, "@version": version, "@series": sid},
            payload=payload,
        )
    )


def _index_mode_sources(db: TSDB, m: Measure, req: QueryRequest) -> list[ColumnData]:
    """Build scan sources straight from index docs (SearchWithoutSeries) —
    the same device executor then runs over them unchanged.

    Segments wholly past the group's TTL are excluded at QUERY time (the
    retention sweep may not have run yet; ref 'excludes data expired
    beyond TTL' golden): data past retention must never surface."""
    from banyandb_tpu.index.inverted import And, RangeQuery, TermQuery

    ttl_floor = None
    ttl = getattr(db.opts, "ttl", None)
    if ttl is not None and ttl.millis:
        ttl_floor = int(time.time() * 1000) - ttl.millis
    sources = []
    for seg in db.select_segments(
        req.time_range.begin_millis, req.time_range.end_millis
    ):
        if ttl_floor is not None and seg.end <= ttl_floor:
            continue  # fully expired segment
        idx = seg.series_index._idx
        ids = idx.search(
            And(
                (
                    TermQuery("@measure", m.name.encode()),
                    RangeQuery(
                        "@ts",
                        req.time_range.begin_millis,
                        req.time_range.end_millis - 1,
                    ),
                )
            )
        )
        docs = idx.get_many(ids.tolist())
        if not docs:
            continue
        n = len(docs)
        ts = np.asarray([d.numerics["@ts"] for d in docs], dtype=np.int64)
        series = np.asarray([d.numerics["@series"] for d in docs], dtype=np.int64)
        version = np.asarray(
            [d.numerics.get("@version", 0) for d in docs], dtype=np.int64
        )
        tags: dict[str, np.ndarray] = {}
        dicts: dict[str, list[bytes]] = {}
        for tname in _tag_col_names(m):
            vocab: dict[bytes, int] = {}
            codes = np.empty(n, dtype=np.int32)
            for i, d in enumerate(docs):
                v = d.keywords.get(tname, b"")
                codes[i] = vocab.setdefault(v, len(vocab))
            tags[tname] = codes
            dicts[tname] = [
                v for v, _ in sorted(vocab.items(), key=lambda kv: kv[1])
            ]
        fields: dict[str, np.ndarray] = {}
        num_fields = _numeric_fields(m)
        raw = np.frombuffer(b"".join(d.payload for d in docs), dtype=np.float64)
        raw = (
            raw.reshape(n, len(num_fields))
            if num_fields
            else raw.reshape(n, 0)
        )
        for j, f in enumerate(num_fields):
            fields[f.name] = raw[:, j].copy()
        sources.append(
            ColumnData(
                ts=ts, series=series, version=version,
                tags=tags, fields=fields, dicts=dicts,
            )
        )
    return sources
