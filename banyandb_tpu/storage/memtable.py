"""In-memory write buffer (the reference's memPart,
banyand/measure/tstable.go mustAddDataPoints path).

Accumulates rows column-wise with string-tag interning so a flush is a
sort + encode, and a query over hot data can build a device batch without
re-parsing rows.
"""

from __future__ import annotations

import threading
from typing import Mapping

import numpy as np

from banyandb_tpu.obs import metrics as obs_metrics
from banyandb_tpu.storage.part import ColumnData

# Monotonic memtable generation counter (itertools.count is GIL-atomic).
import itertools as _itertools

_MEM_GEN = _itertools.count(1)


class PayloadMemtable:
    """Shard memtable keyed by resource name, for payload-bearing engines
    (stream elements / trace spans).  `meta_key` names the resource kind
    recorded in flushed part metadata ("stream" / "trace")."""

    def __init__(self, meta_key: str):
        self.meta_key = meta_key
        self._lock = threading.Lock()
        self._tables: dict[str, "MemTable"] = {}

    def __len__(self) -> int:
        return sum(len(t) for t in self._tables.values())

    def append(self, resource, tag_names, ts, sid, tags, payload) -> None:
        with self._lock:
            tbl = self._tables.get(resource)
            if tbl is None:
                tbl = self._tables[resource] = MemTable(
                    tag_names, [], with_payload=True
                )
        tbl.append(ts, sid, 0, tags, {}, payload=payload)

    def columns_for(self, resource: str):
        tbl = self._tables.get(resource)
        return tbl.snapshot_columns() if tbl else None

    def drain(self) -> list:
        return [
            (name, tbl.snapshot_columns(), {self.meta_key: name})
            for name, tbl in self._tables.items()
        ]


class MemTable:
    def __init__(
        self,
        tag_names: list[str],
        field_names: list[str],
        with_payload: bool = False,
    ):
        self._lock = threading.Lock()
        self.tag_names = list(tag_names)
        self.field_names = list(field_names)
        self._ts: list[int] = []
        self._series: list[int] = []
        self._version: list[int] = []
        self._tag_codes: dict[str, list[int]] = {t: [] for t in tag_names}
        self._dicts: dict[str, dict[bytes, int]] = {t: {} for t in tag_names}
        self._fields: dict[str, list[float]] = {f: [] for f in field_names}
        self._payloads: list[bytes] | None = [] if with_payload else None
        self._snapshot_cache: tuple[int, ColumnData] | None = None
        # process-unique generation: id() would recycle after GC and
        # alias a new table's cache_key onto a dead one's cached rows
        self._gen = next(_MEM_GEN)

    def __len__(self) -> int:
        return len(self._ts)

    def append(
        self,
        ts_millis: int,
        series_id: int,
        version: int,
        tag_values: Mapping[str, bytes],
        field_values: Mapping[str, float],
        payload: bytes | None = None,
    ) -> None:
        with self._lock:
            self._ts.append(ts_millis)
            self._series.append(series_id)
            self._version.append(version)
            for t in self.tag_names:
                d = self._dicts[t]
                v = tag_values.get(t, b"")
                code = d.setdefault(v, len(d))
                self._tag_codes[t].append(code)
            for f in self.field_names:
                self._fields[f].append(float(field_values.get(f, 0.0)))
            if self._payloads is not None:
                self._payloads.append(payload or b"")

    def append_bulk(
        self,
        ts_millis: "np.ndarray",
        series_ids: "np.ndarray",
        versions: "np.ndarray",
        tag_values: Mapping[str, list],
        field_values: Mapping[str, "np.ndarray"],
        payloads: list | None = None,
    ) -> None:
        """Vectorized append: columns land in one extend per column.

        tag_values: per-tag row values — either list[bytes] (interned
        here via np.unique so each distinct value hits the dict once) or
        an already dictionary-encoded column (duck-typed: has .values +
        .codes, models.measure.DictColumn) whose dict remaps straight
        into this table's dict — zero per-row Python.  Of such a column
        only the values its codes name enter this table's dict, as the
        row path enters only the values it sees: a message's dictionary
        spans every shard, one shard's rows use its share of it, and a
        part keeps what its memtable's dict holds.
        """
        n = len(ts_millis)
        used = unused = 0
        with self._lock:
            self._ts.extend(ts_millis.tolist())
            self._series.extend(series_ids.tolist())
            self._version.extend(versions.tolist())
            for t in self.tag_names:
                vals = tag_values.get(t)
                d = self._dicts[t]
                if vals is None:
                    code = d.setdefault(b"", len(d))
                    self._tag_codes[t].extend([code] * n)
                    continue
                if hasattr(vals, "codes"):  # dictionary-encoded column
                    codes, inv = np.unique(
                        np.asarray(vals.codes, dtype=np.int64),
                        return_inverse=True,
                    )
                    uniq = [vals.values[c] for c in codes.tolist()]
                    used += len(uniq)
                    unused += len(vals.values) - len(uniq)
                else:
                    uniq, inv = np.unique(
                        np.asarray(vals, dtype=object), return_inverse=True
                    )
                lut = np.fromiter(
                    (d.setdefault(v, len(d)) for v in uniq),
                    dtype=np.int64,
                    count=len(uniq),
                )
                self._tag_codes[t].extend(lut[inv].tolist())
            for f in self.field_names:
                vals = field_values.get(f)
                if vals is None:
                    self._fields[f].extend([0.0] * n)
                else:
                    self._fields[f].extend(
                        np.asarray(vals, dtype=np.float64).tolist()
                    )
            if self._payloads is not None:
                self._payloads.extend(payloads or [b""] * n)
        if used or unused:
            meter = obs_metrics.global_meter()
            meter.counter_add("dict_values_entered", used, {"kind": "used"})
            meter.counter_add(
                "dict_values_entered", unused, {"kind": "unused"}
            )

    def drain(self) -> list[tuple[str, ColumnData, dict]]:
        """Flush protocol: [(part-name-suffix, columns, extra metadata)]."""
        return [("", self.snapshot_columns(), {})]

    def snapshot_columns(self) -> ColumnData:
        """Columnar view of the buffered rows (for hot-data queries/flush).

        Cached per row count AND built incrementally: the table is
        append-only between drains, so when rows grew since the last
        snapshot only the NEW tail converts from Python lists — the old
        prefix re-uses the previous snapshot's arrays via a memcpy
        concatenate.  Without this, sustained ingest makes every query
        that touches the memtable pay a full O(buffered-rows)
        list→numpy conversion (hundreds of ms at ~1M buffered rows, the
        dominant cost of the streamagg head/tail rescans under load);
        with it the per-query cost is O(rows since last query).  The
        cache_key ("mem", gen, count) is an honest immutable identity —
        dict codes are append-only, so prefix arrays stay valid as the
        dicts grow."""
        with self._lock:
            n = len(self._ts)
            cached = self._snapshot_cache
            if cached is not None and cached[0] == n:
                return cached[1]

            if cached is not None and 0 < cached[0] < n:
                n0, prev = cached
            else:
                n0, prev = 0, None

            def col(old, rows: list, dtype) -> np.ndarray:
                # grown table: convert only the appended tail and memcpy-
                # concat with the cached prefix; otherwise full convert
                if prev is None:
                    return np.asarray(rows, dtype=dtype)
                return np.concatenate(
                    [old, np.asarray(rows[n0:], dtype=dtype)]
                )

            snap = ColumnData(
                ts=col(prev.ts if prev else None, self._ts, np.int64),
                series=col(
                    prev.series if prev else None, self._series, np.int64
                ),
                version=col(
                    prev.version if prev else None, self._version, np.int64
                ),
                tags={
                    t: col(
                        prev.tags[t] if prev else None,
                        self._tag_codes[t], np.int32,
                    )
                    for t in self.tag_names
                },
                fields={
                    f: col(
                        prev.fields[f] if prev else None,
                        self._fields[f], np.float64,
                    )
                    for f in self.field_names
                },
                dicts=self._dicts_snapshot_locked(),
                payloads=(
                    list(self._payloads)
                    if self._payloads is not None
                    else None
                ),
                cache_key=("mem", self._gen, n),
            )
            self._snapshot_cache = (n, snap)
            return snap

    def _dicts_snapshot_locked(self) -> dict:
        """code -> value lists per tag (dict sizes are the distinct-value
        counts — small — so rebuilding per snapshot is cheap)."""
        return {
            t: [
                v
                for v, _ in sorted(
                    self._dicts[t].items(), key=lambda kv: kv[1]
                )
            ]
            for t in self.tag_names
        }
