"""Liaison-side write queue: buffer -> sealed parts -> chunked sync.

Analog of the reference's wqueue architecture
(banyand/internal/wqueue/wqueue.go:75 + banyand/measure/syncer.go:69):
instead of fanning every row batch out synchronously, the liaison
buffers writes per (group, measure, shard) in columnar memtables, seals
them into real on-disk parts when a row threshold or flush interval
hits, and ships sealed parts to the shard's data node over the
streaming ChunkedSyncService (cluster/chunked_sync.py).  Data nodes
introduce shipped parts directly — the write path and the inter-tier
sync path are the same code.

Failure contract: a sealed part that fails to ship stays spooled on
disk and retries with bounded exponential backoff + jitter (the spool
is the liaison's handoff buffer for the part plane); seal+ship never
loses acknowledged rows — rows are acknowledged only after landing in
the spool-backed memtable of a seal group, and a liaison crash loses at
most the unsealed buffer (same window as the reference's liaison
wqueue).  The spool is bounded by BACKPRESSURE, not eviction: past the
high watermark (``max_spool_bytes``) new appends raise ServerBusy — a
retryable shed rejection on the wire (the reference's ServerBusy,
pub.go:301-387) — instead of buffering unboundedly while data nodes
are down.
"""

from __future__ import annotations

import random
import shutil
import threading
import time
import uuid
from pathlib import Path
from typing import Callable, Optional

from banyandb_tpu.api.model import WriteRequest
from banyandb_tpu.api.schema import SchemaRegistry
from banyandb_tpu.cluster import faults
from banyandb_tpu.storage.memtable import MemTable
from banyandb_tpu.storage.part import PartWriter
from banyandb_tpu.utils import hashing


def _dir_bytes(path: Path) -> int:
    total = 0
    try:
        for f in path.rglob("*"):
            if f.is_file():
                total += f.stat().st_size
    except OSError:
        pass
    return total


class WriteQueue:
    def __init__(
        self,
        registry: SchemaRegistry,
        spool_root: str | Path,
        shipper: Callable[[str, int, Path], None],
        *,
        max_rows: int = 65536,
        flush_interval_s: float = 1.0,
        max_spool_bytes: int = 256 << 20,
        retry_base_s: float = 0.05,
        retry_cap_s: float = 30.0,
    ):
        """shipper(group, shard_id, part_dir) ships one sealed part;
        raises on failure (the part stays spooled and retries with
        exponential backoff capped at ``retry_cap_s``)."""
        self.registry = registry
        self.spool = Path(spool_root)
        self.spool.mkdir(parents=True, exist_ok=True)
        self.shipper = shipper
        self.max_rows = max_rows
        self.flush_interval_s = flush_interval_s
        self.max_spool_bytes = max_spool_bytes
        self.retry_base_s = retry_base_s
        self.retry_cap_s = retry_cap_s
        # key: (catalog, group, resource, shard)
        self._buffers: dict[tuple[str, str, str, int], MemTable] = {}
        self._lock = threading.Lock()
        # ordered-tag sets per trace buffer (ride in sealed part meta)
        self._trace_meta: dict[tuple, tuple[str, ...]] = {}
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # per-part retry state: str(part_dir) -> (attempts, next_try
        # monotonic); jitter decorrelates a fleet of liaisons hammering
        # one recovering data node
        self._retry: dict[str, tuple[int, float]] = {}
        self._jitter = random.Random(0xBDB)
        # orphaned sealed parts from a previous process retry first
        self._pending: list[tuple[str, int, Path]] = self._recover_spool()
        # parts a ship_pending call has taken off _pending and not yet
        # put back or shipped: still pending to every observer, and
        # flush() waits them out (the tick thread and a caller's flush
        # run ship_pending concurrently)
        self._inflight = 0
        self._ships_idle = threading.Event()
        self._ships_idle.set()
        # per-part byte sizes, measured ONCE (at seal/recovery) and
        # reused when the ship frees them
        self._part_bytes: dict[str, int] = {
            str(p): _dir_bytes(p.parent) for _g, _s, p in self._pending
        }
        self._spool_bytes = sum(self._part_bytes.values())

    # -- admission (spool high-watermark backpressure) ----------------------
    def _admit(self) -> None:
        """Reject new rows while the ship spool is past its high
        watermark: the caller gets a RETRYABLE shed rejection (ServerBusy
        serializes as kind="shed" on the transport, so clients back off
        and retry instead of treating the liaison as dead), and already-
        acked rows keep their bounded, eventually-shipped spool."""
        with self._lock:
            over = self._spool_bytes > self.max_spool_bytes
            spooled = self._spool_bytes
        if over:
            from banyandb_tpu.admin.protector import ServerBusy
            from banyandb_tpu.obs.metrics import global_meter

            global_meter().counter_add("wqueue_shed", 1.0)
            raise ServerBusy(
                f"write queue spool over high watermark "
                f"({spooled} > {self.max_spool_bytes} bytes); retry later"
            )

    # -- append path --------------------------------------------------------
    def append(self, req: WriteRequest) -> int:
        """Route points into per-(group, measure, shard) buffers; returns
        the accepted count.  Same shard routing as the synchronous path
        (entity hash -> seriesID -> shard).  The queue lock is held for
        the whole batch so a concurrent seal can never orphan a buffer
        between lookup and append (acknowledged rows must reach a seal)."""
        self._admit()
        m = self.registry.get_measure(req.group, req.name)
        shard_num = self.registry.get_group(req.group).resource_opts.shard_num
        tag_names = [t.name for t in m.tags]
        field_names = [f.name for f in m.fields]
        full = set()
        with self._lock:
            for p in req.points:
                entity = [req.name.encode()] + [
                    hashing.entity_bytes(p.tags[t]) for t in m.entity.tag_names
                ]
                sid = hashing.series_id(entity)
                shard = hashing.shard_id(sid, shard_num)
                key = ("measure", req.group, req.name, shard)
                buf = self._buffers.get(key)
                if buf is None:
                    buf = self._buffers[key] = MemTable(tag_names, field_names)
                tag_bytes = {
                    t: hashing.entity_bytes(p.tags[t])
                    if p.tags.get(t) is not None
                    else b""
                    for t in tag_names
                }
                fields = {f: float(p.fields.get(f, 0)) for f in field_names}
                version = p.version or int(time.time() * 1000)
                buf.append(p.ts_millis, sid, version, tag_bytes, fields)
                if len(buf) >= self.max_rows:
                    full.add(key)
        for key in full:
            self._seal(key)
        return len(req.points)

    def append_stream(self, group: str, name: str, elements) -> int:
        """Stream twin of append(): elements (models.stream.ElementValue)
        buffer per (group, stream, shard) with the element-id+body
        payload column, sealing into stream parts the data node
        introduces identically to its own flushes."""
        from banyandb_tpu.models.stream import encode_element_payload

        self._admit()
        st = self.registry.get_stream(group, name)
        shard_num = self.registry.get_group(group).resource_opts.shard_num
        tag_names = [t.name for t in st.tags]
        full = set()
        with self._lock:
            for e in elements:
                entity = [name.encode()] + [
                    hashing.entity_bytes(e.tags[t]) for t in st.entity
                ]
                sid = hashing.series_id(entity)
                shard = hashing.shard_id(sid, shard_num)
                key = ("stream", group, name, shard)
                buf = self._buffers.get(key)
                if buf is None:
                    buf = self._buffers[key] = MemTable(
                        tag_names, [], with_payload=True
                    )
                tag_bytes = {
                    t: hashing.entity_bytes(e.tags[t])
                    if e.tags.get(t) is not None
                    else b""
                    for t in tag_names
                }
                buf.append(
                    e.ts_millis,
                    sid,
                    0,
                    tag_bytes,
                    {},
                    payload=encode_element_payload(e.element_id, e.body),
                )
                if len(buf) >= self.max_rows:
                    full.add(key)
        for key in full:
            self._seal(key)
        return len(elements)

    def append_trace(self, group: str, name: str, spans, ordered_tags=()) -> int:
        """Trace twin of append(): spans (models.trace.SpanValue) buffer
        per (group, trace, shard) — trace routing hashes the TRACE ID
        (partition.TraceShardID), not the series — with the opaque span
        payload.  ordered_tags ride in part meta so the data node can
        rebuild sidx entries on install."""
        from banyandb_tpu.models.trace import trace_shard_id

        self._admit()
        t = self.registry.get_trace(group, name)
        shard_num = self.registry.get_group(group).resource_opts.shard_num
        tag_names = [x.name for x in t.tags]
        full = set()
        with self._lock:
            for sp in spans:
                trace_id = str(sp.tags[t.trace_id_tag])
                sid = hashing.series_id([name.encode(), trace_id.encode()])
                shard = trace_shard_id(trace_id, shard_num)
                key = ("trace", group, name, shard)
                buf = self._buffers.get(key)
                if buf is None:
                    buf = self._buffers[key] = MemTable(
                        tag_names, [], with_payload=True
                    )
                # union across calls: a later batch naming MORE ordered
                # tags must not be silently ignored for this buffer
                prev = self._trace_meta.get(key, ())
                self._trace_meta[key] = tuple(
                    dict.fromkeys((*prev, *ordered_tags))
                )
                tag_bytes = {
                    x: hashing.entity_bytes(sp.tags[x])
                    if sp.tags.get(x) is not None
                    else b""
                    for x in tag_names
                }
                buf.append(sp.ts_millis, sid, 0, tag_bytes, {}, payload=sp.span)
                if len(buf) >= self.max_rows:
                    full.add(key)
        for key in full:
            self._seal(key)
        return len(spans)

    # -- seal + ship --------------------------------------------------------
    def _seal(self, key: tuple[str, str, str, int]) -> None:
        """Swap the buffer out and write its rows as sealed parts in the
        spool — one part per storage segment (rows spanning a segment
        boundary must not land in one part: the receiver installs a part
        into a single segment, and rows outside it would be invisible to
        time-pruned queries).  On write failure the buffer is restored so
        acknowledged rows are never dropped."""
        catalog, group, resource, shard = key
        with self._lock:
            buf = self._buffers.pop(key, None)
        if buf is None or len(buf) == 0:
            return
        tmp_parents: list[Path] = []
        sealed: list[tuple[str, int, Path]] = []
        try:
            # disk-fault boundary (cluster/faults.py): ENOSPC raises here
            # (rows restored below); a "short" decision tears the first
            # staged write so the cleanup path is exercised too
            torn = faults.check_disk("wqueue-seal")
            cols = buf.snapshot_columns()
            iv = self.registry.get_group(group).resource_opts.segment_interval.millis
            seg_starts = cols.ts - (cols.ts % iv)
            import numpy as np

            # All segment-split parts are written under .tmp dirs first and
            # renamed only after EVERY one succeeds — a mid-seal failure
            # must not leave a recoverable orphan part while the same rows
            # are also restored to the buffer (double delivery).
            staged: list[tuple[Path, Path]] = []
            for start in np.unique(seg_starts).tolist():
                mask = seg_starts == start
                session = uuid.uuid4().hex
                final_parent = self.spool / f"{group}@{resource}@{shard}@{session}"
                tmp_parent = self.spool / f".tmp-{session}"
                tmp_parents.append(tmp_parent)
                payloads = None
                if cols.payloads is not None:
                    payloads = [p for p, k in zip(cols.payloads, mask) if k]
                extra_meta = {
                    catalog: resource,
                    "group": group,
                    "catalog": catalog,
                    # unique per seal: receiver-side dedup must distinguish
                    # re-delivery of THIS part from an independent later
                    # seal of byte-identical content (client retry batch)
                    "seal_session": session,
                    # row count stamped for the receiver's ingest-side
                    # consumers (the streamagg install hook short-
                    # circuits empty parts on it without a part read)
                    "rows": int(np.count_nonzero(mask)),
                }
                if catalog == "trace":
                    extra_meta["ordered_tags"] = list(
                        self._trace_meta.get(key, ())
                    )
                if torn:
                    import errno as _errno

                    tmp_parent.mkdir(parents=True, exist_ok=True)
                    (tmp_parent / "part-000000.torn").write_bytes(b"\0" * 8)
                    raise OSError(
                        _errno.EIO, "injected short write at wqueue seal"
                    )
                PartWriter.write(
                    tmp_parent / "part-000000",
                    ts=cols.ts[mask],
                    series=cols.series[mask],
                    version=cols.version[mask],
                    tag_codes={t: v[mask] for t, v in cols.tags.items()},
                    tag_dicts=dict(cols.dicts),
                    fields={f: v[mask] for f, v in cols.fields.items()},
                    extra_meta=extra_meta,
                    payloads=payloads,
                )
                staged.append((tmp_parent, final_parent))
            for tmp_parent, final_parent in staged:
                tmp_parent.rename(final_parent)
                sealed.append((group, shard, final_parent / "part-000000"))
            sizes = {
                str(p): _dir_bytes(p.parent) for _g, _s, p in sealed
            }
            with self._lock:
                self._pending.extend(sealed)
                self._part_bytes.update(sizes)
                self._spool_bytes += sum(sizes.values())
            from banyandb_tpu.obs.metrics import global_meter

            global_meter().counter_add(
                "wqueue_sealed_rows", float(len(buf))
            )
        except Exception:
            # undo everything (renamed-but-unregistered parts included):
            # the restored rows below are the single surviving copy
            for tmp_parent in tmp_parents:
                shutil.rmtree(tmp_parent, ignore_errors=True)
            for _g, _s, part_dir in sealed:
                shutil.rmtree(part_dir.parent, ignore_errors=True)
            # restore the rows: seal again next tick (merge into any new
            # buffer created meanwhile)
            with self._lock:
                cur = self._buffers.get(key)
                if cur is None or len(cur) == 0:
                    self._buffers[key] = buf
                else:
                    snap = buf.snapshot_columns()
                    cur.append_bulk(
                        snap.ts,
                        snap.series,
                        snap.version,
                        {
                            t: [snap.dicts[t][c] for c in snap.tags[t]]
                            for t in snap.tags
                        },
                        dict(snap.fields),
                        payloads=snap.payloads,
                    )
            raise

    def seal_all(self) -> None:
        with self._lock:
            keys = list(self._buffers.keys())
        errors = []
        for key in keys:
            try:
                self._seal(key)
            except Exception as e:  # noqa: BLE001 - other keys still seal
                errors.append(e)
        if errors:
            raise errors[0]

    def ship_pending(self, *, force: bool = False) -> tuple[int, int]:
        """Try to ship every sealed part that is DUE; -> (shipped,
        failed).  A part whose last attempt failed waits out its
        exponential backoff (base * 2^attempts, capped, +25% jitter)
        before the next try — deferred parts count as neither shipped
        nor failed.  ``force=True`` ignores the backoff clock (final
        flush at stop, post-recovery drains)."""
        from banyandb_tpu.obs.metrics import global_meter

        now = time.monotonic()
        with self._lock:
            pending, self._pending = self._pending, []
            if pending:
                self._inflight += len(pending)
                self._ships_idle.clear()
        shipped = failed = 0
        still: list[tuple[str, int, Path]] = []
        try:
            for group, shard, part_dir in pending:
                key = str(part_dir)
                attempts, next_try = self._retry.get(key, (0, 0.0))
                if not force and now < next_try:
                    still.append((group, shard, part_dir))  # not due yet
                    continue
                try:
                    self.shipper(group, shard, part_dir)
                    shutil.rmtree(part_dir.parent, ignore_errors=True)
                    shipped += 1
                    with self._lock:
                        self._retry.pop(key, None)
                        freed = self._part_bytes.pop(key, 0)
                        self._spool_bytes = max(0, self._spool_bytes - freed)
                    global_meter().counter_add("wqueue_shipped", 1.0)
                except Exception:  # noqa: BLE001 - retried after backoff
                    attempts += 1
                    delay = min(
                        self.retry_cap_s,
                        self.retry_base_s * (2 ** (attempts - 1)),
                    )
                    delay *= 1.0 + 0.25 * self._jitter.random()
                    with self._lock:
                        self._retry[key] = (attempts, time.monotonic() + delay)
                    still.append((group, shard, part_dir))
                    failed += 1
                    global_meter().counter_add("wqueue_ship_retry", 1.0)
        finally:
            with self._lock:
                self._pending.extend(still)
                self._inflight -= len(pending)
                if not self._inflight:
                    self._ships_idle.set()
                global_meter().gauge_set("wqueue_spool_bytes", self._spool_bytes)
        return shipped, failed

    def flush(self, *, force: bool = False) -> tuple[int, int]:
        """Seal everything and attempt shipping (one tick, also the test
        hook).  Returns once no ship is in flight: parts a concurrent
        tick took are delivered (or back on the pending list) too, so
        "flushed" means what it says to the caller."""
        self.seal_all()
        out = self.ship_pending(force=force)
        self._ships_idle.wait(timeout=60.0)
        return out

    def pending_parts(self) -> int:
        with self._lock:
            return len(self._pending) + self._inflight

    def buffered_rows(self) -> int:
        with self._lock:
            return sum(len(b) for b in self._buffers.values())

    def spool_bytes(self) -> int:
        with self._lock:
            return self._spool_bytes

    # -- lifecycle ----------------------------------------------------------
    def _recover_spool(self) -> list[tuple[str, int, Path]]:
        out = []
        for d in sorted(self.spool.iterdir()) if self.spool.exists() else []:
            if d.is_dir() and d.name.startswith(".tmp"):
                # crashed mid-seal: rows never left the (lost) buffer OR
                # were restored and resealed — either way this is garbage
                shutil.rmtree(d, ignore_errors=True)
                continue
            if not d.is_dir() or "@" not in d.name:
                continue
            try:
                group, _measure, shard, _session = d.name.split("@", 3)
                part_dir = d / "part-000000"
                if (part_dir / "metadata.json").exists():
                    out.append((group, int(shard), part_dir))
                else:  # crashed mid-write: the part is not durable yet
                    shutil.rmtree(d, ignore_errors=True)
            except (ValueError, OSError):
                continue
        return out

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()

        import logging

        log = logging.getLogger("banyandb.wqueue")

        def loop():
            while not self._stop.wait(self.flush_interval_s):
                try:
                    self.flush()
                except Exception:  # noqa: BLE001 - the loop must survive
                    log.exception("wqueue flush tick failed (rows retained)")

        self._thread = threading.Thread(target=loop, daemon=True, name="wqueue")
        self._thread.start()

    def stop(self, final_flush: bool = True) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        if final_flush:
            # the last chance to drain before shutdown ignores backoff
            self.flush(force=True)
