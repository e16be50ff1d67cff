"""Streaming chunked part sync on the reference wire protocol.

Implements banyandb.cluster.v1.ChunkedSyncService/SyncPart (bidi stream;
/root/reference/api/proto/banyandb/cluster/v1/rpc.proto,
banyand/queue/pub/chunked_sync.go sender + sub side receiver): sealed
parts ship as raw binary 1 MiB chunks with per-chunk CRC32 and a
files-within-parts layout (PartInfo/FileInfo offsets), replacing the
round-1 base64-in-JSON unary path — no 33% inflation, no whole-part
memory residency on the sender, streaming backpressure for free.

Wire layout: the byte stream is the concatenation of each part's files
(FileInfo.offset relative to the part's start, parts concatenated in
PartInfo order); chunk boundaries are arbitrary.  parts_info rides the
completion chunk.
"""

from __future__ import annotations

import json
import threading
import time
import uuid
import zlib
from pathlib import Path
from typing import Callable, Iterable

import grpc

from banyandb_tpu.api import pb

SERVICE = "banyandb.cluster.v1.ChunkedSyncService"
METHOD = f"/{SERVICE}/SyncPart"
CHUNK_SIZE = 1 << 20
API_VERSION = "1.0"
# how long a stream's iteration may outlive its status (sync_part_dirs)
_WEDGE_GRACE_S = 2.0


def _crc(data: bytes) -> str:
    return f"{zlib.crc32(data) & 0xFFFFFFFF:08x}"


# -- failure injection -------------------------------------------------------
#
# Deterministic fault injection for sync paths, mirroring the reference's
# queue.ChunkedSyncFailureInjector contract (banyand/queue/queue.go:230).
# Two sources, explicit registration winning: tests may register an
# injector directly; otherwise the process-global fault plane
# (cluster/faults.py, BYDB_FAULTS schedule) drives the same hooks when
# its schedule names sync faults.  Production with no plane configured
# injects nothing.


class SyncFailureInjector:
    """Override any subset; the default injects nothing."""

    def before_sync(self, part_dirs) -> tuple[bool, str]:
        """-> (short_circuit, error): True fails the sync before the
        stream opens (queue.go:234 BeforeSync analog)."""
        return (False, "")

    def mutate_request(self, req):
        """Per-chunk hook: return a (possibly corrupted) request, or
        raise to kill the stream mid-flight (wire-level fault)."""
        return req


_failure_injector: SyncFailureInjector | None = None


def register_failure_injector(inj: SyncFailureInjector | None) -> None:
    global _failure_injector
    _failure_injector = inj


def clear_failure_injector() -> None:
    register_failure_injector(None)


# -- server ----------------------------------------------------------------


def sync_method_handler(install_cb: Callable):
    """-> grpc stream_stream handler for SyncPart.

    install_cb(meta: SyncMetadata, parts: list[(PartInfo, {file: bytes})])
    is called once per completed session; it raises to fail the sync.
    """
    rpcpb = pb.cluster_rpc_pb2

    def sync_part(request_iterator, context):
        import os
        import tempfile

        meta = None
        expected = 0
        total = 0
        t0 = time.monotonic()
        # chunks spool to disk as they arrive, so receiver memory stays
        # O(chunk) regardless of part size; per-file slices are read back
        # at install (peak = largest single column file, not the part)
        spool = tempfile.NamedTemporaryFile(
            prefix="bydb-sync-", suffix=".spool", delete=False
        )
        try:
            for req in request_iterator:
                if req.chunk_index != expected:
                    yield rpcpb.SyncPartResponse(
                        session_id=req.session_id,
                        chunk_index=req.chunk_index,
                        status=3,  # SYNC_STATUS_CHUNK_OUT_OF_ORDER
                        error=f"expected chunk {expected}, got {req.chunk_index}",
                    )
                    return
                if req.chunk_data and _crc(req.chunk_data) != req.chunk_checksum:
                    yield rpcpb.SyncPartResponse(
                        session_id=req.session_id,
                        chunk_index=req.chunk_index,
                        status=2,  # SYNC_STATUS_CHUNK_CHECKSUM_MISMATCH
                        error="chunk CRC mismatch",
                    )
                    return
                if req.WhichOneof("content") == "metadata":
                    meta = req.metadata
                spool.write(req.chunk_data)
                total += len(req.chunk_data)
                expected += 1
                if req.WhichOneof("content") == "completion":
                    if meta is None:
                        yield rpcpb.SyncPartResponse(
                            session_id=req.session_id,
                            chunk_index=req.chunk_index,
                            status=4,  # SYNC_STATUS_SESSION_NOT_FOUND
                            error="completion without metadata",
                        )
                        return
                    spool.flush()
                    # split the stream into parts/files per the layout
                    parts = []
                    offset = 0
                    with open(spool.name, "rb") as rd:
                        for pi in req.parts_info:
                            files = {}
                            end = offset
                            for fi in pi.files:
                                rd.seek(offset + fi.offset)
                                files[fi.name] = rd.read(fi.size)
                                end = max(end, offset + fi.offset + fi.size)
                            parts.append((pi, files))
                            offset = end
                    results = []
                    ok = True
                    try:
                        install_cb(meta, parts)
                        results = [
                            rpcpb.PartResult(
                                success=True,
                                bytes_processed=sum(len(b) for b in f.values()),
                            )
                            for _, f in parts
                        ]
                    except Exception as e:  # noqa: BLE001 - reported in-band
                        ok = False
                        results = [rpcpb.PartResult(success=False, error=str(e))]
                    yield rpcpb.SyncPartResponse(
                        session_id=req.session_id,
                        chunk_index=req.chunk_index,
                        status=5 if ok else 4,  # COMPLETE | SESSION_NOT_FOUND
                        error="" if ok else results[0].error,
                        sync_result=rpcpb.SyncResult(
                            success=ok,
                            total_bytes_received=total,
                            duration_ms=int((time.monotonic() - t0) * 1000),
                            chunks_received=expected,
                            parts_received=len(parts),
                            parts_results=results,
                        ),
                    )
                    return
                yield rpcpb.SyncPartResponse(
                    session_id=req.session_id,
                    chunk_index=req.chunk_index,
                    status=1,  # SYNC_STATUS_CHUNK_RECEIVED
                )
        finally:
            spool.close()
            try:
                os.unlink(spool.name)
            except OSError:
                pass

    return grpc.stream_stream_rpc_method_handler(
        sync_part,
        request_deserializer=rpcpb.SyncPartRequest.FromString,
        response_serializer=lambda m: m.SerializeToString(),
    )


def generic_handler(install_cb: Callable):
    return grpc.method_handlers_generic_handler(
        SERVICE, {"SyncPart": sync_method_handler(install_cb)}
    )


# -- client ----------------------------------------------------------------


def _part_layout(part_dir: Path) -> tuple[list, list[Path], int]:
    """-> (FileInfo list, file paths in stream order, total bytes) for one
    part dir — stat-only, no file contents loaded."""
    rpcpb = pb.cluster_rpc_pb2
    files = []
    paths = []
    off = 0
    for f in sorted(part_dir.iterdir()):
        if not f.is_file():
            continue
        size = f.stat().st_size
        files.append(rpcpb.FileInfo(name=f.name, offset=off, size=size))
        paths.append(f)
        off += size
    return files, paths, off


def parse_epoch_topic(topic: str) -> "tuple[str, int | None]":
    """Split a ``<topic>@epoch=N`` wire topic -> (bare topic, epoch or
    None).  The placement-epoch fence rides the topic string because
    the SyncMetadata proto has no spare field; receivers parse it here
    and feed their EpochRecord (docs/robustness.md "Elastic cluster")."""
    base, sep, tail = topic.partition("@epoch=")
    if not sep:
        return topic, None
    try:
        return base, int(tail)
    except ValueError:
        return base, None


def sync_part_dirs(
    dial: Callable[[], grpc.Channel],
    part_dirs: Iterable[str | Path],
    *,
    group: str,
    shard_id: int,
    topic: str = "measure-part-sync",
    sender_node: str = "liaison",
    chunk_size: int = CHUNK_SIZE,
    timeout: float = 120.0,
    placement_epoch: "int | None" = None,
):
    """Ship sealed part dirs over one SyncPart stream; -> SyncResult.

    dial() -> the channel the stream rides, of its own: this closes it
    on return (GrpcTransport.channel dials one).

    placement_epoch: optional epoch fence — stamped as a ``@epoch=N``
    topic suffix so the receiver can reject sessions from a sender
    routing on a superseded placement map.

    Raises TransportError on any non-OK chunk status or stream failure,
    at the latest ``_WEDGE_GRACE_S`` after the call's status (which its
    deadline, ``timeout``, gives at the latest).
    """
    from banyandb_tpu.cluster import faults
    from banyandb_tpu.cluster.rpc import TransportError

    rpcpb = pb.cluster_rpc_pb2
    part_dirs = [Path(p) for p in part_dirs]
    injector = (
        _failure_injector
        if _failure_injector is not None
        else faults.plane_sync_injector()
    )
    if injector is not None:
        short, err = injector.before_sync(part_dirs)
        if short:
            raise TransportError(f"sync failure injected: {err}")
    session = uuid.uuid4().hex
    parts_info = []
    file_lists: list[list[Path]] = []
    total_bytes = 0
    for pd in part_dirs:
        files, paths, nbytes = _part_layout(pd)
        meta = {}
        try:
            meta = json.loads((pd / "metadata.json").read_bytes())
        except (OSError, json.JSONDecodeError):
            pass
        parts_info.append(
            rpcpb.PartInfo(
                id=int(pd.name.split("-")[-1], 16) if "-" in pd.name else 0,
                files=files,
                uncompressed_size_bytes=nbytes,
                total_count=int(meta.get("total_count", 0)),
                blocks_count=int(meta.get("blocks", 0)),
                min_timestamp=int(meta.get("min_ts", 0)),
                max_timestamp=int(meta.get("max_ts", 0)),
                part_type=topic.split("-")[0],
            )
        )
        file_lists.append(paths)
        total_bytes += nbytes

    def requests():
        # metadata and completion share a oneof, so the stream is always
        # [metadata+data chunk, data chunks..., completion-only chunk].
        # Files are read incrementally: at most ~one chunk is resident on
        # the sender at a time (parts may be big; the spool is on disk).
        idx = 0

        def mk(data: bytes):
            nonlocal idx
            req = rpcpb.SyncPartRequest(
                session_id=session,
                chunk_index=idx,
                chunk_data=data,
                chunk_checksum=_crc(data),
                version_info=rpcpb.VersionInfo(api_version=API_VERSION),
            )
            if idx == 0:
                req.metadata.group = group
                req.metadata.shard_id = shard_id
                req.metadata.topic = (
                    f"{topic}@epoch={placement_epoch}"
                    if placement_epoch is not None
                    else topic
                )
                req.metadata.total_parts = len(parts_info)
                req.metadata.sender_node = sender_node
            idx += 1
            if injector is not None:
                req = injector.mutate_request(req)
            return req

        buf = bytearray()
        for paths in file_lists:
            for path in paths:
                with open(path, "rb") as fh:
                    while True:
                        piece = fh.read(chunk_size)
                        if not piece:
                            break
                        buf.extend(piece)
                        while len(buf) >= chunk_size:
                            yield mk(bytes(buf[:chunk_size]))
                            del buf[:chunk_size]
        if buf or idx == 0:
            yield mk(bytes(buf))
        fin = rpcpb.SyncPartRequest(
            session_id=session,
            chunk_index=idx,
            chunk_checksum=_crc(b""),
        )
        fin.parts_info.extend(parts_info)
        fin.completion.total_bytes_sent = total_bytes
        fin.completion.total_parts_sent = len(parts_info)
        fin.completion.total_chunks = idx + 1
        if injector is not None:
            fin = injector.mutate_request(fin)
        yield fin

    with dial() as channel:
        call = channel.stream_stream(
            METHOD,
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=rpcpb.SyncPartResponse.FromString,
        )
        responses = call(requests(), timeout=timeout)
        # A call that fails as its peer goes away (stopped with grace=0)
        # can get its status while grpc (1.76) never completes its
        # pending receive: the iterator then waits forever, deadline or
        # not.  So the status starts a timer that closes the channel, which
        # completes every batch still pending on it, if the iteration has
        # not ended within the grace.
        guard = threading.Timer(_WEDGE_GRACE_S, channel.close)
        if not responses.add_callback(guard.start):
            guard.start()  # the status is in already
        final = None
        try:
            for resp in responses:
                if resp.status not in (1, 5):  # RECEIVED | COMPLETE
                    raise TransportError(
                        f"sync chunk {resp.chunk_index} failed: "
                        f"status={resp.status} {resp.error}"
                    )
                if resp.status == 5:
                    final = resp.sync_result
        except grpc.RpcError as e:
            raise TransportError(f"sync stream failed: {e.code()}") from e
        finally:
            guard.cancel()
            if guard.ident is not None:
                guard.join()
    if final is None or not final.success:
        raise TransportError(
            f"sync incomplete: {final.parts_results[0].error if final and final.parts_results else 'no completion'}"
        )
    return final
