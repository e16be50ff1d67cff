"""Device-side decode kernels.

The reference decodes int64 columns on the CPU with delta / delta-of-delta +
zigzag varint (pkg/encoding/int_list.go:27) and dictionary-encodes low-
cardinality byte columns (pkg/encoding/dictionary.go).  On TPU, variable-
width varint decode is hostile to the VPU, so the on-disk format (see
banyandb_tpu.utils.encoding) stores *fixed-width* deltas; the prefix-sum
reconstruction and dictionary gather run on device where they fuse into the
scan pipeline.
"""

from __future__ import annotations

import jax.numpy as jnp


def delta_decode(first, deltas):
    """Reconstruct the FULL series of ``len(deltas) + 1`` values:
    out[0] == first, out[i] == first + sum(deltas[:i]).

    Matches the on-disk encoder (utils/encoding.encode_int64: `first` stored
    separately + np.diff payload) so a device caller can feed the decoded
    delta payload directly.  Mirrors encoding.EncodeTypeDelta
    (pkg/encoding/int_list.go:60) as a cumsum instead of a sequential loop.
    Narrow i8/i16 delta payloads always widen to i32 first (a narrow
    cumsum would wrap), so the output dtype is max(deltas.dtype, i32) on
    every backend.  ``first`` must fit the compute dtype — raw int64
    column heads (absolute timestamps) must be REBASED by the caller
    (the chunk pipeline's epoch-relative convention) or decoded with
    i64 deltas under host x64; a concrete out-of-range ``first`` raises
    instead of silently wrapping.
    """
    import numpy as _np

    if deltas.dtype in (jnp.int8, jnp.int16):
        deltas = deltas.astype(jnp.int32)
    if (
        isinstance(first, (int, _np.integer))  # concrete host scalar
        and deltas.dtype == jnp.int32
        and not -(2**31) <= first < 2**31
    ):
        raise ValueError(
            f"first={first} does not fit the i32 decode width; "
            "rebase it to an epoch offset (ts - epoch) or pass i64 deltas"
        )
    first = jnp.asarray(first, dtype=deltas.dtype)
    rest = first[..., None] + jnp.cumsum(deltas, axis=-1, dtype=deltas.dtype)
    head = jnp.broadcast_to(first[..., None], rest.shape[:-1] + (1,))
    return jnp.concatenate([head, rest], axis=-1)


def dod_decode(first, first_delta, dods):
    """Delta-of-delta decode (pkg/encoding/int_list.go:66 analog).

    Reconstructs the FULL series of ``len(dods) + 1`` values from second
    differences with two cumsums: out[0] == first,
    out[1] == first + first_delta + dods[0] (encoders emit dods[0] = 0),
    out[i] == out[i-1] + (first_delta + sum(dods[:i])).
    """
    first = jnp.asarray(first, dtype=dods.dtype)
    deltas = first_delta + jnp.cumsum(dods, axis=-1, dtype=dods.dtype)
    rest = first[..., None] + jnp.cumsum(deltas, axis=-1, dtype=deltas.dtype)
    head = jnp.broadcast_to(first[..., None], rest.shape[:-1] + (1,))
    return jnp.concatenate([head, rest], axis=-1)


def dict_gather(dictionary, codes):
    """Materialize dictionary-encoded values: out[i] = dictionary[codes[i]].

    The scan pipeline usually *avoids* this by pushing predicates onto the
    codes themselves (storage-and-format.md§7.3 dictionary-as-filter); this
    exists for projections of numeric dictionary columns.  Out-of-range
    codes clip to the dictionary bounds instead of wrapping (the OOB
    guard: a corrupt code must never read another row's slot).
    """
    return jnp.take(dictionary, codes, axis=0, mode="clip")


def widen_codes(codes):
    """Narrow stored-width dict codes (i8/i16) -> the i32 the plan
    kernels consume.  THE hot decode op of the compressed-ship path: the
    column crossed PCIe at stored width and widens here, on device."""
    return codes.astype(jnp.int32)


def dict_remap(codes, lut2d, src_ord):
    """Local -> global dictionary code remap, on device.

    ``codes``: narrow per-row LOCAL codes (any shape), ``src_ord``: the
    per-row source ordinal (same shape), ``lut2d``: ``[S, L]`` i32 table
    whose row ``s`` maps source s's local codes to global codes
    (storage/encoded.pack_luts).  Replaces the host-side per-source
    ``lut[codes]`` gather of the decoded path; exact integer math, so
    the A/B is byte-identical.  The flattened take clips (OOB guard) —
    in-range by construction, never wrapping on corrupt input."""
    flat = lut2d.reshape(-1)
    idx = (
        src_ord.astype(jnp.int32) * lut2d.shape[-1]
        + codes.astype(jnp.int32)
    )
    return jnp.take(flat, idx, mode="clip")


def ints_to_f32(vals):
    """Narrow int field column -> f32, on device.  Exact (and therefore
    byte-identical to the host f64 -> f32 cast) because every i8/i16
    value is representable in f32."""
    return vals.astype(jnp.float32)


def decode_chunk(chunk: dict) -> dict:
    """The device-side decode stage: encoded chunk pytree -> the
    canonical chunk the plan kernels consume.

    Runs inside the fused plan program's scan step (query/fused_exec
    applies it to ONE ``[nrows]`` chunk, in the branch a chunk with a
    valid row takes), so decode work fuses into the one dispatch per
    part-batch instead of running as host numpy in the gather stage,
    and a padding chunk of the bucket is not widened.

    Encoded chunks carry (pad/ship stage, fused_exec._stacked_chunks):

    - ``tags_enc``  narrow local dict codes per tag column
    - ``tags_lut``  [S, L] local->global LUT per tag column (one per
      batch: the scan closes over it, every chunk sees the same)
    - ``src_ord``   per-row source ordinal (shared by all tag columns)
    - ``fields_enc``  narrow exact-int field columns

    A chunk with nothing to decode (no tag column, every field shipped
    as f32) passes through unchanged.
    """
    if "tags_enc" not in chunk and "fields_enc" not in chunk:
        return chunk
    out = {
        k: v
        for k, v in chunk.items()
        if k not in ("tags_enc", "tags_lut", "src_ord", "fields_enc")
    }
    import jax

    with jax.named_scope("bydb.decode"):
        tags_code = dict(out.get("tags_code", {}))
        for t, codes in chunk.get("tags_enc", {}).items():
            tags_code[t] = dict_remap(
                codes, chunk["tags_lut"][t], chunk["src_ord"]
            )
        out["tags_code"] = tags_code
        fields = dict(out.get("fields", {}))
        for f, vals in chunk.get("fields_enc", {}).items():
            fields[f] = ints_to_f32(vals)
        out["fields"] = fields
    return out
