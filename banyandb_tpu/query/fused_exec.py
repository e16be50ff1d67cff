"""Fused whole-plan executor: one XLA program per plan signature.

Tailwind (arXiv 2604.28079) argues the accelerator win comes from
compiling the *whole* query, not offloading operators; this module is
that compiler for the measure plan family: filter + group-by +
aggregate + the rank inputs (TopN metric vectors, percentile
histograms) execute as ONE jitted program per plan signature, so a
part-batch crosses the accelerator boundary exactly once — one dispatch
in, one batched device_get out.  It is the only executor of a measure
plan (``measure_exec._reduce_partials`` always comes here).

- the program ``lax.scan``s the per-chunk body
  (``measure_exec._kernel_body``) over a ``[C, nrows]`` stacked chunk
  batch and returns the per-chunk f32 partials stacked ``[C, ...]`` —
  the host then folds them into the f64 accumulators in scan order.
  A chunk with no valid row (the bucket's padding) is branched past on
  the device: no decode, no body.
- a percentile plan's histogram is not a per-chunk partial: the scan
  carries ONE exact int32 histogram (``[G * 512]``, flat: the layout
  the device's scatter writes) across its chunks and
  batches, and it leaves the device once a query at most — where the
  caller combines partials — or never: where the partial is finalized
  alone the last batch's program ends with the CDF inversion
  (``ops.invert_histogram``) and returns ``[G, Q, 3]`` integers.
- a scan whose stacked footprint passes the device budget
  (``BYDB_FUSED_MAX_MB``) runs the SAME program over consecutive chunk
  batches, one after another (``plan_batches``), the histogram handed
  from one to the next on the device: same per-chunk graph, same absorb
  order => byte-identical partials and results whatever the batching.
- group-by strategy (hash/scatter vs segment-sort, per arXiv
  2411.13245) resolves through ``ops.groupby.select_group_method`` from
  the signature's (nrows, num_groups).

Signature lifecycle: the chunk-count bucket rides the jit key
(``FusedSpec = PlanSpec + num_chunks``, power-of-two buckets keep the
compiled-shape set finite), every resolution is recorded in the
precompile registry under kind="fused" (cold starts warm the fused
kernels), and the bdjit kernel audit pins each builtin fused signature
to dispatches=1 / gets=1 in ``lint/kernel/kernel_budgets.py`` so
staging can never silently creep back.

The mesh half (``build_fused_dist_step``) shard_maps the same chunked
scan over a ('shard','seg') device mesh with the dist-path collectives
(psum count/sums/hist + pmin/pmax), so a distributed scan is one
collective program with a BOUNDED compile-shape set instead of one
unbounded-width kernel per row-count bucket.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from banyandb_tpu.obs import tracer
from banyandb_tpu.query.measure_exec import DeviceLeg, PlanSpec, _kernel_body
from banyandb_tpu.utils.envflag import env_int


# fused dispatches issued and not yet fetched, over every query of the
# process: one chip runs them one after another, so a dispatch issued
# behind others waits for theirs inside its own device_get
_OUTSTANDING = 0
_OUTSTANDING_LOCK = threading.Lock()


def dispatches_outstanding() -> int:
    """Fused dispatches issued and not yet fetched, now (/metrics
    ``fused_dispatches_outstanding``)."""
    with _OUTSTANDING_LOCK:
        return _OUTSTANDING


def max_fused_mb() -> int:
    """Device-footprint ceiling for one fused dispatch (stacked input
    columns + stacked per-chunk partials).  A scan whose one-shot
    footprint exceeds it (e.g. a huge-G percentile over many chunks,
    where the stacked [C, G, 512] histogram explodes) runs in chunk
    batches (``plan_batches``) instead of OOMing the device."""
    return env_int("BYDB_FUSED_MAX_MB", 1024)


@dataclass(frozen=True)
class FusedSpec:
    """Static jit key of one fused program: the plan signature plus the
    chunk-count bucket the part-batch is stacked into."""

    plan: PlanSpec
    num_chunks: int
    # > 0: a percentile plan's program ends with the inversion for this
    # many quantiles and returns the ranks, not the histogram
    quantiles: int = 0


def chunk_count_bucket(n_chunks: int) -> int:
    """Power-of-two chunk-count buckets: the compiled-shape set stays
    O(log max_chunks); chunks beyond the real count are fully invalid
    (valid=False everywhere): the program branches past them on the
    device (``_build_kernel``) and the host absorbs only the real
    ones, so the padding costs the host's pad + ship and nothing else."""
    b = 1
    while b < n_chunks:
        b <<= 1
    return b


def key_columns(spec: PlanSpec) -> tuple[str, ...]:
    """The per-row columns besides the tag and field columns that the
    plan's program reads, so the only ones a chunk batch pads and ships:
    ``valid`` always (the mask, and the branch past a padding chunk);
    the epoch-relative ``ts`` and the global ``row`` only where the
    program tracks scan order (``bydb.rep``).  No program reads the
    series id: an ungrouped plan's key is zeros of ``valid``'s shape."""
    return ("ts", "valid", "row") if spec.want_rep else ("valid",)


_KERNEL_CACHE: dict[FusedSpec, object] = {}


def _build_kernel(fspec: FusedSpec):
    """jit the whole-plan program: scan the shared per-chunk body over
    the stacked chunk axis, emitting stacked per-chunk partials.

    A chunk that holds no valid row (the padding chunks of the
    chunk-count bucket) does no work: the step branches on what it
    observes in its input (``lax.cond`` on ``any(valid)``: control flow
    on the device, not a select) and a padding chunk's partials are
    zeros the host never reads (``run_fused`` absorbs real chunks only).

    Compressed part-batches (``BYDB_DEVICE_DECODE``) decode inside the
    step's taken branch: ops.decode.decode_chunk widens/remaps ONE
    chunk's per-row leaves (the per-batch ``[S, L]`` remap LUTs are
    closed over: loop invariants, not scanned leaves), then the body
    sees a canonical chunk.  Elementwise integer decode, so the two
    ship forms stay byte-identical and a padding chunk is not widened
    either."""
    from banyandb_tpu import ops

    body = _kernel_body(fspec.plan)
    plan = fspec.plan

    # the name is the device trace's module line: jit_bydb_fused_plan
    def bydb_fused_plan(
        chunks: dict, pred_vals: dict, hist_lo, hist_span, hist=None,
        quantiles=None,
    ):
        per_row = {k: v for k, v in chunks.items() if k != "tags_lut"}
        per_batch = {k: v for k, v in chunks.items() if k == "tags_lut"}

        def real_chunk(chunk, hist):
            chunk = ops.decode_chunk({**chunk, **per_batch})
            return body(chunk, pred_vals, hist_lo, hist_span, hist)

        def padding_chunk(chunk, hist):
            out = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype),
                jax.eval_shape(real_chunk, chunk, hist),
            )
            if hist is not None:
                out["hist"] = hist  # a padding chunk adds nothing
            return out

        def step(hist, chunk):
            out = jax.lax.cond(
                jnp.any(chunk["valid"]), real_chunk, padding_chunk, chunk, hist
            )
            return out.pop("hist", None), out

        if plan.hist_field and hist is None:  # the scan's first batch
            hist = jnp.zeros(plan.num_groups * _num_hist_buckets(), jnp.int32)
        with jax.named_scope("bydb.fused_scan"):
            hist, stacked = jax.lax.scan(step, hist, per_row)
        if hist is not None:
            stacked["hist"] = hist  # flat [G * 512]
            if fspec.quantiles:
                with jax.named_scope("bydb.invert"):
                    r = ops.invert_histogram(
                        hist.reshape(plan.num_groups, -1), quantiles
                    )
                    stacked["ranks"] = jnp.stack([r.hit, r.before, r.at], axis=-1)
        return stacked

    # the carried histogram is updated in place: each batch's program
    # takes over the buffer the previous one returned
    return jax.jit(bydb_fused_plan, donate_argnames=("hist",))


def _num_hist_buckets() -> int:
    from banyandb_tpu.query import measure_exec

    return measure_exec._NUM_HIST_BUCKETS


def estimate_bytes(spec: PlanSpec, num_chunks: int) -> int:
    """Device footprint of one fused part-batch: stacked input columns
    plus the stacked per-chunk partials pytree, plus once (not per
    chunk) a percentile plan's carried int32 histogram.

    Under ``BYDB_DEVICE_DECODE`` the ceiling accounts the compressed
    inputs (narrow tag/field buffers, the i16 src-ordinal column)
    ALONGSIDE a decoded i32/f32 copy of every chunk.  Conservative
    since the decode moved into the scan step (one chunk's decoded copy
    is live at a time) and left so: no scan is near the budget, and
    moving the estimate moves the split points.  (The [S, L] remap
    LUTs are a rounding error next to the per-row columns and ride the
    same margin.)"""
    from banyandb_tpu.storage import encoded as enc_mod

    g = spec.num_groups
    nf = len(spec.fields)
    per_chunk_out = g * (1 + nf + (2 * nf if spec.want_minmax else 0))
    if spec.want_rep:
        per_chunk_out += 2 * g
    hist = g * _num_hist_buckets() if spec.hist_field else 0
    cols = len(key_columns(spec)) + len(spec.tags_code) + nf
    per_row = 4 * cols
    if enc_mod.device_decode_enabled():
        # narrow inputs (<=2 B/row per tag/field) + src_ord (2 B/row)
        per_row += 2 + 2 * (len(spec.tags_code) + nf)
    return num_chunks * (per_row * spec.nrows + 4 * per_chunk_out) + 4 * hist


def _resolve_bucket(n_chunks: int, min_bucket: int | None) -> int:
    """The chunk-count bucket for a part-batch, honoring the planner's
    minimum-bucket hint.  The hint only ever rounds UP (padding chunks
    are fully invalid: skipped on the device, never absorbed by the
    host — byte-identical)
    and is capped at one doubling of the actual bucket: the hint exists
    for part populations oscillating around a bucket boundary, not to
    pad a 1-chunk batch into a 64-chunk program."""
    bucket = chunk_count_bucket(n_chunks)
    if min_bucket is not None and bucket < min_bucket <= bucket * 2:
        return min_bucket
    return bucket


def plan_batches(
    spec: PlanSpec,
    chunk_spans: list[tuple[int, int]],
    min_bucket: int | None = None,
) -> tuple[int, list[list[tuple[int, int]]]]:
    """How a scan's chunks reach the device: -> (chunk-count bucket,
    consecutive batches of spans), each batch ONE dispatch of the
    bucket's program.

    A scan whose bucket fits the device budget (``max_fused_mb``) is
    one batch.  A scan over it runs in batches of ``b`` chunks, ``b``
    the largest power of two whose footprint fits and never under 1 (a
    single chunk over the budget still runs); every batch, a short last
    one too, uses the ``b``-chunk program.  The planner's ``min_bucket``
    hint is honoured only where the rounded-up bucket fits."""
    if not chunk_spans:
        return 1, []
    budget = max_fused_mb() * (1 << 20)
    bucket = _resolve_bucket(len(chunk_spans), min_bucket)
    if estimate_bytes(spec, bucket) <= budget:
        return bucket, [chunk_spans]
    b = 1
    while b < len(chunk_spans) and estimate_bytes(spec, 2 * b) <= budget:
        b *= 2
    return b, [chunk_spans[i : i + b] for i in range(0, len(chunk_spans), b)]


def _stacked_chunks(
    cols: dict,
    spans: list[tuple[int, int]],
    spec: PlanSpec,
    num_chunks: int,
    epoch: int,
    pack_s: list | None = None,
    h2d_s: list | None = None,
    ship_stats: list | None = None,
    pack_use: list | None = None,
) -> dict:
    """Pad the gathered columns into ``[C, nrows]`` device arrays.

    THE padded chunk layout (per-row dtypes, zero padding, and of the
    epoch-relative int32 ts, the valid mask and the global row index
    what ``key_columns`` says the program reads), in either ship
    form: compressed snapshots (``BYDB_DEVICE_DECODE``) stack the narrow
    local tag codes, the per-row source ordinals and exact-int fields,
    plus the per-batch [S, L] remap LUTs the in-program decode stage
    consumes; dense ones the i32 global codes and f32 fields.  Per-column
    pad work rides the chunk_stream prefetch worker (BYDB_PIPELINE
    honored) so padding column j+1 overlaps shipping column j.
    ``pack_s`` collects the pad thunks' seconds (worker thread),
    ``h2d_s`` the ``jnp.asarray`` ships' (this thread); ``pack_use``
    (given when the query's spans read their threads' clocks) one
    (seconds off the CPU, minor page faults) pair a pad thunk: whether
    the worker ran or waited (``obs/tracer.thread_usage``); ``ship_stats``
    one (shipped, dense, packed) byte triple for the whole part-batch
    (decode-span attribution): shipped counts the tag and field columns,
    packed every array padded and shipped.
    """
    from banyandb_tpu.storage.chunk_stream import prefetched

    C, nb = num_chunks, spec.nrows
    compressed = "src_ord" in cols

    def pad2(get, dtype):
        out = np.zeros((C, nb), dtype=dtype)
        for k, (s, e) in enumerate(spans):
            out[k, : e - s] = get(s, e)
        return out

    def valid2():
        out = np.zeros((C, nb), dtype=bool)
        for k, (s, e) in enumerate(spans):
            out[k, : e - s] = True
        return out

    key_thunks = {
        "ts": lambda: pad2(lambda s, e: cols["ts"][s:e] - epoch, np.int32),
        "valid": valid2,
        "row": lambda: pad2(
            lambda s, e: np.arange(s, e, dtype=np.int32), np.int32
        ),
    }
    paths: list[tuple] = [(k,) for k in key_columns(spec)]
    thunks = [key_thunks[k] for k in key_columns(spec)]
    counted: set = set()
    if compressed:
        from banyandb_tpu.storage import encoded as enc_mod

        if spec.tags_code:
            for t in spec.tags_code:
                paths.append(("tags_enc", t))
                counted.add(("tags_enc", t))
                thunks.append(
                    lambda t=t: pad2(
                        lambda s, e: cols["tags_enc"][t][s:e],
                        cols["tags_enc"][t].dtype,
                    )
                )
                paths.append(("tags_lut", t))
                counted.add(("tags_lut", t))
                thunks.append(
                    lambda t=t: enc_mod.pack_luts(cols["tags_lut"][t])
                )
            paths.append(("src_ord",))
            counted.add(("src_ord",))
            thunks.append(
                lambda: pad2(
                    lambda s, e: cols["src_ord"][s:e], enc_mod.SRC_ORD_DTYPE
                )
            )
        for f in spec.fields:
            ndt = cols["fields_narrow"].get(f)
            if ndt is not None:
                paths.append(("fields_enc", f))
                counted.add(("fields_enc", f))
                thunks.append(
                    lambda f=f, ndt=ndt: pad2(
                        lambda s, e: cols["fields"][f][s:e], ndt
                    )
                )
            else:
                paths.append(("fields", f))
                counted.add(("fields", f))
                thunks.append(
                    lambda f=f: pad2(
                        lambda s, e: cols["fields"][f][s:e], np.float32
                    )
                )
    else:
        for t in spec.tags_code:
            paths.append(("tags_code", t))
            counted.add(("tags_code", t))
            thunks.append(
                lambda t=t: pad2(lambda s, e: cols["tags_code"][t][s:e], np.int32)
            )
        for f in spec.fields:
            paths.append(("fields", f))
            counted.add(("fields", f))
            thunks.append(
                lambda f=f: pad2(lambda s, e: cols["fields"][f][s:e], np.float32)
            )

    def timed(fn):
        def pad_thunk():  # host-side work on the prefetch worker
            if pack_use is not None:
                cpu0, flt0 = tracer.thread_usage()
            t0 = time.perf_counter()
            try:
                with tracer.annotate("decode.pack"):
                    return fn()
            finally:
                dt = time.perf_counter() - t0
                if pack_s is not None:
                    pack_s.append(dt)
                if pack_use is not None:
                    cpu, flt = tracer.thread_usage()
                    pack_use.append((dt - (cpu - cpu0), flt - flt0))

        return pad_thunk

    out: dict = {
        "tags_code": {},
        "tags_enc": {},
        "tags_lut": {},
        "fields": {},
        "fields_enc": {},
    }
    shipped = packed = 0
    for path, arr in zip(
        paths,
        prefetched([timed(fn) for fn in thunks], name="bydb-fused-pad"),
    ):
        t0 = time.perf_counter()
        dev = jnp.asarray(arr)
        if h2d_s is not None:
            h2d_s.append(time.perf_counter() - t0)
        packed += dev.nbytes
        if path in counted:
            shipped += dev.nbytes
        if len(path) == 1:
            out[path[0]] = dev
        else:
            out[path[0]][path[1]] = dev
    # canonical keys (tags_code/fields) stay present even when empty —
    # the pre-decode chunk structure the precompile warm args share;
    # the compressed-only keys appear only when used
    for key in ("tags_enc", "tags_lut", "fields_enc"):
        if not out[key]:
            del out[key]
    if ship_stats is not None:
        dense = (len(spec.tags_code) + len(spec.fields)) * C * nb * 4
        ship_stats.append((shipped, dense, packed))
    return out


def run_fused(
    chunks_np: dict,
    chunk_spans: list[tuple[int, int]],
    spec: PlanSpec,
    pred_vals: dict,
    hist_lo,
    hist_span,
    epoch: int,
    *,
    num_chunks: int,
    leg: DeviceLeg,
    gather_key=None,
    dev_cache=None,
    pack_s: list | None = None,
    h2d_s: list | None = None,
    ship_stats: list | None = None,
    decode_span=None,
    pack_use: list | None = None,
    hist=None,
    quantiles=None,
    fetch_hist: bool = False,
) -> tuple[list[dict], dict, object, str]:
    """Execute one chunk batch (``plan_batches``) through the fused
    program of its ``num_chunks`` bucket.

    -> (per-chunk host partials in scan order for the f64 absorb loop,
    the batch's whole-scan results on the host, a percentile plan's
    histogram on the device, input-cache outcome tag).  Exactly one
    kernel dispatch and one batched device_get regardless of chunk
    count; their host-clock times, the bytes the get brought back, what
    the dispatch compiled and how many dispatches of other queries were
    outstanding when it was issued add to ``leg`` (one leg per
    reduction, summed over its batches).  ``decode_span`` (open, or
    None) is finished when the stacked inputs are on the device: it
    covers the pad + ship loop and nothing of this dispatch.

    A percentile plan's histogram: ``hist`` is the device histogram the
    previous batch returned (None for the first; it is donated), and it
    stays on the device unless ``fetch_hist`` (the whole-scan results
    then hold it as ``hist``).  ``quantiles`` (a device f32 [Q], the
    last batch only): the program also inverts it, and the whole-scan
    results hold the int32 ``ranks`` [G, Q, 3] (hit bucket, count below
    it, count in it).
    """
    fspec = FusedSpec(
        plan=spec,
        num_chunks=num_chunks,
        quantiles=0 if quantiles is None else int(quantiles.shape[0]),
    )
    kernel = _KERNEL_CACHE.get(fspec)
    if kernel is None:
        kernel = _KERNEL_CACHE[fspec] = _build_kernel(fspec)
    # function-local import: precompile imports this module's builders
    from banyandb_tpu.query.precompile import default_registry

    default_registry().record("fused", fspec)

    built: list = []

    def _build():
        built.append(1)
        return _stacked_chunks(
            chunks_np, chunk_spans, spec, num_chunks, epoch, pack_s, h2d_s,
            ship_stats=ship_stats, pack_use=pack_use,
        )

    if dev_cache is not None:
        # stacked inputs depend only on (gathered data, the batch's row
        # span, bucket, columns): keep them device-resident so repeat
        # queries skip pad+ship too.  The key columns ride the key: a
        # Top-N's batch holds no ts / row, and a listing over the same
        # gather must not be served it
        ck = (
            "fused_chunks",
            gather_key,
            chunk_spans[0][0],
            chunk_spans[-1][1],
            num_chunks,
            spec.nrows,
            spec.tags_code,
            spec.fields,
            key_columns(spec),
        )
        dev_chunks = dev_cache.get_or_load(ck, _build)
    else:
        dev_chunks = _build()

    if decode_span is not None:
        decode_span.finish()

    global _OUTSTANDING
    with _OUTSTANDING_LOCK:
        # a reduction's own batches run one after another, so what is
        # outstanding here is other queries': get_s holds their programs
        leg.dispatches_ahead = max(leg.dispatches_ahead, _OUTSTANDING)
        _OUTSTANDING += 1
    try:
        with leg.paid:  # what this dispatch traces or compiles
            t0 = time.perf_counter()
            out = kernel(
                dev_chunks, pred_vals, hist_lo, hist_span, hist, quantiles
            )
            leg.dispatch_s += time.perf_counter() - t0
        hist = out.pop("hist", None)
        whole = {"ranks": out.pop("ranks")} if "ranks" in out else {}
        if fetch_hist and hist is not None:
            whole["hist"] = hist
        t0 = time.perf_counter()
        # bdlint: disable=host-sync -- THE result boundary of the fused
        # plan: the whole batch's stacked partials move in one batched
        # transfer (1 get per dispatch, ratcheted by kernel_budgets)
        moved, whole = jax.device_get((out, whole))
        leg.get_s += time.perf_counter() - t0
    finally:
        with _OUTSTANDING_LOCK:
            _OUTSTANDING -= 1
    leg.get_bytes += sum(
        a.nbytes for a in jax.tree_util.tree_leaves((moved, whole))
    )
    if "hist" in whole:
        leg.hist_bytes += whole["hist"].nbytes
    chunks_out = [
        jax.tree_util.tree_map(lambda a, k=k: a[k], moved)
        for k in range(len(chunk_spans))
    ]
    return chunks_out, whole, hist, ("built" if built else "hit")


# ---------------------------------------------------------------------------
# Mesh-parallel fused step: the whole distributed scan as ONE collective
# program (shard_map over ('shard','seg'), dist_exec's psum/pmin/pmax set)
# with a bounded compile-shape set (fixed-nrows chunks scanned per device).
# ---------------------------------------------------------------------------


def _fused_dist_step(
    plan, num_chunks: int, chunks: dict, pred_codes: dict, hist_lo, hist_span
):
    """One device's [1, C*nrows] slice -> chunked scan -> collectives.

    Per-chunk f32 partials combine across chunks with Kahan-compensated
    f32 (count/sums/hist) and exact min/max — the precision contract's
    bounded-span rule, on device.  With num_chunks=1 the math reduces to
    parallel/dist_exec._step exactly (Kahan from zero is the identity).
    """
    from banyandb_tpu import ops
    from banyandb_tpu.ops.groupby import _kahan_add
    from banyandb_tpu.parallel import dist_exec

    nhb = dist_exec._NUM_HIST_BUCKETS
    chunks = jax.tree.map(
        lambda a: a.reshape((num_chunks, -1)), chunks
    )
    G = plan.num_groups
    zero = jnp.zeros(G, jnp.float32)

    def step(carry, chunk):
        # the SAME map half the legacy mesh step runs (dist_exec.map_chunk)
        part, key, mask = dist_exec.map_chunk(plan, chunk, pred_codes)
        count, sums, mins, maxs, hist = carry
        count = _kahan_add(count[0], count[1], part.count)
        sums = {
            f: _kahan_add(sums[f][0], sums[f][1], part.sums[f])
            for f in plan.fields
        }
        mins = {
            f: jnp.minimum(mins[f], part.mins[f]) for f in plan.fields
        }
        maxs = {
            f: jnp.maximum(maxs[f], part.maxs[f]) for f in plan.fields
        }
        if plan.want_hist:
            h = ops.group_histogram(
                key,
                mask,
                chunk["fields"][plan.want_hist],
                G,
                hist_lo,
                hist_span,
                nhb,
            )
            hist = _kahan_add(hist[0], hist[1], h)
        return (count, sums, mins, maxs, hist), None

    axes = ("shard", "seg")
    # the carry becomes device-varying after one step (each device folds
    # its own slice), so the init must be typed varying over the manual
    # axes too or the scan's carry types do not match
    init = jax.lax.pcast(
        (
            (zero, zero),
            {f: (zero, zero) for f in plan.fields},
            {f: jnp.full(G, jnp.inf, jnp.float32) for f in plan.fields},
            {f: jnp.full(G, -jnp.inf, jnp.float32) for f in plan.fields},
            (
                (jnp.zeros((G, nhb), jnp.float32),) * 2
                if plan.want_hist
                else (zero, zero)
            ),
        ),
        axes,
        to="varying",
    )
    with jax.named_scope("bydb.fused_scan"):
        (count, sums, mins, maxs, hist), _ = jax.lax.scan(step, init, chunks)

    # ---- the collective reduce: ICI replaces the proto partial hop ----
    with jax.named_scope("bydb.collective"):
        out = {
            "count": jax.lax.psum(count[0] - count[1], axes),
            "sums": {
                f: jax.lax.psum(sums[f][0] - sums[f][1], axes)
                for f in plan.fields
            },
            "mins": {f: jax.lax.pmin(mins[f], axes) for f in plan.fields},
            "maxs": {f: jax.lax.pmax(maxs[f], axes) for f in plan.fields},
        }
        if plan.want_hist:
            out["hist"] = jax.lax.psum(hist[0] - hist[1], axes)
    if plan.topn:
        mean = out["sums"][plan.fields[0]] / jnp.maximum(out["count"], 1.0)
        vals, idx = ops.topk_groups(mean, out["count"] > 0, plan.topn)
        out["top_vals"], out["top_idx"] = vals, idx
    return out


_DIST_STEP_CACHE: dict[tuple, object] = {}


def build_fused_dist_step(mesh, plan, num_chunks: int):
    """-> jitted f(chunks, pred_codes, hist_lo, hist_span): the whole
    distributed scan as one collective program.  ``chunks`` arrays carry
    [D, num_chunks*nrows] sharded over ('shard','seg'); outputs are
    replicated.  Memoized per (mesh devices, plan, chunk bucket)."""
    from banyandb_tpu.parallel import dist_exec

    cache_key = (
        tuple(d.id for d in mesh.devices.flat),
        mesh.axis_names,
        plan,
        num_chunks,
    )
    cached = _DIST_STEP_CACHE.get(cache_key)
    if cached is not None:
        return cached

    from jax.sharding import PartitionSpec as P

    data_spec = P(("shard", "seg"))
    step = jax.shard_map(
        partial(_fused_dist_step, plan, num_chunks),
        mesh=mesh,
        in_specs=(
            {
                "valid": data_spec,
                "tags": {t: data_spec for t in plan.tags_code},
                "fields": {f: data_spec for f in plan.fields},
            },
            {t: P() for t in plan.eq_preds},
            P(),
            P(),
        ),
        out_specs=dist_exec._out_specs(plan),
    )

    # the name is the device trace's module line
    def bydb_fused_dist_step(chunks, pred_codes, hist_lo, hist_span):
        return step(chunks, pred_codes, hist_lo, hist_span)

    jitted = jax.jit(bydb_fused_dist_step)
    _DIST_STEP_CACHE[cache_key] = jitted
    return jitted


def fused_distributed_aggregate(
    mesh,
    plan,
    num_chunks: int,
    chunks: dict,
    pred_codes=None,
    hist_lo: float = 0.0,
    hist_span: float = 1.0,
):
    """Convenience wrapper mirroring dist_exec.distributed_aggregate."""
    step = build_fused_dist_step(mesh, plan, num_chunks)
    codes = {
        t: jnp.int32((pred_codes or {}).get(t, -1)) for t in plan.eq_preds
    }
    return step(chunks, codes, jnp.float32(hist_lo), jnp.float32(hist_span))
