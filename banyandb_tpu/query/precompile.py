"""Plan precompile registry: compile query kernels before queries arrive.

Three cooperating pieces close the cold-start compile gap:

1. **Recording**: the measure/stream executors call ``record()`` every
   time they resolve a plan signature (``PlanSpec`` / ``_MaskSpec``), so
   the registry always knows the live plan population of this process.
2. **Persistence**: when a server attaches a store file
   (``<root>/plan-registry.json``), newly seen signatures are saved (top
   ``MAX_STORED`` by use count) and reloaded on the next boot — the
   process remembers WHICH kernels matter across restarts, while
   ``utils/compile_cache`` remembers their compiled XLA executables.
3. **Warming**: ``warm_async()`` (server start = schema load, and once
   after the first flush via ``note_flush``) compiles the stored
   signatures plus the builtin dashboard matrix on a background daemon
   thread, by building each kernel into the executors' process-global
   jit caches and dispatching it once on zero-filled arguments of the
   exact production shapes/dtypes — so the first real query finds a
   warm jit cache instead of paying XLA compilation.

``builtin_fused()`` (the programs of ``builtin_plans()``'s signatures)
and ``builtin_masks()`` are the checked-in dashboard kernel matrix.  The
lint plan auditor (``lint/whole_program/plan_audit.py``)
eval_shape-audits EXACTLY this list — a meta-test pins the agreement, so a signature added here is
automatically contract-checked and a signature audited is automatically
precompiled.

``BYDB_PRECOMPILE=0`` disables recording and warming (tests that need a
deterministic kernel-cache population set this).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import threading
from pathlib import Path
from typing import Optional

log = logging.getLogger("banyandb.precompile")

MAX_STORED = 64


def enabled() -> bool:
    from banyandb_tpu.utils.envflag import env_flag

    return env_flag("BYDB_PRECOMPILE", default=True)


# -- the builtin dashboard matrix (single source for warm + plan audit) ------


def builtin_plans():
    """(name, PlanSpec) pairs: the dashboard plan population.

    Mirrors the shapes real consoles issue: flat count tiles, grouped
    eq+LUT filters with scan-order tracking, the two-pass percentile
    histogram, OR criteria trees, and the TopN ranking shape (grouped
    mean/minmax at a scan-chunk bucket; a Top-N that projects no tag
    tracks no scan order)."""
    from banyandb_tpu.query.measure_exec import PlanSpec, _PredSpec

    flat = PlanSpec(
        tags_code=(),
        fields=("v",),
        preds=(),
        group_tags=(),
        radices=(),
        num_groups=1,
        want_minmax=True,
        nrows=8192,
    )
    grouped = PlanSpec(
        tags_code=("region", "svc"),
        fields=("v",),
        preds=(
            _PredSpec("code", "svc", "eq"),
            _PredSpec("lut", "region", "le", nvals=4),
        ),
        group_tags=("svc", "region"),
        radices=(8, 4),
        num_groups=32,
        want_minmax=True,
        nrows=8192,
        want_rep=True,
    )
    pct = PlanSpec(
        tags_code=("svc",),
        fields=("lat",),
        preds=(),
        group_tags=("svc",),
        radices=(16,),
        num_groups=16,
        want_minmax=True,
        hist_field="lat",
        nrows=65536,
    )
    orplan = PlanSpec(
        tags_code=("svc",),
        fields=("v",),
        preds=(
            _PredSpec("code", "svc", "in", nvals=4),
            _PredSpec("code", "svc", "eq"),
        ),
        group_tags=(),
        radices=(),
        num_groups=1,
        want_minmax=False,
        nrows=8192,
        expr=("or", ("p", 0), ("p", 1)),
    )
    topn = PlanSpec(
        tags_code=("region", "svc"),
        fields=("value",),
        preds=(_PredSpec("code", "region", "ne"),),
        group_tags=("svc",),
        radices=(1024,),
        num_groups=1024,
        want_minmax=True,
        nrows=65536,
    )
    return (
        ("measure/flat-count", flat),
        ("measure/group-eq-lut", grouped),
        ("measure/percentile-hist", pct),
        ("measure/or-expr", orplan),
        ("measure/topn-dashboard", topn),
    )


def builtin_masks():
    """(name, _MaskSpec) pairs for the stream retrieval mask kernel."""
    from banyandb_tpu.query.stream_exec import _MaskSpec

    return (
        ("stream/mask-eq-in", _MaskSpec(preds=(("eq", 1), ("in", 4)), nrows=32768)),
    )


def builtin_fused():
    """(name, FusedSpec) pairs: the programs of the builtin measure
    matrix (query/fused_exec).  One-chunk buckets — the shape a
    dashboard part-batch resolves — warmed, plan-audited and budget-
    ratcheted."""
    from banyandb_tpu.query.fused_exec import FusedSpec

    return tuple(
        (name.replace("measure/", "fused/"), FusedSpec(plan=spec, num_chunks=1))
        for name, spec in builtin_plans()
    )


# -- shape/dtype argument builders (shared with the lint plan auditor) -------


def pred_struct(spec) -> dict:
    """ShapeDtypeStruct map matching compute_partials' pred_vals."""
    import jax
    import jax.numpy as jnp

    S = jax.ShapeDtypeStruct
    out = {}
    for i, p in enumerate(spec.preds):
        if p.kind == "lut":
            out[f"p{i}"] = S((p.nvals,), jnp.bool_)
        elif p.op in ("in", "not_in"):
            out[f"p{i}"] = S((p.nvals,), jnp.int32)
        else:
            out[f"p{i}"] = S((), jnp.int32)
    return out


def mask_structs(mspec) -> tuple:
    """(cols, pred_vals) ShapeDtypeStructs matching device_tag_mask."""
    import jax
    import jax.numpy as jnp

    S = jax.ShapeDtypeStruct
    cols = tuple(S((mspec.nrows,), jnp.int32) for _ in mspec.preds)
    vals = tuple(
        S((nv,), jnp.int32) if op in ("in", "not_in") else S((), jnp.int32)
        for op, nv in mspec.preds
    )
    return cols, vals


def _zeros_like_structs(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), tree
    )


def mask_warm_args(mspec) -> tuple:
    cols, vals = mask_structs(mspec)
    return (_zeros_like_structs(cols), _zeros_like_structs(vals))


def _key_column_structs(spec, shape) -> dict:
    """The per-row key columns the plan's program reads
    (fused_exec.key_columns, the set _stacked_chunks pads and ships)."""
    import jax
    import jax.numpy as jnp

    from banyandb_tpu.query.fused_exec import key_columns

    dtypes = {"ts": jnp.int32, "valid": jnp.bool_, "row": jnp.int32}
    return {
        k: jax.ShapeDtypeStruct(shape, dtypes[k]) for k in key_columns(spec)
    }


def _decode_lut_len(spec, t: str) -> int:
    for tag, radix in zip(spec.group_tags, spec.radices):
        if tag == t:
            return 1 << max(int(radix) - 1, 1).bit_length()
    return 16


def _decode_code_dtype(spec, t: str):
    """Canonical narrow code width per tag: from the group radix where
    the signature pins one, i8 otherwise (the dashboard population's
    dictionaries are small).  Production widths are data-dependent — a
    mismatch just means one extra trace on first contact, the same cost
    class as an unseen row bucket."""
    import jax.numpy as jnp

    import numpy as _np

    from banyandb_tpu.storage import encoded as enc_mod

    for tag, radix in zip(spec.group_tags, spec.radices):
        if tag == t:
            return jnp.dtype(enc_mod.code_dtype(int(radix)))
    return jnp.dtype(_np.int8)


def fused_chunk_struct(fspec, float_fields: bool = False) -> dict:
    """ShapeDtypeStruct pytree of a fused part-batch
    (fused_exec._stacked_chunks) at a canonical single-source shape:

    - tag columns as narrow local codes (width from the group radix, i8
      otherwise) plus a ``[1, L]`` remap LUT with L
      the power-of-two bucket of the tag's radix (group tags) or 16;
    - one i16 source-ordinal column;
    - fields as i16 exact-int columns, except the histogram field
      (percentile inputs are real-valued) and, with ``float_fields``,
      every field (values non-integral or wider than i16): f32.

    Production widths vary with the data (i8 dictionaries, multi-source
    LUT stacks — jit re-specializes per pytree); this canonical shape is
    what the ``fused/*`` budget rows lower and jaxpr-audit, the same
    way nrows is a representative row bucket."""
    import jax
    import jax.numpy as jnp

    S = jax.ShapeDtypeStruct
    spec = fspec.plan
    c, n = fspec.num_chunks, spec.nrows
    lut_len = lambda t: _decode_lut_len(spec, t)  # noqa: E731
    floats = [f for f in spec.fields if float_fields or f == spec.hist_field]

    out = {
        **_key_column_structs(spec, (c, n)),
        "tags_code": {},
        "fields": {f: S((c, n), jnp.float32) for f in floats},
    }
    if spec.tags_code:
        out["tags_enc"] = {
            t: S((c, n), _decode_code_dtype(spec, t))
            for t in spec.tags_code
        }
        out["tags_lut"] = {
            t: S((1, lut_len(t)), jnp.int32) for t in spec.tags_code
        }
        out["src_ord"] = S((c, n), jnp.int16)
    enc = {f: S((c, n), jnp.int16) for f in spec.fields if f not in floats}
    if enc:
        out["fields_enc"] = enc
    return out


def _whole_scan_warm_args(fspec) -> tuple:
    """The histogram and quantile args of a program that inverts: the
    first batch's (no histogram yet) and Q quantiles."""
    import jax.numpy as jnp

    if not fspec.quantiles:
        return ()
    return (None, jnp.zeros(fspec.quantiles, jnp.float32))


def fused_warm_args(fspec, float_fields: bool = False) -> tuple:
    """Zero-filled args of one fused plan program at the canonical
    widths (``fused_chunk_struct``)."""
    import jax.numpy as jnp

    return (
        _zeros_like_structs(fused_chunk_struct(fspec, float_fields)),
        _zeros_like_structs(pred_struct(fspec.plan)),
        jnp.float32(0.0),
        jnp.float32(1.0),
        *_whole_scan_warm_args(fspec),
    )


# -- signature (de)serialization ---------------------------------------------


def spec_to_json(kind: str, spec) -> dict:
    d = dataclasses.asdict(spec)
    d["kind"] = kind
    return d


def _tuplify(node):
    """JSON lists -> tuples, recursively (expr trees, pred tuples)."""
    if isinstance(node, list):
        return tuple(_tuplify(v) for v in node)
    return node


def spec_from_json(d: dict):
    kind = d["kind"]
    if kind == "fused":
        from banyandb_tpu.query.fused_exec import FusedSpec

        _, plan = spec_from_json({**d["plan"], "kind": "measure"})
        return kind, FusedSpec(
            plan=plan,
            num_chunks=int(d["num_chunks"]),
            quantiles=int(d.get("quantiles", 0)),
        )
    if kind == "measure":
        from banyandb_tpu.query.measure_exec import PlanSpec, _PredSpec

        return kind, PlanSpec(
            tags_code=tuple(d["tags_code"]),
            fields=tuple(d["fields"]),
            preds=tuple(_PredSpec(**p) for p in d["preds"]),
            group_tags=tuple(d["group_tags"]),
            radices=tuple(d["radices"]),
            num_groups=int(d["num_groups"]),
            want_minmax=bool(d["want_minmax"]),
            hist_field=d.get("hist_field", ""),
            nrows=int(d["nrows"]),
            group_method=d.get("group_method", "auto"),
            want_rep=bool(d.get("want_rep", False)),
            rep_desc=bool(d.get("rep_desc", False)),
            expr=_tuplify(d.get("expr", [])),
        )
    if kind == "stream_mask":
        from banyandb_tpu.query.stream_exec import _MaskSpec

        return kind, _MaskSpec(
            preds=_tuplify(d["preds"]), nrows=int(d["nrows"])
        )
    raise ValueError(f"unknown plan signature kind {kind!r}")


# -- the registry ------------------------------------------------------------


class PrecompileRegistry:
    """Thread-safe record of live plan signatures + background warming."""

    def __init__(self):
        self._lock = threading.Lock()
        # (kind, spec) -> use count; insertion order = first-seen order
        self._recorded: dict[tuple, int] = {}
        # (kind, spec) -> epoch-ms of the latest record() — persisted so
        # warming (and the autoreg miner) can rank by freshness too
        self._last_hit: dict[tuple, int] = {}
        # (kind, spec) -> (group, measure) the executor resolved the
        # plan for: the context that turns an anonymous PlanSpec into a
        # registrable streamagg signature (query/planner mining)
        self._contexts: dict[tuple, tuple] = {}
        self._store_path: Optional[Path] = None
        self._warm_thread: Optional[threading.Thread] = None
        self._warm_pending = False
        self._cancel = threading.Event()
        self._save_timer: Optional[threading.Timer] = None
        self._flush_warmed = False
        self.compiled = 0
        self.errors = 0

    # -- recording / persistence --------------------------------------------
    def record(self, kind: str, spec, context: Optional[tuple] = None) -> None:
        """Called by executors on every plan resolution.  Never blocks
        the query hot path: a first-seen signature schedules a debounced
        background save instead of rewriting the store inline.

        ``context`` ((group, measure), measure plans only) attaches the
        schema identity the plan resolved against — the evidence the
        auto-registration miner needs to turn a hot PlanSpec into a
        streamagg registration."""
        if not enabled():
            return
        new = False
        with self._lock:
            key = (kind, spec)
            n = self._recorded.get(key)
            self._recorded[key] = (n or 0) + 1
            import time as _time

            self._last_hit[key] = int(_time.time() * 1000)
            if context is not None:
                self._contexts[key] = tuple(context)
            new = n is None and self._store_path is not None
        if new:
            self._schedule_save()

    def evidence(self) -> list[tuple]:
        """[(kind, spec, count, context-or-None)] for the autoreg
        miner, hottest first."""
        with self._lock:
            return [
                (k, s, count, self._contexts.get((k, s)))
                for (k, s), count in sorted(
                    self._recorded.items(),
                    key=lambda kv: (-kv[1], -self._last_hit.get(kv[0], 0)),
                )
            ]

    def _schedule_save(self, delay: float = 1.0) -> None:
        with self._lock:
            if self._save_timer is not None:
                return  # a pending save will pick this signature up too
            t = threading.Timer(delay, self._save_timer_fire)
            t.daemon = True
            t.name = "bydb-plan-save"
            self._save_timer = t
        t.start()

    def _save_timer_fire(self) -> None:
        with self._lock:
            self._save_timer = None
        self._save()

    def attach_store(self, path) -> None:
        """Bind (and load) the persistent signature store."""
        p = Path(path)
        loaded: list[tuple[tuple, int, int, Optional[tuple]]] = []
        try:
            if p.exists():
                for rec in json.loads(p.read_text()).get("signatures", []):
                    try:
                        kind, spec = spec_from_json(rec)
                        ctx = rec.get("context")
                        loaded.append((
                            (kind, spec),
                            int(rec.get("count", 1)),
                            int(rec.get("last_hit_ms", 0)),
                            tuple(ctx) if ctx else None,
                        ))
                    except Exception:  # noqa: BLE001 — skip stale entries
                        continue
        except (OSError, ValueError):
            loaded = []
        with self._lock:
            self._store_path = p
            for key, count, last_ms, ctx in loaded:
                self._recorded[key] = max(self._recorded.get(key, 0), count)
                if last_ms:
                    self._last_hit[key] = max(
                        self._last_hit.get(key, 0), last_ms
                    )
                if ctx is not None and key not in self._contexts:
                    self._contexts[key] = ctx
            have_unsaved = len(self._recorded) > len(loaded)
        if have_unsaved:
            # signatures recorded before the store was bound (embedded
            # engines, bench) persist now, not on the next new plan
            self._save()

    def _save(self) -> None:
        with self._lock:
            p = self._store_path
            if p is None:
                return
            # frequency-weighted persistence, recency as the tiebreak:
            # the top-MAX_STORED ACTUALLY-HOT signatures survive a
            # restart (and warm first), not the most recently seen ones
            top = sorted(
                self._recorded.items(),
                key=lambda kv: (-kv[1], -self._last_hit.get(kv[0], 0)),
            )[:MAX_STORED]
            doc = {
                "signatures": [
                    {
                        **spec_to_json(kind, spec),
                        "count": count,
                        "last_hit_ms": self._last_hit.get((kind, spec), 0),
                        **(
                            {"context": list(self._contexts[(kind, spec)])}
                            if (kind, spec) in self._contexts
                            else {}
                        ),
                    }
                    for (kind, spec), count in top
                ]
            }
        try:
            p.parent.mkdir(parents=True, exist_ok=True)
            tmp = p.with_suffix(".tmp")
            tmp.write_text(json.dumps(doc, indent=1))
            os.replace(tmp, p)
        except OSError:
            pass  # persistence is an optimization, never a query failure

    def signatures(self) -> list[tuple[str, object]]:
        """Hottest first (count, then recency): warm_async compiles the
        actually-hot population before the long tail."""
        with self._lock:
            return [
                (k, s)
                for (k, s), _ in sorted(
                    self._recorded.items(),
                    key=lambda kv: (-kv[1], -self._last_hit.get(kv[0], 0)),
                )
            ]

    # -- warming -------------------------------------------------------------
    def _compile_one(self, kind: str, spec) -> None:
        import jax

        from banyandb_tpu.query import fused_exec, stream_exec

        # fused kernels trace per chunk-pytree STRUCTURE: warm the one a
        # batch of canonical-width data resolves and, for a plan with no
        # tag column, the batch whose fields ship as f32 (nothing of it
        # is encoded then)
        if kind == "fused":
            cache, build = (
                fused_exec._KERNEL_CACHE,
                fused_exec._build_kernel,
            )
            args_list = [fused_warm_args(spec)]
            if not spec.plan.tags_code:
                args_list.append(fused_warm_args(spec, float_fields=True))
        elif kind == "stream_mask":
            cache, build = (
                stream_exec._KERNEL_CACHE,
                stream_exec._build_kernel,
            )
            args_list = [mask_warm_args(spec)]
        else:
            return
        kernel = cache.get(spec)
        if kernel is None:
            kernel = cache[spec] = build(spec)
        # one dispatch per structure on zero args of the production
        # shapes: populates the jit executable cache AND (through
        # utils/compile_cache) the persistent XLA cache; values are
        # irrelevant to the cache key
        for args in args_list:
            # bdlint: disable=host-sync -- warming runs on a background
            # thread and MUST block until the compile finishes; there is
            # no result to batch
            jax.block_until_ready(kernel(*args))

    def warm(self, include_builtin: bool = True, sigs=None) -> int:
        """Compile signatures now (callers wanting async use warm_async).

        Only what the server dispatches is compiled: ``fused`` and
        ``stream_mask`` signatures.  A ``measure`` row is the autoreg's
        evidence (query/planner); it warms as the one-chunk program of
        its plan only where no ``fused`` row of that plan is in the list
        (a store written before every resolution recorded one)."""
        from banyandb_tpu.query.fused_exec import FusedSpec

        if sigs is None:
            sigs = list(self.signatures())
            if include_builtin:
                sigs += [("fused", s) for _, s in builtin_fused()]
                sigs += [("stream_mask", s) for _, s in builtin_masks()]
        fused_plans = {s.plan for kind, s in sigs if kind == "fused"}
        done = 0
        seen = set()
        for kind, spec in sigs:
            if self._cancel.is_set():
                break  # shutdown: stop at a kernel boundary, never mid-compile
            if kind == "measure":
                if spec in fused_plans:
                    continue
                kind, spec = "fused", FusedSpec(plan=spec, num_chunks=1)
            if (kind, spec) in seen:
                continue
            seen.add((kind, spec))
            try:
                self._compile_one(kind, spec)
                done += 1
            except Exception:  # noqa: BLE001 — warm must never take a
                # server down, but a kernel the compiler refuses must not
                # leave one that merely LOOKS warm: count it (the
                # precompile_errors gauge) and log the signature
                self.errors += 1
                log.exception(
                    "precompile failed for %s signature %r", kind, spec
                )
        self.compiled += done
        return done

    def _warm_loop(self, include_builtin: bool) -> None:
        """Warm rounds until no more work was queued while running —
        a note_flush/warm_async arriving mid-round (e.g. plans recorded
        while the boot warm is still compiling) queues another round
        instead of being silently dropped."""
        while True:
            self.warm(include_builtin=include_builtin)
            with self._lock:
                if not self._warm_pending or self._cancel.is_set():
                    return
                self._warm_pending = False
            include_builtin = False  # follow-up rounds: recorded sigs only

    def warm_async(self, include_builtin: bool = True) -> Optional[threading.Thread]:
        """Background warm (server start / post-flush).  If a warm is
        already running, queues one more round for when it finishes."""
        if not enabled():
            return None
        with self._lock:
            if self._warm_thread is not None and self._warm_thread.is_alive():
                self._warm_pending = True
                return self._warm_thread
            t = threading.Thread(
                target=self._warm_loop,
                args=(include_builtin,),
                name="bydb-precompile",
                daemon=True,
            )
            self._warm_thread = t
        t.start()
        return t

    def note_flush(self) -> None:
        """First-flush hook: parts now exist on disk, the next query is
        the cold one — warm the recorded population once."""
        if not enabled():
            return
        with self._lock:
            if self._flush_warmed or not self._recorded:
                return
            self._flush_warmed = True
        self.warm_async(include_builtin=False)

    def wait_warm(self, timeout: float = 120.0) -> bool:
        """Block until the in-flight warm finishes (bench/tests)."""
        with self._lock:
            t = self._warm_thread
        if t is None:
            return True
        t.join(timeout)
        return not t.is_alive()

    def shutdown(self, timeout: float = 30.0) -> None:
        """Server-stop hook: cancel warming at the next kernel boundary
        and join, so process exit never lands mid-XLA-compile (a daemon
        thread killed inside C++ aborts the interpreter); flushes any
        pending store save."""
        with self._lock:
            self._warm_pending = False
            self._cancel.set()
            t = self._warm_thread
            timer = self._save_timer
            self._save_timer = None
        if timer is not None:
            timer.cancel()
        if t is not None:
            t.join(timeout)
            if t.is_alive():
                return  # leave cancel set; the thread exits at its next check
        self._cancel.clear()
        self._save()

    def stats(self) -> dict:
        with self._lock:
            return {
                "enabled": enabled(),
                "recorded": len(self._recorded),
                "stored": str(self._store_path) if self._store_path else None,
                "compiled": self.compiled,
                "errors": self.errors,
                "warming": bool(
                    self._warm_thread and self._warm_thread.is_alive()
                ),
            }


_registry = PrecompileRegistry()


def default_registry() -> PrecompileRegistry:
    return _registry
