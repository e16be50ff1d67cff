"""eval_shape plan auditor: abstract-trace every registered kernel entry
point against a matrix of representative plan shapes.

``jax.eval_shape`` runs the full trace — shape/dtype inference, XLA-less
— so this audit catches, with **zero device execution**:

- **shape mismatch**: a plan whose kernel no longer traces (broadcast
  error, bad segment count, wrong pytree) fails here, not on the first
  production query with that plan shape;
- **dtype promotion**: the precision contract (f32 device partials, i32
  keys/timestamps, f64 only on the host merge) is pinned as an explicit
  expectation table per entry; any drift — an accidental f64 constant, a
  weak-type widening, an int64 key — is a finding;
- **avoidable retrace**: the jit cache key objects (PlanSpec/_MaskSpec)
  are audited for deep immutability, by-value equality, and stable
  hashing (an identity-hashing or array-carrying key defeats the kernel
  cache and recompiles per query), and the row-bucket functions are
  audited to produce a finite power-of-two shape set (raw-n shapes mean
  one compile per distinct row count).

The matrix mirrors the dashboard plan population: flat count, grouped
eq+LUT predicates with scan-order tracking, percentile histogram at a
scan-chunk bucket, and an OR expression tree — plus the stream mask
kernel and the shared ops reduction entries that every plan lowers onto.

tests/test_whole_program.py drives ``audit_kernel`` with a seeded
dtype-promoting kernel to prove the detection; ``run_plan_audit()`` is
the tree audit the CLI runs.
"""

from __future__ import annotations

import copy
import dataclasses
from dataclasses import dataclass, field
from typing import Callable, Optional

from banyandb_tpu.lint.core import Finding

RULE = "plan-audit"

_IMMUTABLE_SCALARS = (str, bytes, int, float, bool, type(None))


def _rel_path(path) -> str:
    """Repo-relative anchor path for a module's source file, matching
    the CLI-relative paths every other analyzer emits (stable SARIF
    URIs across machines).  Falls back to the absolute path when the
    package is installed outside a repo checkout."""
    from pathlib import Path

    import banyandb_tpu

    root = Path(banyandb_tpu.__file__).resolve().parent.parent
    p = Path(path).resolve()
    try:
        return str(p.relative_to(root))
    except ValueError:
        return str(p)


@dataclass
class KernelAudit:
    """One entry of the audit matrix."""

    name: str
    path: str  # finding anchor: the file that owns the kernel builder
    line: int
    fn: Callable  # the (jitted or plain) kernel to eval_shape
    args: tuple  # pytrees of jax.ShapeDtypeStruct / static scalars
    kwargs: dict = field(default_factory=dict)
    # flattened output key-path -> (dtype name, shape); the checked-in
    # precision/shape contract for this plan shape
    expect: Optional[dict[str, tuple[str, tuple]]] = None
    cache_key: object = None  # jit-cache key object to audit, if any


def _mutable_parts(obj, prefix: str = "") -> list[str]:
    """Paths inside a cache-key object that are not deeply immutable."""
    if isinstance(obj, _IMMUTABLE_SCALARS):
        return []
    if isinstance(obj, tuple):
        return [
            p
            for i, v in enumerate(obj)
            for p in _mutable_parts(v, f"{prefix}[{i}]")
        ]
    if isinstance(obj, frozenset):
        return [p for v in obj for p in _mutable_parts(v, prefix + "{}")]
    if dataclasses.is_dataclass(obj) and obj.__dataclass_params__.frozen:
        return [
            p
            for f in dataclasses.fields(obj)
            for p in _mutable_parts(
                getattr(obj, f.name), f"{prefix}.{f.name}".lstrip(".")
            )
        ]
    return [prefix or "<root>"]


def _flat_spec(tree) -> dict[str, tuple[str, tuple]]:
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = "".join(str(p) for p in path) or "<out>"
        out[key] = (str(leaf.dtype), tuple(leaf.shape))
    return out


def audit_kernel(entry: KernelAudit) -> list[Finding]:
    """Run one matrix entry -> findings (empty = the plan holds)."""
    import jax

    findings: list[Finding] = []

    def hit(message: str) -> None:
        findings.append(
            Finding(
                path=entry.path,
                line=entry.line,
                col=0,
                rule=RULE,
                message=f"[{entry.name}] {message}",
            )
        )

    if entry.cache_key is not None:
        for p in _mutable_parts(entry.cache_key):
            hit(
                f"jit cache key field `{p}` is not deeply immutable; "
                "an array/list/dict in the key defeats the kernel cache "
                "(retrace per query)"
            )
        try:
            clone = copy.deepcopy(entry.cache_key)
            if bool(clone != entry.cache_key) or hash(clone) != hash(
                entry.cache_key
            ):
                hit(
                    "jit cache key compares/hashes by identity, not "
                    "value: an equal plan rebuilt next query misses the "
                    "cache and recompiles"
                )
        except TypeError as e:
            hit(f"jit cache key is unhashable: {e}")
        except ValueError:
            # e.g. an ndarray in the key makes != ambiguous — already
            # reported above as a non-immutable field
            pass

    try:
        out = jax.eval_shape(entry.fn, *entry.args, **entry.kwargs)
    except Exception as e:  # noqa: BLE001 — the finding IS the report
        hit(
            f"abstract trace failed (shape mismatch / trace error): "
            f"{type(e).__name__}: {e}"
        )
        return findings

    got = _flat_spec(out)
    for key, (dtype, _shape) in sorted(got.items()):
        if dtype in ("float64", "int64", "uint64"):
            hit(
                f"output `{key}` is {dtype}: 64-bit dtypes in a device "
                "plan double HBM traffic and break the f32-partials/"
                "f64-host-merge precision contract"
            )
    if entry.expect is not None:
        for key in sorted(set(entry.expect) | set(got)):
            want, have = entry.expect.get(key), got.get(key)
            if want is None:
                hit(f"unexpected output `{key}` {have}; extend the contract "
                    "table if this is deliberate")
            elif have is None:
                hit(f"missing output `{key}` (contract says {want})")
            elif want != have:
                hit(
                    f"output `{key}` is dtype={have[0]} shape={have[1]}, "
                    f"contract says dtype={want[0]} shape={want[1]}"
                )
    return findings


def _bucket_findings() -> list[Finding]:
    """The retrace-bound audit: row-bucket functions must emit a finite
    power-of-two shape set."""
    import inspect

    from banyandb_tpu.query import measure_exec, stream_exec

    findings: list[Finding] = []
    for mod, fn_name, fn, hi in (
        (measure_exec, "_scan_bucket", measure_exec._scan_bucket, measure_exec.SCAN_CHUNK),
        (stream_exec, "_pad_bucket", stream_exec._pad_bucket, 1 << 24),
    ):
        path = _rel_path(inspect.getsourcefile(mod))
        line = inspect.getsourcelines(fn)[1]
        buckets = {fn(n) for n in (1, 2, 63, 64, 65, 1000, 8192, 100_000, hi)}
        bad = [b for b in buckets if b & (b - 1) or b > max(hi, 1)]
        if bad:
            findings.append(
                Finding(
                    path=path,
                    line=line,
                    col=0,
                    rule=RULE,
                    message=(
                        f"{fn_name} emitted non-power-of-two/unbounded row "
                        f"buckets {sorted(bad)}: every distinct bucket is "
                        "one XLA compile; the shape set must stay "
                        "O(log max_rows)"
                    ),
                )
            )
    return findings


def default_entries() -> list[KernelAudit]:
    """The checked-in plan matrix for the banyandb_tpu query layer.

    The measure/stream kernel signatures come from the precompile
    registry's builtin matrix (query/precompile.builtin_fused/_masks) —
    ONE list feeds both warming and auditing, and the agreement is
    pinned by a meta-test (tests/test_cold_path.py), so a signature the
    server precompiles is exactly a signature this audit contracts."""
    import inspect

    import jax
    import jax.numpy as jnp

    from banyandb_tpu import ops
    from banyandb_tpu.query import precompile, stream_exec
    from banyandb_tpu.query.measure_exec import PlanSpec

    S = jax.ShapeDtypeStruct
    f32, i32, b8 = jnp.float32, jnp.int32, jnp.bool_

    spath = _rel_path(inspect.getsourcefile(stream_exec))
    sline = inspect.getsourcelines(stream_exec._build_kernel)[1]

    def base_expect(spec: PlanSpec) -> dict[str, tuple[str, tuple]]:
        g = (spec.num_groups,)
        out = {"['count']": ("float32", g)}
        for f in spec.fields:
            out[f"['sums']['{f}']"] = ("float32", g)
            if spec.want_minmax:  # min/max arrays exist only when asked
                out[f"['mins']['{f}']"] = ("float32", g)
                out[f"['maxs']['{f}']"] = ("float32", g)
        if spec.want_rep:
            out["['rep_ts']"] = ("int32", g)
            out["['rep_row']"] = ("int32", g)
        return out

    entries: list[KernelAudit] = []

    for name, mspec in precompile.builtin_masks():
        entries.append(
            KernelAudit(
                name=name,
                path=str(spath),
                line=sline,
                fn=stream_exec._build_kernel(mspec),
                args=precompile.mask_structs(mspec),
                expect={"<out>": ("bool", (mspec.nrows,))},
                cache_key=mspec,
            )
        )

    # the measure plan programs: the per-chunk contract, stacked
    # [num_chunks, ...] outputs (one dispatch/one get per part-batch is
    # the kernel-dispatch half; here the shape/dtype contract is pinned)
    from banyandb_tpu.query import fused_exec

    fpath = _rel_path(inspect.getsourcefile(fused_exec))
    fline = inspect.getsourcelines(fused_exec._build_kernel)[1]
    def fused_expect(fspec) -> dict[str, tuple[str, tuple]]:
        """Per-chunk partials stacked [C, ...]; a percentile plan's
        histogram once, the flat int32 sum the scan carried."""
        out = {
            key: (dtype, (fspec.num_chunks,) + shape)
            for key, (dtype, shape) in base_expect(fspec.plan).items()
        }
        if fspec.plan.hist_field:
            out["['hist']"] = ("int32", (fspec.plan.num_groups * 512,))
        return out

    for name, fspec in precompile.builtin_fused():
        fexpect = fused_expect(fspec)
        entries.append(
            KernelAudit(
                name=name,
                path=str(fpath),
                line=fline,
                fn=fused_exec._build_kernel(fspec),
                args=(
                    precompile.fused_chunk_struct(fspec),
                    precompile.pred_struct(fspec.plan),
                    S((), f32),
                    S((), f32),
                ),
                expect=fexpect,
                cache_key=fspec,
            )
        )

    # the device-decode twins (ROADMAP item 3): SAME fused program, the
    # COMPRESSED chunk ship form (narrow codes + remap LUTs + narrow int
    # fields) — the in-program decode stage must keep the output
    # contract identical and introduce no 64-bit dtypes, and the
    # lowering audit pins the bytes-accessed class the compression buys
    for name, fspec in precompile.builtin_fused_decode():
        fexpect = fused_expect(fspec)
        entries.append(
            KernelAudit(
                name=name,
                path=str(fpath),
                line=fline,
                fn=fused_exec._build_kernel(fspec),
                args=(
                    precompile.fused_decode_chunk_struct(fspec),
                    precompile.pred_struct(fspec.plan),
                    S((), f32),
                    S((), f32),
                ),
                expect=fexpect,
                cache_key=fspec,
            )
        )

    # 6. the shared ops reductions every plan lowers onto, at a
    # representative grouped shape (method dispatch goes through "auto")
    opath = _rel_path(inspect.getsourcefile(ops.groupby))
    oline = inspect.getsourcelines(ops.group_reduce)[1]
    n, G = 8192, 128
    entries.append(
        KernelAudit(
            name="ops/group_reduce",
            path=str(opath),
            line=oline,
            fn=lambda key, valid, f: ops.group_reduce(key, valid, {"v": f}, G),
            args=(S((n,), i32), S((n,), b8), S((n,), f32)),
            expect={
                ".count": ("float32", (G,)),
                ".sums['v']": ("float32", (G,)),
                ".mins['v']": ("float32", (G,)),
                ".maxs['v']": ("float32", (G,)),
            },
        )
    )
    hpath = _rel_path(inspect.getsourcefile(ops.percentile))
    hline = inspect.getsourcelines(ops.group_histogram)[1]
    entries.append(
        KernelAudit(
            name="ops/group_histogram",
            path=str(hpath),
            line=hline,
            fn=lambda key, valid, vals, lo, span: ops.group_histogram(
                key, valid, vals, G, lo, span, 512
            ),
            args=(
                S((n,), i32),
                S((n,), b8),
                S((n,), f32),
                S((), f32),
                S((), f32),
            ),
            expect={"<out>": ("float32", (G, 512))},
        )
    )
    return entries


def run_plan_audit() -> list[Finding]:
    findings: list[Finding] = []
    for entry in default_entries():
        findings.extend(audit_kernel(entry))
    findings.extend(_bucket_findings())
    return findings
