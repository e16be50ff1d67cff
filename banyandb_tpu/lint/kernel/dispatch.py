"""kernel-dispatch: count dispatches and transfers per plan signature by
driving the REAL executor entry paths under an instrumented stub device.

Zero device kernel execution: the per-signature kernel builders
(``fused_exec._build_kernel`` / ``stream_exec._build_kernel``) are
swapped for stubs that count the dispatch, derive the output pytree with
``jax.eval_shape`` (a pure trace) and return host zeros; ``jax.device_get``
and ``jnp.asarray`` are wrapped with counting pass-throughs.  Everything
else — gather, dedup, plan-signature resolution, the batch loop, the
pad pipeline — is the production code path, so the measured counts
are the counts a real query pays:

- **dispatches**  jitted kernel invocations (the fused executor's
                  ROADMAP done-bar drives this to 1 per part-batch)
- **gets**        ``jax.device_get`` transfers (result boundaries)
- **puts**        ``jnp.asarray`` host->device array ships (pad/ship)

Each measure/stream scenario is synthesized so the executor resolves
EXACTLY the builtin precompile signature (dict sizes pin the radices,
row counts pin the scan bucket) — signature drift between what
production queries compile and what the registry warms/audits is itself
a finding.  The ql trace/property executors are host-only by design:
their budget is zero dispatches, zero transfers.

The per-scenario counts are ratcheted by kernel_budgets.BUDGETS.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Callable, Optional

import numpy as np

from banyandb_tpu.lint.core import Finding

RULE = "kernel-dispatch"

T0 = 1_700_000_000_000


class Counters:
    """Event sinks for the stub device (list appends are GIL-atomic: the
    prefetch worker ships chunks while the main thread dispatches).
    Counting can be suspended per-thread while the stub eval_shapes the
    real kernel (tracing must not count as transfer work)."""

    def __init__(self):
        self.dispatches: list[tuple[str, object]] = []  # (kind, spec)
        self.gets: list[int] = []
        self.puts: list[int] = []
        self._local = threading.local()

    def active(self) -> bool:
        return not getattr(self._local, "off", False)

    @contextlib.contextmanager
    def suspended(self):
        self._local.off = True
        try:
            yield
        finally:
            self._local.off = False


@dataclass(frozen=True)
class DispatchTrace:
    """Measured dispatch/transfer profile of one scenario."""

    name: str
    kind: str  # measure | stream | ql
    dispatches: int
    gets: int
    puts: int
    specs: tuple  # plan signatures the executor actually resolved
    builtin: object = None  # the precompile-registry signature expected
    path: str = ""
    line: int = 1
    error: str = ""


def _stub_builder(real_build: Callable, counters: Counters, kind: str):
    """A kernel builder whose kernels count dispatches and return host
    zeros shaped by eval_shape of the real kernel (no XLA compile)."""

    def build(spec):
        real = real_build(spec)
        state: dict = {}

        def stub(*args):
            import jax

            counters.dispatches.append((kind, spec))
            if "out" not in state:
                with counters.suspended():
                    state["out"] = jax.eval_shape(real, *args)
            return jax.tree_util.tree_map(
                lambda s: np.zeros(s.shape, s.dtype), state["out"]
            )

        return stub

    return build


@contextlib.contextmanager
def stub_device():
    """Patch the executors onto the stub device; yields the Counters.

    Scoped and restoring: kernel caches and the precompile registry are
    swapped for throwaways so the audit never pollutes process state,
    and the jax-level wrappers are counting pass-throughs (behavior
    preserved for any concurrent user).
    """
    import jax
    import jax.numpy as jnp

    from banyandb_tpu.query import fused_exec, precompile, stream_exec

    counters = Counters()
    real_get = jax.device_get
    real_asarray = jnp.asarray

    def counting_get(x):
        if counters.active():
            counters.gets.append(1)
        return real_get(x)

    def counting_asarray(a, *args, **kwargs):
        if counters.active():
            counters.puts.append(1)
        return real_asarray(a, *args, **kwargs)

    saved = (
        stream_exec._KERNEL_CACHE,
        stream_exec._build_kernel,
        fused_exec._KERNEL_CACHE,
        fused_exec._build_kernel,
        precompile.default_registry,
    )
    throwaway = precompile.PrecompileRegistry()
    try:
        stream_exec._KERNEL_CACHE = {}
        stream_exec._build_kernel = _stub_builder(
            saved[1], counters, "stream_mask"
        )
        fused_exec._KERNEL_CACHE = {}
        fused_exec._build_kernel = _stub_builder(
            saved[3], counters, "fused"
        )
        precompile.default_registry = lambda: throwaway
        jax.device_get = counting_get
        jnp.asarray = counting_asarray
        yield counters
    finally:
        jax.device_get = real_get
        jnp.asarray = real_asarray
        (
            stream_exec._KERNEL_CACHE,
            stream_exec._build_kernel,
            fused_exec._KERNEL_CACHE,
            fused_exec._build_kernel,
            precompile.default_registry,
        ) = saved


# -- scenario synthesis ------------------------------------------------------


def _int_bytes(i: int) -> bytes:
    return i.to_bytes(8, "little", signed=True)


def _source(n: int, step: int, tags: dict, fields: dict):
    """One synthetic ColumnData: distinct (series, ts) per row so version
    dedup keeps every row, dictionaries sized exactly to pin radices."""
    from banyandb_tpu.storage.part import ColumnData

    return ColumnData(
        ts=T0 + np.arange(n, dtype=np.int64) * step,
        series=np.arange(n, dtype=np.int64) % 64,
        version=np.ones(n, dtype=np.int64),
        tags={t: codes for t, (_vals, codes) in tags.items()},
        fields={f: a for f, a in fields.items()},
        dicts={t: vals for t, (vals, _codes) in tags.items()},
    )


def _measure_schema(tags, fields):
    from banyandb_tpu.api.schema import (
        Entity,
        FieldSpec,
        Measure,
        TagSpec,
    )

    return Measure(
        group="g",
        name="m",
        tags=tuple(TagSpec(n, t) for n, t in tags),
        fields=tuple(FieldSpec(n, t) for n, t in fields),
        entity=Entity((tags[0][0],)),
    )


def _measure_scenarios():
    """(name, builtin FusedSpec, runner) per builtin measure plan.  Each
    runner drives compute_partials so the resolved FusedSpec must equal
    the precompile registry's builtin signature."""
    from banyandb_tpu.api.model import (
        Aggregation,
        Condition,
        GroupBy,
        LogicalExpression,
        QueryRequest,
        TimeRange,
        Top,
    )
    from banyandb_tpu.api.schema import FieldType, TagType
    from banyandb_tpu.query import precompile
    from banyandb_tpu.query.measure_exec import compute_partials

    builtins = dict(precompile.builtin_fused())
    rng = np.random.default_rng(7)

    def svc_dict(k: int):
        vals = [b"s%04d" % i for i in range(k)]
        return vals

    def run_flat():
        n = 8192
        m = _measure_schema(
            [("svc", TagType.STRING)], [("v", FieldType.INT)]
        )
        src = _source(
            n,
            1,
            {"svc": (svc_dict(4), rng.integers(0, 4, n).astype(np.int32))},
            {"v": rng.integers(0, 100, n).astype(np.float64)},
        )
        req = QueryRequest(
            ("g",), "m", TimeRange(T0, T0 + n), field_projection=("v",)
        )
        compute_partials(m, req, [src])

    def run_grouped():
        n = 8192
        m = _measure_schema(
            [("svc", TagType.STRING), ("region", TagType.INT)],
            [("v", FieldType.INT)],
        )
        src = _source(
            n,
            1,
            {
                "svc": (svc_dict(8), rng.integers(0, 8, n).astype(np.int32)),
                "region": (
                    [_int_bytes(i) for i in range(4)],
                    rng.integers(0, 4, n).astype(np.int32),
                ),
            },
            {"v": rng.integers(0, 100, n).astype(np.float64)},
        )
        req = QueryRequest(
            ("g",),
            "m",
            TimeRange(T0, T0 + n),
            criteria=LogicalExpression(
                "and",
                Condition("svc", "eq", "s0003"),
                Condition("region", "le", 2),
            ),
            group_by=GroupBy(("svc", "region")),
            field_projection=("v",),
        )
        compute_partials(m, req, [src])

    def run_pct():
        n = 65536
        # ts span > 2^31 ms: long-range percentile dashboards run with
        # scan-order tracking off (int32 offsets would wrap), which is
        # exactly the builtin percentile-hist signature shape
        step = 32769
        m = _measure_schema(
            [("svc", TagType.STRING)], [("lat", FieldType.FLOAT)]
        )
        src = _source(
            n,
            step,
            {"svc": (svc_dict(16), rng.integers(0, 16, n).astype(np.int32))},
            {"lat": rng.random(n).astype(np.float64) * 100},
        )
        req = QueryRequest(
            ("g",),
            "m",
            TimeRange(T0, T0 + n * step + 1),
            group_by=GroupBy(("svc",)),
            agg=Aggregation("percentile", "lat", quantiles=(0.5,)),
        )
        compute_partials(m, req, [src])

    def run_or():
        n = 8192
        m = _measure_schema(
            [("svc", TagType.STRING)], [("v", FieldType.INT)]
        )
        src = _source(
            n,
            1,
            {"svc": (svc_dict(8), rng.integers(0, 8, n).astype(np.int32))},
            {"v": rng.integers(0, 100, n).astype(np.float64)},
        )
        req = QueryRequest(
            ("g",),
            "m",
            TimeRange(T0, T0 + n),
            criteria=LogicalExpression(
                "or",
                Condition(
                    "svc", "in", ("s0000", "s0001", "s0002", "s0003")
                ),
                Condition("svc", "eq", "s0000"),
            ),
            agg=Aggregation("sum", "v"),
        )
        compute_partials(m, req, [src])

    def run_topn():
        n = 65536
        m = _measure_schema(
            [("svc", TagType.STRING), ("region", TagType.STRING)],
            [("value", FieldType.INT)],
        )
        src = _source(
            n,
            1,
            {
                "svc": (
                    svc_dict(1024),
                    rng.integers(0, 1024, n).astype(np.int32),
                ),
                "region": (
                    [b"r%d" % i for i in range(8)],
                    rng.integers(0, 8, n).astype(np.int32),
                ),
            },
            {"value": rng.integers(0, 100, n).astype(np.float64)},
        )
        req = QueryRequest(
            ("g",),
            "m",
            TimeRange(T0, T0 + n),
            criteria=Condition("region", "ne", "r0"),
            group_by=GroupBy(("svc",)),
            top=Top(10, "value"),
        )
        compute_partials(m, req, [src])

    return [
        ("fused/flat-count", builtins["fused/flat-count"], run_flat),
        ("fused/group-eq-lut", builtins["fused/group-eq-lut"], run_grouped),
        ("fused/percentile-hist", builtins["fused/percentile-hist"], run_pct),
        ("fused/or-expr", builtins["fused/or-expr"], run_or),
        ("fused/topn-dashboard", builtins["fused/topn-dashboard"], run_topn),
    ]


def _multichunk_scenario():
    """fused/multi-chunk: a part-batch spanning SEVERAL scan chunks must
    still cost exactly ONE dispatch and ONE batched get on the fused
    path — the tripwire that fails CI the moment per-chunk staging
    creeps back into the fused executor."""
    from banyandb_tpu.api.model import QueryRequest, TimeRange
    from banyandb_tpu.api.schema import FieldType, TagType

    def run():
        from banyandb_tpu.query import measure_exec
        from banyandb_tpu.query.measure_exec import compute_partials

        n = 8192
        rng = np.random.default_rng(5)
        m = _measure_schema(
            [("svc", TagType.STRING)], [("v", FieldType.INT)]
        )
        src = _source(
            n,
            1,
            {
                "svc": (
                    [b"s%04d" % i for i in range(4)],
                    rng.integers(0, 4, n).astype(np.int32),
                )
            },
            {"v": rng.integers(0, 100, n).astype(np.float64)},
        )
        req = QueryRequest(
            ("g",), "m", TimeRange(T0, T0 + n), field_projection=("v",)
        )
        saved = measure_exec.SCAN_CHUNK
        measure_exec.SCAN_CHUNK = 4096  # n=8192 -> a 2-chunk part-batch
        try:
            compute_partials(m, req, [src])
        finally:
            measure_exec.SCAN_CHUNK = saved

    return run


def _stream_scenario():
    """The stream retrieval-mask scenario, its source at the stored i8
    code width models/stream reads (``narrow_codes``): the columns ship
    narrow and widen on device."""
    from banyandb_tpu.api.model import Condition
    from banyandb_tpu.query import precompile, stream_exec

    builtin = dict(precompile.builtin_masks())["stream/mask-eq-in"]

    def run():
        n = 32768
        rng = np.random.default_rng(9)
        src = _source(
            n,
            1,
            {
                "svc": (
                    [b"a", b"b"],
                    rng.integers(0, 2, n).astype(np.int8),
                ),
                "region": (
                    [b"r0", b"r1", b"r2", b"r3"],
                    rng.integers(0, 4, n).astype(np.int8),
                ),
            },
            {},
        )
        conds = [
            Condition("svc", "eq", "a"),
            Condition("region", "in", ("r0", "r1", "r2", "r3")),
        ]
        mask = stream_exec.device_tag_mask(src, conds)
        assert mask is not None and mask.shape == (n,)

    return ("stream/mask-eq-in", builtin, run)


def _ql_scenarios():
    from banyandb_tpu.api.model import Condition, QueryRequest, TimeRange
    from banyandb_tpu.query import ql_exec

    def run_trace():
        from banyandb_tpu.api.model import QueryResult

        def q(req, tracer=None):
            res = QueryResult()
            res.data_points = [
                {
                    "trace_id": "t-1",
                    "timestamp": T0,
                    "tags": {"svc": "a", "trace_id": "t-1"},
                    "span": b"",
                }
            ]
            return res

        eng = SimpleNamespace(query=q)
        req = QueryRequest(
            ("g",),
            "t",
            TimeRange(T0, T0 + 1000),
            criteria=Condition("trace_id", "eq", "t-1"),
        )
        ql_exec.execute_trace_ql(eng, req)

    def run_property():
        eng = SimpleNamespace(
            query=lambda g, n, tag_filters=None, ids=None, limit=100: [
                SimpleNamespace(id="p1", tags={"k": "v"}, mod_revision=1)
            ]
        )
        req = QueryRequest(
            ("g",),
            "p",
            TimeRange(T0, T0 + 1000),
            criteria=Condition("id", "eq", "p1"),
        )
        ql_exec.execute_property_ql(eng, req)

    return [("ql/trace", None, run_trace), ("ql/property", None, run_property)]


def _anchor(kind: str) -> tuple[str, int]:
    import inspect

    from banyandb_tpu.lint.whole_program.plan_audit import _rel_path
    from banyandb_tpu.query import measure_exec, ql_exec, stream_exec

    mod, fn = {
        "measure": (measure_exec, measure_exec.compute_partials),
        "stream_mask": (stream_exec, stream_exec.device_tag_mask),
        "ql": (ql_exec, ql_exec.execute_trace_ql),
    }[kind]
    return _rel_path(inspect.getsourcefile(mod)), inspect.getsourcelines(fn)[1]


def audit_dispatch() -> dict[str, DispatchTrace]:
    """Run every scenario under the stub device -> measured traces.

    Each measure scenario (the ``fused/*`` rows, pinned to the
    precompile registry's builtin FusedSpecs) must cost exactly one
    dispatch and one batched get with its batch shipped compressed —
    the decode stage fuses into the plan program or the whole point is
    lost — plus the multi-chunk staging tripwire."""
    scenarios = [
        (name, "measure", builtin, run)
        for name, builtin, run in _measure_scenarios()
    ]
    scenarios.append(
        ("fused/multi-chunk", "measure", None, _multichunk_scenario())
    )
    s_name, s_builtin, s_run = _stream_scenario()
    scenarios.append((s_name, "stream_mask", s_builtin, s_run))
    scenarios += [
        (name, "ql", builtin, run)
        for name, builtin, run in _ql_scenarios()
    ]

    out: dict[str, DispatchTrace] = {}
    for name, kind, builtin, run in scenarios:
        path, line = _anchor(kind)
        with stub_device() as counters:
            error = ""
            try:
                run()
            except Exception as e:  # noqa: BLE001 — the finding IS the report
                error = f"{type(e).__name__}: {e}"
        out[name] = DispatchTrace(
            name=name,
            kind=kind,
            dispatches=len(counters.dispatches),
            gets=len(counters.gets),
            puts=len(counters.puts),
            specs=tuple(spec for _k, spec in counters.dispatches),
            builtin=builtin,
            path=path,
            line=line,
            error=error,
        )
    return out


def _spec_diff(got, want) -> str:
    parts = []
    for f in dataclasses.fields(want):
        g, w = getattr(got, f.name), getattr(want, f.name)
        if g != w:
            parts.append(f"{f.name}: resolved {g!r} != builtin {w!r}")
    return "; ".join(parts) or f"resolved {got!r} != builtin {want!r}"


def dispatch_findings(traces: dict[str, DispatchTrace]) -> list[Finding]:
    """Scenario failures and signature drift (budget columns are checked
    by kernel_budgets.audit_budgets on the same traces)."""
    findings: list[Finding] = []
    for name in sorted(traces):
        t = traces[name]
        if t.error:
            findings.append(
                Finding(
                    path=t.path,
                    line=t.line,
                    col=0,
                    rule=RULE,
                    message=f"[{name}] scenario failed under the stub "
                    f"device: {t.error}",
                )
            )
            continue
        if t.builtin is None:
            continue
        resolved = tuple(dict.fromkeys(t.specs))
        if resolved != (t.builtin,):
            detail = (
                _spec_diff(resolved[0], t.builtin)
                if len(resolved) == 1
                and dataclasses.is_dataclass(resolved[0])
                else f"resolved {len(resolved)} distinct signatures"
            )
            findings.append(
                Finding(
                    path=t.path,
                    line=t.line,
                    col=0,
                    rule=RULE,
                    message=(
                        f"[{name}] plan signature drift: the executor did "
                        "not resolve the precompile-registry builtin "
                        f"signature ({detail}); the registry would warm a "
                        "kernel production queries never hit"
                    ),
                )
            )
    return findings


def measured_columns(t: DispatchTrace) -> dict[str, Optional[int]]:
    """The budget-table columns this analyzer measures."""
    return {"dispatches": t.dispatches, "gets": t.gets, "puts": t.puts}
