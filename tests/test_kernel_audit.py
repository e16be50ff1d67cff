"""bdjit kernel audit: seeded-violation proofs for every analyzer
(planted host callback, planted f64 promotion, planted narrowing,
planted extra dispatch, loosened budget entry), the budget-table pins,
and the obs cross-check (static dispatch budget bounds the observed
device_execute span count).

Mirrors tests/test_whole_program.py's contract: detection is proven on
seeded inputs, then meta-tests pin the real tree to zero findings and
the checked-in budget table to its reviewed shape.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from banyandb_tpu.lint.core import apply_ratchet, ratchet_value
from banyandb_tpu.lint.kernel import (
    KERNEL_RULES,
    kernel_entries,
    run_kernel_audit,
)
from banyandb_tpu.lint.kernel import dispatch as kdispatch
from banyandb_tpu.lint.kernel import jaxpr_audit, kernel_budgets
from banyandb_tpu.lint.whole_program.plan_audit import KernelAudit


def _entry(fn, args=None, name="seeded"):
    import jax
    import jax.numpy as jnp

    if args is None:
        args = (jax.ShapeDtypeStruct((64,), jnp.float32),)
    return KernelAudit(
        name=name, path="query/x.py", line=1, fn=fn, args=args, expect=None
    )


# -- kernel-jaxpr ------------------------------------------------------------


def test_jaxpr_host_callback_flagged():
    import jax
    import jax.numpy as jnp

    def k(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v),
            jax.ShapeDtypeStruct((64,), jnp.float32),
            x,
        )
        return y + 1.0

    fs, _ = jaxpr_audit.audit_entry(_entry(k))
    assert any(
        f.rule == "kernel-jaxpr" and "host callback" in f.message
        and "pure_callback" in f.message
        for f in fs
    ), [f.message for f in fs]


def test_jaxpr_debug_print_flagged():
    import jax

    def k(x):
        jax.debug.print("x={x}", x=x)
        return x + 1.0

    fs, _ = jaxpr_audit.audit_entry(_entry(k))
    assert any("host callback" in f.message for f in fs)


def test_jaxpr_f64_promotion_flagged():
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(True):
        fs, widest = jaxpr_audit.audit_entry(
            _entry(lambda x: x.astype(jnp.float64) * 2.0)
        )
    assert widest == 8
    assert any(
        "64-bit dtype `float64`" in f.message and "jaxpr eqn" in f.message
        for f in fs
    ), [f.message for f in fs]


def test_jaxpr_narrowing_accumulator_flagged():
    import jax.numpy as jnp

    fs, _ = jaxpr_audit.audit_entry(
        _entry(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32))
    )
    assert any(
        "accumulator narrowed" in f.message and "bfloat16" in f.message
        for f in fs
    ), [f.message for f in fs]


def test_jaxpr_nondonated_alias_flagged_and_donated_clean():
    import jax
    import jax.numpy as jnp

    args = (jax.ShapeDtypeStruct((1 << 15,), jnp.float32),)  # 128 KiB
    fs, _ = jaxpr_audit.audit_entry(_entry(jax.jit(lambda x: x + 1.0), args))
    assert any("donate_argnums" in f.message for f in fs), [
        f.message for f in fs
    ]
    fs, _ = jaxpr_audit.audit_entry(
        _entry(jax.jit(lambda x: x + 1.0, donate_argnums=0), args)
    )
    assert fs == [], [f.message for f in fs]


def test_jaxpr_clean_kernel():
    fs, widest = jaxpr_audit.audit_entry(_entry(lambda x: (x * 2.0).sum()))
    assert fs == [] and widest == 4


def test_jaxpr_real_matrix_clean():
    for entry in kernel_entries():
        fs, widest = jaxpr_audit.audit_entry(entry)
        assert fs == [], "\n".join(f.render() for f in fs)
        assert widest == 4, (entry.name, widest)


def test_stored_signatures_audited():
    """Recorded (non-builtin) signatures get the jaxpr audit too: the
    live plan population a server warms is held to the same invariants,
    without needing checked-in budget rows."""
    from banyandb_tpu.lint.kernel import stored_entries
    from banyandb_tpu.query import precompile

    reg = precompile.PrecompileRegistry()
    reg._recorded[("measure", precompile.builtin_plans()[0][1])] = 3
    reg._recorded[("stream_mask", precompile.builtin_masks()[0][1])] = 1
    entries = stored_entries(registry=reg)
    assert len(entries) == 2
    for e in entries:
        assert e.name.startswith("stored/")
        fs, widest = jaxpr_audit.audit_entry(e)
        assert fs == [] and widest == 4


def test_stored_entries_empty_registry():
    from banyandb_tpu.lint.kernel import stored_entries
    from banyandb_tpu.query import precompile

    assert stored_entries(registry=precompile.PrecompileRegistry()) == []


# -- kernel-dispatch ---------------------------------------------------------


def test_dispatch_real_scenarios_match_builtins_and_budgets():
    """The measured plane: every scenario runs clean, resolves exactly
    its builtin precompile signature, and matches its budget row."""
    traces = kdispatch.audit_dispatch()
    assert kdispatch.dispatch_findings(traces) == []
    for name, t in traces.items():
        assert not t.error, (name, t.error)
        row = kernel_budgets.BUDGETS[name]
        assert t.dispatches == row.dispatches, name
        assert t.gets == row.gets, name
        assert t.puts == row.puts, name
        if t.builtin is not None:
            assert tuple(dict.fromkeys(t.specs)) == (t.builtin,), name


def test_dispatch_stub_device_restores_patches():
    import jax
    import jax.numpy as jnp

    from banyandb_tpu.query import fused_exec, stream_exec

    before = (
        jax.device_get,
        jnp.asarray,
        fused_exec._build_kernel,
        stream_exec._build_kernel,
    )
    with kdispatch.stub_device():
        assert jax.device_get is not before[0]
        assert jnp.asarray is not before[1]
    after = (
        jax.device_get,
        jnp.asarray,
        fused_exec._build_kernel,
        stream_exec._build_kernel,
    )
    assert before == after


def test_dispatch_planted_extra_dispatch_fails_budget():
    """The seeded regression: one extra jitted dispatch on a signature
    whose budget says 1 must fail the kernel-budget gate."""
    traces = kdispatch.audit_dispatch()
    t = traces["fused/flat-count"]
    planted = dataclasses.replace(t, dispatches=t.dispatches + 1)
    fs = kernel_budgets.audit_budgets(
        traces={"fused/flat-count": planted},
        budgets={
            "fused/flat-count": kernel_budgets.BUDGETS["fused/flat-count"]
        },
    )
    assert any(
        f.rule == "kernel-budget"
        and "dispatches regression" in f.message
        and "measured 2" in f.message
        for f in fs
    ), [f.message for f in fs]


def test_dispatch_signature_drift_flagged():
    traces = kdispatch.audit_dispatch()
    t = traces["fused/flat-count"]
    drifted = dataclasses.replace(
        t,
        builtin=dataclasses.replace(
            t.builtin, plan=dataclasses.replace(t.builtin.plan, num_groups=2)
        ),
    )
    fs = kdispatch.dispatch_findings({"fused/flat-count": drifted})
    assert len(fs) == 1 and "plan signature drift" in fs[0].message
    assert "num_groups" in fs[0].message


def test_dispatch_ql_paths_are_device_free():
    traces = kdispatch.audit_dispatch()
    for name in ("ql/trace", "ql/property"):
        t = traces[name]
        assert (t.dispatches, t.gets, t.puts) == (0, 0, 0), name


# -- kernel-budget / shared ratchet mechanics --------------------------------


def test_ratchet_value_semantics():
    kw = dict(rule="kernel-budget", path="a.py", line=3, budget_path="b.py")
    assert ratchet_value("sig", "dispatches", 1, 1, **kw) == []
    up = ratchet_value("sig", "dispatches", 3, 1, **kw)
    assert len(up) == 1 and "regression" in up[0].message
    assert up[0].path == "a.py" and up[0].line == 3
    down = ratchet_value("sig", "dispatches", 1, 3, **kw)
    assert len(down) == 1 and "stale budget entry" in down[0].message
    assert "tighten" in down[0].message and down[0].path == "b.py"


def test_apply_ratchet_semantics():
    from banyandb_tpu.lint.core import Finding

    def v(key):
        return (key, Finding(path="x.py", line=1, col=0, rule="r", message=key))

    # live+baselined tolerated, new passes through, stale fails
    fs = apply_ratchet([v("a"), v("b")], frozenset({"a", "c"}),
                       rule="r", baseline_path="base.py")
    msgs = [f.message for f in fs]
    assert "b" in msgs
    assert any("stale baseline entry `c`" in m for m in msgs)
    assert not any(m == "a" for m in msgs)


def test_budget_loosened_entry_fails_stale():
    """The ratchet's other half: loosening a budget row (or landing an
    improvement without tightening) fails until the row matches."""
    loose = {
        "fused/flat-count": dataclasses.replace(
            kernel_budgets.BUDGETS["fused/flat-count"], dispatches=2
        )
    }
    traces = {
        "fused/flat-count": kdispatch.audit_dispatch()["fused/flat-count"]
    }
    fs = kernel_budgets.audit_budgets(traces=traces, budgets=loose)
    assert any(
        "stale budget entry" in f.message and "tighten" in f.message
        for f in fs
    ), [f.message for f in fs]


def test_budget_missing_row_and_unmeasured_row_fail():
    traces = {
        "fused/flat-count": kdispatch.audit_dispatch()["fused/flat-count"]
    }
    fs = kernel_budgets.audit_budgets(
        traces=traces,
        budgets={"ghost/row": kernel_budgets.KernelBudget(dispatches=1)},
    )
    msgs = [f.message for f in fs]
    assert any("no budget row" in m for m in msgs), msgs
    assert any("stale baseline entry `ghost/row`" in m for m in msgs), msgs


def test_budget_table_row_count_pinned():
    """The reviewed budget-table shape: one row per audited signature.
    Adding a kernel forces a row (the table is total); dropping one
    forces deleting the row AND this pin."""
    assert len(kernel_budgets.BUDGETS) == 20
    assert set(kernel_budgets.BUDGETS) == {
        "fused/flat-count",
        "fused/group-eq-lut",
        "fused/percentile-hist",
        "fused/or-expr",
        "fused/topn-dashboard",
        "fused/multi-chunk",
        "fused/dist-step",
        "fused+decode/flat-count",
        "fused+decode/group-eq-lut",
        "fused+decode/percentile-hist",
        "fused+decode/or-expr",
        "fused+decode/topn-dashboard",
        "fused+decode/multi-chunk",
        "stream/mask-eq-in",
        "stream+decode/mask-eq-in",
        "ops/group_reduce",
        "ops/group_histogram",
        "parallel/dist-step",
        "ql/trace",
        "ql/property",
    }


def test_budget_table_agrees_with_plan_audit_matrix():
    """Every eval_shape-audited signature has a budget row: the plan
    audit, the precompile registry and the kernel budgets stay ONE
    matrix (test_cold_path pins registry<->audit agreement)."""
    from banyandb_tpu.lint.whole_program.plan_audit import default_entries

    audited = {e.name for e in default_entries()}
    assert audited <= set(kernel_budgets.BUDGETS), (
        audited - set(kernel_budgets.BUDGETS)
    )


def test_kernel_rules_catalogued():
    from banyandb_tpu.lint.whole_program import WP_RULES

    names = {n for n, _ in WP_RULES}
    assert {n for n, _ in KERNEL_RULES} <= names


# -- the audited tree --------------------------------------------------------


def test_kernel_audit_clean_tree_fast():
    fs = run_kernel_audit(fast=True)
    assert fs == [], "\n".join(f.render() for f in fs)


def test_kernel_audit_clean_tree_full():
    """The full gate including the lowering-audit (XLA compiles on CPU):
    fusion/bytes/collective classes all match the checked-in budgets."""
    fs = run_kernel_audit(fast=False)
    assert fs == [], "\n".join(f.render() for f in fs)


def test_cli_only_kernel_and_selection():
    from banyandb_tpu.lint.__main__ import main

    from pathlib import Path

    import banyandb_tpu

    pkg = str(Path(banyandb_tpu.__file__).parent)
    assert main(["--only", "layering", "--check", pkg]) == 0
    assert main(["--only", "bogus", pkg]) == 2


def test_cli_contradictory_only_rules_is_usage_error():
    """--check must never exit 0 having checked nothing: a --only/--rules
    combination that excludes every analyzer is a usage error."""
    from banyandb_tpu.lint.__main__ import main

    from pathlib import Path

    import banyandb_tpu

    pkg = str(Path(banyandb_tpu.__file__).parent)
    # --only=kernel excludes per-file rules; --rules=host-sync excludes
    # every whole-program family -> nothing would run
    assert main(["--check", "--only", "kernel", "--rules", "host-sync", pkg]) == 2
    # --only=rules + a whole-program-only rule name -> nothing would run
    assert main(["--check", "--only", "rules", "--rules", "layering", pkg]) == 2


def test_failed_measurement_does_not_cascade_into_budget_findings():
    """A signature whose measurement errored carries its failure finding
    only — no 'tighten widest to 0' / 'stale row' guidance on top."""
    fs = kernel_budgets.audit_budgets(
        traces={},
        budgets={"fused/flat-count": kernel_budgets.BUDGETS["fused/flat-count"]},
        failed={"fused/flat-count"},
    )
    assert fs == [], [f.message for f in fs]


def test_plan_audit_false_skips_kernel_family(monkeypatch):
    """run_whole_program(plan_audit=False) is the legacy 'AST analyses
    only' switch: it must skip BOTH jax-backed families (plan audit and
    the kernel audit), so the shared-state meta-test never pays — or
    fails on — kernel compiles."""
    from pathlib import Path

    import banyandb_tpu
    import banyandb_tpu.lint.kernel as kernel_mod
    from banyandb_tpu.lint.whole_program import run_whole_program

    def boom(fast=False):
        raise AssertionError("kernel audit must not run with plan_audit=False")

    monkeypatch.setattr(kernel_mod, "run_kernel_audit", boom)
    pkg = Path(banyandb_tpu.__file__).parent
    findings, stats = run_whole_program(pkg, plan_audit=False, only={"kernel"})
    assert findings == [] and "kernel_signatures" not in stats


# -- obs cross-check ---------------------------------------------------------


def test_static_dispatch_budget_bounds_observed_device_spans():
    """Close the loop between PR 5's measurement and this PR's
    prediction: run a REAL device-path aggregation and assert the
    observed device_execute span count is bounded by the static
    dispatch budget (scripts/obs_smoke.py asserts the same invariant on
    a 2-node cluster)."""
    from banyandb_tpu.api.model import (
        Aggregation,
        GroupBy,
        QueryRequest,
        TimeRange,
    )
    from banyandb_tpu.api.schema import FieldType, TagType
    from banyandb_tpu.obs import metrics as obs_metrics
    from banyandb_tpu.query.measure_exec import compute_partials

    n = 512
    rng = np.random.default_rng(3)
    m = kdispatch._measure_schema(
        [("svc", TagType.STRING)], [("v", FieldType.INT)]
    )
    src = kdispatch._source(
        n,
        1,
        {
            "svc": (
                [b"s0", b"s1", b"s2", b"s3"],
                rng.integers(0, 4, n).astype(np.int32),
            )
        },
        {"v": rng.integers(0, 50, n).astype(np.float64)},
    )
    req = QueryRequest(
        ("g",),
        "m",
        TimeRange(kdispatch.T0, kdispatch.T0 + n),
        group_by=GroupBy(("svc",)),
        agg=Aggregation("sum", "v"),
    )
    h = obs_metrics.stage_histogram("device_execute")
    before = h.snapshot()[0]
    compute_partials(m, req, [src])  # one part-batch, REAL device path
    observed = h.snapshot()[0] - before
    budget = kernel_budgets.dispatch_budget("measure")
    assert 0 < observed <= budget, (observed, budget)


def test_publish_budgets_to_meter():
    from banyandb_tpu.obs.metrics import Meter

    meter = Meter()
    n = kernel_budgets.publish_to_meter(meter)
    assert n == sum(
        1
        for r in kernel_budgets.BUDGETS.values()
        if r.dispatches is not None
    )
    text = meter.prometheus_text()
    assert 'kernel_dispatch_budget{signature="fused/flat-count"} 1' in text
    assert kernel_budgets.dispatch_budget("measure") == 1
    assert kernel_budgets.dispatch_budget("ql") == 0
    with pytest.raises(KeyError):
        kernel_budgets.dispatch_budget("nope")
