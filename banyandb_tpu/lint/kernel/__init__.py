"""bdjit: whole-program kernel audit — the third analysis family on the
bdlint engine (docs/linting.md "Kernel audit").

The fused whole-plan executor and device-side decode (ROADMAP items 2-3)
are ratcheted against *countable, compile-time* properties of our
kernels: how many jitted dispatches a plan costs, what crosses the
PCIe/ICI bus, and which dtypes ride the device.  Four analyzers state
those properties statically — everything runs through ``jax.make_jaxpr``
and ``jit(...).lower()`` on the CPU backend with **zero device kernel
execution**:

- ``kernel-jaxpr``     walk every audited kernel's closed jaxpr: host
                       callbacks (``pure_callback``/``io_callback``/
                       ``debug_print``), 64-bit dtypes anywhere inside a
                       device plan, accumulator-narrowing conversions
                       (f32 -> f16/bf16), and large output buffers that
                       alias an input without ``donate_argnums``
- ``kernel-dispatch``  drive the real executor entry paths
                       (measure_exec.compute_partials,
                       stream_exec.device_tag_mask, ql_exec trace/
                       property) under an instrumented stub device and
                       count jitted dispatches + device_get/device_put
                       transfers per builtin plan signature — also
                       proving the executor resolves EXACTLY the
                       signature the precompile registry warms
- ``kernel-lowering``  ``lower(...).compile()`` per signature on CPU:
                       fused-computation count, bytes-accessed estimate
                       (cost_analysis) and collective count — including
                       the shard_map mesh variant from parallel/dist_exec
- ``kernel-budget``    the checked-in per-signature budget table
                       (kernel_budgets.BUDGETS) enforced with the same
                       ratchet discipline as the layering baseline:
                       regressions fail, improvements fail the now-stale
                       entry until it is tightened

Findings carry witness chains (signature -> jaxpr eqn / HLO measure) and
anchor at the kernel builder's source line, so they flow through the
bdlint suppression and SARIF machinery unchanged.
"""

from __future__ import annotations

from typing import Optional

# (name, summary) catalog for --list-rules / the SARIF driver rules.
KERNEL_RULES = (
    ("kernel-jaxpr", "host callback / 64-bit dtype / narrowing inside a kernel"),
    ("kernel-dispatch", "dispatch+transfer count per plan signature (stub device)"),
    ("kernel-lowering", "HLO fusion/bytes/collective audit per signature"),
    ("kernel-budget", "ratcheted per-signature dispatch/transfer/dtype budgets"),
)


def kernel_entries():
    """The audited kernel matrix: the plan_audit entries (ONE list feeds
    eval_shape contracts, precompile warming and this audit) plus the
    shard_map mesh-variant step from parallel/dist_exec."""
    from banyandb_tpu.lint.whole_program.plan_audit import default_entries

    from banyandb_tpu.lint.kernel.lowering import fused_mesh_entry, mesh_entry

    return list(default_entries()) + [mesh_entry(), fused_mesh_entry()]


def stored_entries(registry=None, limit: int = 16):
    """Audit entries for the top stored/recorded plan signatures — the
    live population the precompile registry warms beyond the builtin
    matrix.  Empty in a fresh lint process (no store bound); in an
    embedded run (server, bench) the hottest production signatures get
    the same jaxpr audit the builtins do.  These are *dynamic*: they
    carry no checked-in budget rows, so they are jaxpr-audited only."""
    import inspect

    import jax
    import jax.numpy as jnp

    from banyandb_tpu.lint.whole_program.plan_audit import (
        KernelAudit,
        _rel_path,
    )
    from banyandb_tpu.query import fused_exec, precompile, stream_exec

    if registry is None:
        registry = precompile.default_registry()
    S = jax.ShapeDtypeStruct
    entries = []
    for i, (kind, spec) in enumerate(registry.signatures()[:limit]):
        try:
            if kind == "measure":
                # an evidence row runs as its plan's one-chunk program
                spec = fused_exec.FusedSpec(plan=spec, num_chunks=1)
            if kind in ("measure", "fused"):
                mod = fused_exec
                fn = fused_exec._build_kernel(spec)
                args = (
                    precompile.fused_chunk_struct(spec),
                    precompile.pred_struct(spec.plan),
                    S((), jnp.float32),
                    S((), jnp.float32),
                    # a program that inverts: no histogram yet, Q quantiles
                    *(
                        (None, S((spec.quantiles,), jnp.float32))
                        if spec.quantiles
                        else ()
                    ),
                )
                anchor = fused_exec._build_kernel
            elif kind == "stream_mask":
                mod = stream_exec
                fn = stream_exec._build_kernel(spec)
                args = precompile.mask_structs(spec)
                anchor = stream_exec._build_kernel
            else:
                continue
        except Exception:  # noqa: BLE001 — a stale stored signature is
            continue  # skipped here exactly like warming skips it
        entries.append(
            KernelAudit(
                name=f"stored/{kind}#{i}",
                path=_rel_path(inspect.getsourcefile(mod)),
                line=inspect.getsourcelines(anchor)[1],
                fn=fn,
                args=args,
                cache_key=spec,
            )
        )
    return entries


def run_kernel_audit(fast: bool = False) -> list:
    """Run the kernel analyzers -> findings (empty = budgets hold).

    ``fast=True`` skips the lowering-audit (XLA compiles dominate the
    runtime; jaxpr + dispatch + their budget columns still run).
    """
    from banyandb_tpu.lint.kernel import dispatch, jaxpr_audit, kernel_budgets

    entries = kernel_entries()
    findings = []
    anchors = {e.name: (e.path, e.line) for e in entries}
    # signatures whose measurement itself failed: they already carry a
    # failure finding and must NOT be judged against the budget table (a
    # widest=0 / absent row would cascade into misleading "tighten" /
    # "stale" guidance)
    failed: set[str] = set()
    measured_widest: dict[str, int] = {}
    for entry in entries:
        fs, widest = jaxpr_audit.audit_entry(entry)
        findings += fs
        if widest > 0:
            measured_widest[entry.name] = widest
        else:
            failed.add(entry.name)
    for entry in stored_entries():
        # dynamic (recorded) signatures: jaxpr invariants only — no
        # checked-in budget row to ratchet against
        fs, _widest = jaxpr_audit.audit_entry(entry)
        findings += fs
    traces = dispatch.audit_dispatch()
    findings += dispatch.dispatch_findings(traces)
    failed |= {t.name for t in traces.values() if t.error}
    anchors.update(
        {t.name: (t.path, t.line) for t in traces.values() if t.path}
    )
    lowered = None
    if not fast:
        from banyandb_tpu.lint.kernel import lowering

        lowered = {}
        for entry in entries:
            fs, meas = lowering.audit_entry(entry)
            findings += fs
            lowered[entry.name] = meas
            if meas is None:
                failed.add(entry.name)
    findings += kernel_budgets.audit_budgets(
        widest=measured_widest,
        traces=traces,
        lowered=lowered,
        anchors=anchors,
        failed=failed,
    )
    return findings


def kernel_stats(fast: bool = False) -> dict:
    """Summary keys folded into the CLI run stats."""
    from banyandb_tpu.lint.kernel.kernel_budgets import BUDGETS

    return {
        "kernel_signatures": len(BUDGETS),
        "kernel_lowering": not fast,
    }
