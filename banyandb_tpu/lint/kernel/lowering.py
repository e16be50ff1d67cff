"""kernel-lowering: what XLA actually makes of each plan signature.

``jit(fn).lower(...)`` + ``.compile()`` on the CPU backend — still zero
device kernel execution (nothing is dispatched) — yields three countable
facts per signature:

- **fusions**: fused computations in the optimized HLO.  A fusion-count
  jump means the compiler stopped fusing a stage (new materialized
  temporaries, more HBM round-trips on a real chip).
- **bytes accessed**: the compiler's traffic estimate
  (``cost_analysis()``).  The decode-throughput law (PAPERS.md
  2606.22423) says scans are bound by exactly this number; device-side
  decode (ROADMAP item 3) must shrink it by the compression ratio.
- **collectives**: all-reduce/all-gather/… ops in the lowered module.
  Single-device plan kernels must carry none; the shard_map mesh step
  (parallel/dist_exec, the SNIPPETS.md sharding pattern) carries exactly
  its psum/pmin/pmax set, and a change means the cross-shard combine
  plan changed.

Bytes and fusion counts ride the budget table as power-of-two *classes*
(``int.bit_length``) so an XLA point release moving an estimate a few
percent does not churn the ratchet, while a real regression — 2x the
traffic, a lost fusion pass — lands in the next class and fails.
"""

from __future__ import annotations

from typing import Optional

from banyandb_tpu.lint.core import Finding

RULE = "kernel-lowering"

_COLLECTIVE_TOKENS = (
    "all_reduce",
    "all-reduce",
    "all_gather",
    "all-gather",
    "all_to_all",
    "all-to-all",
    "collective_permute",
    "collective-permute",
    "reduce_scatter",
    "reduce-scatter",
)


def mesh_entry():
    """The shard_map mesh-variant audit entry: one representative
    distributed step (grouped sum/min/max + top-N over a ('shard','seg')
    mesh) lowered over a single CPU device — the collective *structure*
    (psum/pmin/pmax per output) is identical at any mesh size."""
    import inspect
    from functools import partial

    import jax
    import jax.numpy as jnp

    from banyandb_tpu.lint.whole_program.plan_audit import (
        KernelAudit,
        _rel_path,
    )
    from banyandb_tpu.parallel import dist_exec
    from banyandb_tpu.parallel import mesh as pmesh

    plan = dist_exec.DistPlan(
        tags_code=("svc",),
        fields=("v",),
        group_tags=("svc",),
        radices=(16,),
        num_groups=16,
        topn=4,
    )
    mesh = pmesh.make_mesh(1)
    from jax.sharding import PartitionSpec as P

    data_spec = P(("shard", "seg"))
    step = jax.shard_map(
        partial(dist_exec._step, plan),
        mesh=mesh,
        in_specs=(
            {
                "valid": data_spec,
                "tags": {"svc": data_spec},
                "fields": {"v": data_spec},
            },
            {},
            P(),
            P(),
        ),
        out_specs=dist_exec._out_specs(plan),
    )
    S = jax.ShapeDtypeStruct
    n = 1024
    return KernelAudit(
        name="parallel/dist-step",
        path=_rel_path(inspect.getsourcefile(dist_exec)),
        line=inspect.getsourcelines(dist_exec._step)[1],
        fn=jax.jit(step),
        args=(
            {
                "valid": S((1, n), jnp.bool_),
                "tags": {"svc": S((1, n), jnp.int32)},
                "fields": {"v": S((1, n), jnp.float32)},
            },
            {},
            S((), jnp.float32),
            S((), jnp.float32),
        ),
    )


def fused_mesh_entry():
    """The fused chunked-scan collective program
    (query/fused_exec.build_fused_dist_step) at a 2-chunk bucket,
    lowered over a single CPU device: the whole distributed scan is ONE
    program carrying exactly the staged mesh step's psum/pmin/pmax set —
    a collective-count change here means the fused path altered the
    cross-shard combine plan."""
    import inspect
    from functools import partial

    import jax
    import jax.numpy as jnp

    from banyandb_tpu.lint.whole_program.plan_audit import (
        KernelAudit,
        _rel_path,
    )
    from banyandb_tpu.parallel import dist_exec
    from banyandb_tpu.parallel import mesh as pmesh
    from banyandb_tpu.query import fused_exec

    plan = dist_exec.DistPlan(
        tags_code=("svc",),
        fields=("v",),
        group_tags=("svc",),
        radices=(16,),
        num_groups=16,
        topn=4,
    )
    num_chunks = 2
    mesh = pmesh.make_mesh(1)
    from jax.sharding import PartitionSpec as P

    data_spec = P(("shard", "seg"))
    step = jax.shard_map(
        partial(fused_exec._fused_dist_step, plan, num_chunks),
        mesh=mesh,
        in_specs=(
            {
                "valid": data_spec,
                "tags": {"svc": data_spec},
                "fields": {"v": data_spec},
            },
            {},
            P(),
            P(),
        ),
        out_specs=dist_exec._out_specs(plan),
    )
    S = jax.ShapeDtypeStruct
    n = num_chunks * 1024
    return KernelAudit(
        name="fused/dist-step",
        path=_rel_path(inspect.getsourcefile(fused_exec)),
        line=inspect.getsourcelines(fused_exec._fused_dist_step)[1],
        fn=jax.jit(step),
        args=(
            {
                "valid": S((1, n), jnp.bool_),
                "tags": {"svc": S((1, n), jnp.int32)},
                "fields": {"v": S((1, n), jnp.float32)},
            },
            {},
            S((), jnp.float32),
            S((), jnp.float32),
        ),
    )


def lower_entry(entry):
    """-> (lowered, compiled) for one audit entry, CPU backend."""
    import jax

    fn = entry.fn if hasattr(entry.fn, "lower") else jax.jit(entry.fn)
    lowered = fn.lower(*entry.args, **entry.kwargs)
    return lowered, lowered.compile()


def audit_entry(entry) -> tuple[list[Finding], Optional[dict]]:
    """-> (findings, measured columns) for one signature.

    Measured columns: ``collectives`` (lowered module), ``fusion_class``
    and ``bytes_class`` (compiled module / cost analysis) — ratcheted by
    kernel_budgets.BUDGETS.
    """
    findings: list[Finding] = []
    try:
        lowered, compiled = lower_entry(entry)
        lowered_text = lowered.as_text()
        compiled_text = compiled.as_text()
        cost = compiled.cost_analysis()
    except Exception as e:  # noqa: BLE001 — the finding IS the report
        findings.append(
            Finding(
                path=entry.path,
                line=entry.line,
                col=0,
                rule=RULE,
                message=(
                    f"[{entry.name}] lowering/compile failed on the CPU "
                    f"backend: {type(e).__name__}: {e}"
                ),
            )
        )
        return findings, None

    collectives = sum(lowered_text.count(t) for t in _COLLECTIVE_TOKENS)
    fusions = compiled_text.count("fusion(")
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    bytes_accessed = int(cost.get("bytes accessed", 0.0)) if cost else 0
    return findings, {
        "collectives": collectives,
        "fusion_class": fusions.bit_length(),
        "bytes_class": bytes_accessed.bit_length(),
    }
