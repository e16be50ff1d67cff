"""TPU routing, checked without a chip.

CPU runs pick `matmul`/`scatter` and interpret Pallas, so the code a TPU
actually traces — `pallas` group-reduce inside the plan kernels and
inside `jax.shard_map` — is never reached by the rest of the suite.  Here the backend
check is forced to "tpu" and each program is lowered for the `tpu`
platform from the CPU: shard_map typing (`vma`) errors and primitives
the Pallas TPU lowering does not implement surface at trace/lower time.
Whether libtpu then compiles the Mosaic kernels is for `chip_smoke.py`
on the chip to say.
"""

import jax
import jax.numpy as jnp
import pytest

from banyandb_tpu.query import precompile


@pytest.fixture()
def tpu_routing(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


# the plan kernels' two trailing scalars (histogram lo, span)
_SCALARS = (
    jax.ShapeDtypeStruct((), jnp.float32),
    jax.ShapeDtypeStruct((), jnp.float32),
)


def _lower_tpu(fn, *structs) -> str:
    return fn.trace(*structs).lower(lowering_platforms=("tpu",)).as_text()


def _mesh_structs(plan, d: int, n: int):
    S = jax.ShapeDtypeStruct
    return (
        {
            "valid": S((d, n), jnp.bool_),
            "tags": {t: S((d, n), jnp.int32) for t in plan.tags_code},
            "fields": {f: S((d, n), jnp.float32) for f in plan.fields},
        },
        {t: S((), jnp.int32) for t in plan.eq_preds},
        S((), jnp.float32),
        S((), jnp.float32),
    )


def test_mesh_steps_lower_for_tpu_with_pallas_inside_shard_map(tpu_routing):
    from banyandb_tpu.parallel import DistPlan, dist_exec, make_mesh
    from banyandb_tpu.query import fused_exec

    # a plan no other test builds: the step caches are process-global and
    # a TPU-routed trace must not be served to a CPU test
    plan = DistPlan(
        tags_code=("region", "svc"),
        fields=("latency",),
        group_tags=("svc",),
        radices=(24,),
        num_groups=24,
        eq_preds=("region",),
        topn=3,
        want_hist="latency",
    )
    mesh = make_mesh(2, 2)
    try:
        legacy = dist_exec.build_distributed_step(mesh, plan)
        fused = fused_exec.build_fused_dist_step(mesh, plan, 2)
        for step, n in ((legacy, 4096), (fused, 2 * 4096)):
            text = _lower_tpu(step, *_mesh_structs(plan, 4, n))
            assert "tpu_custom_call" in text  # the Pallas kernel, not scatter
    finally:
        for cache in (dist_exec._STEP_CACHE, fused_exec._DIST_STEP_CACHE):
            for key in [k for k in cache if plan in k]:
                del cache[key]


def test_builtin_plans_lower_for_tpu(tpu_routing):
    """The TopN dashboard plan (G=1024 -> pallas) in both structures
    its batch ships: the field as a narrow int, and as f32."""
    from banyandb_tpu.query import fused_exec

    fspec = dict(precompile.builtin_fused())["fused/topn-dashboard"]
    preds = precompile.pred_struct(fspec.plan)
    # a fresh build: never the executor's process-global kernel cache
    kernel = fused_exec._build_kernel(fspec)
    for float_fields in (False, True):
        chunk = precompile.fused_chunk_struct(fspec, float_fields)
        assert "tpu_custom_call" in _lower_tpu(kernel, chunk, preds, *_SCALARS)


def test_ep9k_plan_lowers_for_tpu_as_xla_scatter(tpu_routing):
    """`ep9k.topn-6h`'s plan (benchmarks/e2e: sum(hits) WHERE region != r
    GROUP BY svc TOP 10, G = 9,000, four 1M-row chunks in one fused
    program): over the Pallas limit and under the sort threshold, so on
    a TPU it is the XLA scatter group-by and holds no Pallas kernel."""
    from banyandb_tpu.ops import groupby
    from banyandb_tpu.query import fused_exec, measure_exec

    spec = measure_exec.PlanSpec(
        tags_code=("region", "svc"),
        fields=("hits",),
        preds=(measure_exec._PredSpec("code", "region", "ne"),),
        group_tags=("svc",),
        radices=(9000,),
        num_groups=9000,
        want_minmax=False,
        nrows=measure_exec.SCAN_CHUNK,
        want_rep=True,
    )
    assert groupby.select_group_method(spec.nrows, spec.num_groups) == "scatter"
    fspec = fused_exec.FusedSpec(plan=spec, num_chunks=4)
    kernel = fused_exec._build_kernel(fspec)  # a fresh build, not the cache
    for float_fields in (False, True):
        chunk = precompile.fused_chunk_struct(fspec, float_fields)
        text = _lower_tpu(kernel, chunk, precompile.pred_struct(spec), *_SCALARS)
        assert "tpu_custom_call" not in text
        assert "stablehlo.scatter" in text and "stablehlo.sort" not in text
