"""kernel-budget: the checked-in per-signature kernel budget table.

This file IS the performance contract the kernel audit enforces — the
same role layer_config.py plays for the import graph.  One row per
audited signature; every column is a *measured* compile-time fact
(dispatch.py / jaxpr_audit.py / lowering.py) ratcheted with the shared
lint/core mechanics:

- measured **above** budget  -> regression finding, CI fails;
- measured **below** budget  -> the entry is stale (too loose) and fails
  until tightened, so an improvement — e.g. the fused whole-plan
  executor driving dispatches toward 1 per part-batch, or device-side
  decode shrinking bytes_class — is locked in the moment it lands.

Columns (None = not measured for that row's kind):

- ``dispatches``/``gets``/``puts``: jitted dispatches, batched
  device_get transfers, and host->device array ships per scenario run
  (dispatch.py's stub device; measure/stream scenarios are one
  part-batch, one scan chunk but for the multi-chunk rows).  The ql
  rows pin the trace/property executors to ZERO device work.
- ``widest``: widest dtype itemsize anywhere in the jaxpr (4 = the
  32-bit device contract; 8 would mean a 64-bit leak).
- ``bytes_class``/``fusion_class``: power-of-two class
  (``int.bit_length``) of the compiled HLO bytes-accessed estimate and
  fused-computation count — classes absorb XLA point-release noise,
  real regressions land in the next class.
- ``collectives``: collective ops in the lowered module; single-device
  plan kernels carry none, the parallel/dist-step mesh variant carries
  exactly its psum(count/sums) + pmin/pmax set.

``bytes_class``/``fusion_class`` are XLA **CPU**-compile classes: the
audit lowers and compiles for the host platform (no chip in CI), so
they track the installed jaxlib's CPU pipeline and say nothing about
the TPU executable.  Last re-measured with jax 0.9.0 / jaxlib 0.9.0; a
jaxlib upgrade that shifts a class re-measures the table, it is not a
kernel regression.

Legitimately changing a row: land the kernel change, run
``python -m banyandb_tpu.lint --check`` (or scripts/kernel_smoke.py),
and copy the measured value the failure reports into the row — tighter
is always allowed, looser must be argued in review like any baseline
growth (docs/linting.md "Kernel audit").
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from banyandb_tpu.lint.core import (
    Finding,
    ratchet_value,
    stale_entry_finding,
)

RULE = "kernel-budget"


@dataclass(frozen=True)
class KernelBudget:
    """One signature's budget row (None = column not measured)."""

    dispatches: Optional[int] = None
    gets: Optional[int] = None
    puts: Optional[int] = None
    widest: Optional[int] = None
    bytes_class: Optional[int] = None
    fusion_class: Optional[int] = None
    collectives: Optional[int] = None


def _b(dispatches=None, gets=None, puts=None, widest=None,
       bytes_class=None, fusion_class=None, collectives=None):
    return KernelBudget(dispatches, gets, puts, widest,
                        bytes_class, fusion_class, collectives)


# fmt: off
BUDGETS: dict[str, KernelBudget] = {
    # columns: (dispatches, gets, puts, widest, bytes_class,
    #           fusion_class, collectives)
    # the builtin measure plan matrix (query/fused_exec): ONE dispatch +
    # ONE batched get per part-batch regardless of chunk count — the
    # executor's raison d'être, ratcheted so staging can never creep
    # back; puts = stacked chunk columns + traced predicate arrays.  Of
    # the per-row key columns a batch holds what its program reads
    # (fused_exec.key_columns): valid, and ts + row where the plan tracks
    # scan order (group-eq-lut; the multi-chunk rows run a flat plan).
    "fused/flat-count":        _b(1, 1, 2, 4, 20, 4, 0),
    "fused/group-eq-lut":      _b(1, 1, 7, 4, 23, 5, 0),
    "fused/percentile-hist":   _b(1, 1, 3, 4, 25, 5, 0),
    "fused/or-expr":           _b(1, 1, 4, 4, 20, 4, 0),
    "fused/topn-dashboard":    _b(1, 1, 4, 4, 23, 5, 0),
    # the staging tripwire: a 2-chunk part-batch, still 1 dispatch/get
    # (dispatch columns only: the bucket is synthesized per run, so it
    # has no standing jaxpr/lowering entry)
    "fused/multi-chunk":       _b(1, 1, 2),
    # device-side decode twins (BYDB_DEVICE_DECODE=1, ROADMAP item 3):
    # the compressed ship form — narrow local codes + [S, L] remap LUTs
    # + src-ordinals + narrow int fields — STILL costs exactly one
    # dispatch and one batched get (decode fuses into the plan program);
    # puts grow by the LUT/ordinal ships, bytes_class is pinned so the
    # in-program decode can never double the traffic class, and
    # widest=4 proves the i8->i32 widen never leaks 64-bit
    "fused+decode/flat-count":      _b(1, 1, 2, 4, 20, 5, 0),
    "fused+decode/group-eq-lut":    _b(1, 1, 10, 4, 23, 5, 0),
    "fused+decode/percentile-hist": _b(1, 1, 5, 4, 25, 5, 0),
    "fused+decode/or-expr":         _b(1, 1, 6, 4, 20, 4, 0),
    "fused+decode/topn-dashboard":  _b(1, 1, 7, 4, 23, 5, 0),
    # compressed multi-chunk tripwire: staging AND decode-stage
    # de-fusion both show up here first
    "fused+decode/multi-chunk":     _b(1, 1, 2),
    # fused chunked-scan mesh step: the whole distributed scan as one
    # collective program, SAME psum(count/sums)+pmin+pmax set
    "fused/dist-step":         _b(widest=4, bytes_class=16, fusion_class=5, collectives=4),
    # stream retrieval mask: whole bool mask in one get
    "stream/mask-eq-in":       _b(1, 1, 3, 4, 19, 1, 0),
    # the narrow-ship twin (i8 source codes widened on device): same
    # dispatch/transfer shape — an extra put here means the stream
    # decode stage de-fused (dispatch columns only: the narrow form has
    # no standing jaxpr/lowering entry)
    "stream+decode/mask-eq-in": _b(1, 1, 3),
    # shared ops reductions every plan lowers onto (no executor path of
    # their own: jaxpr + lowering columns only)
    "ops/group_reduce":        _b(widest=4, bytes_class=24, fusion_class=4, collectives=0),
    "ops/group_histogram":     _b(widest=4, bytes_class=21, fusion_class=3, collectives=0),
    # shard_map mesh step: psum(count)+psum(sums)+pmin+pmax = 4
    # collectives (the hist/topn outputs reduce over already-combined
    # vectors)
    "parallel/dist-step":      _b(widest=4, bytes_class=16, fusion_class=4, collectives=4),
    # ql trace/property executors are host-only BY CONTRACT: zero
    # dispatches, zero transfers — a device leg appearing here is a bug
    "ql/trace":                _b(0, 0, 0),
    "ql/property":             _b(0, 0, 0),
}
# fmt: on


def budget_path() -> str:
    from banyandb_tpu.lint.whole_program.plan_audit import _rel_path

    return _rel_path(__file__)


def audit_budgets(
    widest: Optional[dict] = None,
    traces: Optional[dict] = None,
    lowered: Optional[dict] = None,
    budgets: Optional[dict] = None,
    anchors: Optional[dict] = None,
    failed: Optional[set] = None,
) -> list[Finding]:
    """Compare measured columns against the budget table.

    Any argument left None means that analyzer did not run (``--fast``
    skips lowering) and its columns are not judged.  Row-set agreement
    is judged from the measurements that DID run: a measured signature
    with no row fails (new kernels ship with a budget), a row no
    measurement covers fails as stale.  ``failed`` names signatures
    whose measurement itself errored — they already carry a failure
    finding and are excluded from both the column ratchet and the
    stale-row check (a failed measurement is not an improvement).
    """
    from banyandb_tpu.lint.kernel.dispatch import measured_columns

    budgets = BUDGETS if budgets is None else budgets
    bpath = budget_path()
    anchors = anchors or {}
    failed = failed or set()

    measured: dict[str, dict] = {}
    for name, w in (widest or {}).items():
        measured.setdefault(name, {})["widest"] = w
    for name, t in (traces or {}).items():
        if not t.error:
            measured.setdefault(name, {}).update(measured_columns(t))
    for name, cols in (lowered or {}).items():
        if cols is not None:
            measured.setdefault(name, {}).update(cols)
    for name in failed:
        measured.pop(name, None)

    findings: list[Finding] = []
    for name in sorted(set(measured) - set(budgets)):
        findings.append(
            Finding(
                path=anchors.get(name, (bpath, 1))[0],
                line=anchors.get(name, (bpath, 1))[1],
                col=0,
                rule=RULE,
                message=(
                    f"[{name}] audited signature has no budget row; add "
                    "one to lint/kernel/kernel_budgets.py with the "
                    "measured values (the table is total)"
                ),
            )
        )
    for key in sorted(set(budgets) - set(measured) - failed):
        findings.append(
            stale_entry_finding(
                key, rule=RULE, path=bpath, what="the audited signature"
            )
        )

    for name in sorted(measured):
        row = budgets.get(name)
        if row is None:
            continue  # already reported above
        cols = measured[name]
        path, line = anchors.get(name, (bpath, 1))
        for column, value in sorted(cols.items()):
            budget = getattr(row, column)
            if budget is None:
                continue
            findings += ratchet_value(
                name,
                column,
                value,
                budget,
                rule=RULE,
                path=path,
                line=line,
                budget_path=bpath,
                regression_hint=_HINTS.get(column, ""),
            )
    return findings


_HINTS = {
    "dispatches": (
        "every extra dispatch is a host round-trip per chunk; the fused "
        "executor (ROADMAP item 2) must drive this DOWN, never up"
    ),
    "gets": "result transfers must stay batched (one device_get per chunk)",
    "puts": (
        "extra host->device ships grow the pad/ship stage device-side "
        "decode (ROADMAP item 3) is meant to shrink"
    ),
    "widest": "64-bit values double HBM traffic; keep device math 32-bit",
    "bytes_class": (
        "the scan is decode-throughput-bound: bytes moved per query "
        "doubled a class"
    ),
    "fusion_class": (
        "XLA stopped fusing a stage — new materialized temporaries"
    ),
    "collectives": "the cross-shard combine plan changed",
}


# -- obs-plane export --------------------------------------------------------


def publish_to_meter(meter=None) -> int:
    """Export the static dispatch budgets as gauges
    (``kernel_dispatch_budget{signature=...}``) so the obs plane can be
    cross-checked against the prediction (scripts/obs_smoke.py asserts
    observed device_execute spans per query <= this budget).  -> rows
    published."""
    if meter is None:
        from banyandb_tpu.obs import global_meter

        meter = global_meter()
    n = 0
    for name, row in sorted(BUDGETS.items()):
        if row.dispatches is None:
            continue
        meter.gauge_set(
            "kernel_dispatch_budget",
            float(row.dispatches),
            labels={"signature": name},
        )
        n += 1
    return n


def dispatch_budget(kind: str = "measure") -> int:
    """The static per-part-batch dispatch budget for a signature family
    (max over its rows): the bound runtime ``device_execute`` span
    counts are asserted against."""
    # a measure plan runs as its fused program, in either ship form
    families = ("fused", "fused+decode") if kind == "measure" else (kind,)
    vals = [
        row.dispatches
        for name, row in BUDGETS.items()
        if name.split("/")[0] in families and row.dispatches is not None
    ]
    if not vals:
        raise KeyError(f"no dispatch budgets for kind {kind!r}")
    return max(vals)
