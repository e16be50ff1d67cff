"""dataset.py - the deployment's data from --seed, and the plain reference.

A configuration file (configs/<name>.json) fixes series, regions,
buckets and the field distributions; `Dataset` draws the data from the
seed as two [buckets, series] arrays (time-major, one point per series
per bucket, timestamps aligned to the bucket).  `Dataset.answer` is the
NumPy oracle for one query spec on that data, `check` the comparison
that decides `correct`, `gaps` the numbers it compares, each against its
limit in LIMITS.  Nothing here imports the program or JAX.
"""

from __future__ import annotations

import math

import numpy as np

HIST_BUCKETS = 512  # the device percentile histogram's width
SUM_RTOL = 1e-5  # INT sums: f32 tile partials + Kahan (tests/test_precision.py)
FLOAT_RTOL = 1e-9  # FLOAT sums/means: exact f64 on the host by design
REHEARSAL_SERIES_CUT = 20  # BENCH_E2E_REHEARSE: series (and batches) / 20
# what a run compares (`gaps`, and run.py's two counts), and the most each may
# read in a correct run: the guarantees of configs/<name>.json, a value's gap
# in units of its tolerance
LIMITS = {
    "readback_missing": 0, "unanswered": 0,
    "groups_gap": 0, "count_gap": 0, "value_gap_tol": 1.0, "top_gap_tol": 1.0,
}
GAP_CAP = 1e30  # what a gap that is not a number reads: JSON holds it, no limit admits it


class Dataset:
    """[buckets, series] arrays plus what the oracle derives from them."""

    def __init__(self, cfg: dict, seed: int, rehearse: bool = False):
        d = cfg["data"]
        cut = REHEARSAL_SERIES_CUT if rehearse else 1
        self.series = max(d["series"] // cut, 2 * d["regions"])
        self.regions = d["regions"]
        self.buckets = d["buckets"]
        self.bucket_ms = d["bucket_ms"]
        self.t0 = d["t0_ms"]
        self.batch_rows = max(d["batch_rows"] // cut, 1)
        self.snapshot_every_rows = max(d["snapshot_every_rows"] // cut, 1)
        rng = np.random.default_rng(seed)
        shape = (self.buckets, self.series)
        h, v = d["hits"], d["value"]
        if h["dist"] != "uniform_int" or v["dist"] != "gamma":
            raise ValueError("configs: hits is uniform_int, value is gamma")
        self.hits = rng.integers(h["lo"], h["hi"] + 1, shape, dtype=np.int64)
        self.value = rng.gamma(v["shape"], v["scale"], shape)
        self.ts = self.t0 + np.arange(self.buckets, dtype=np.int64) * self.bucket_ms
        # region is a fixed function of svc, as an endpoint's zone is
        self.region_of = (np.arange(self.series) % self.regions).astype(np.int32)
        self._hits_csum = np.concatenate(
            [np.zeros((1, self.series), np.int64), np.cumsum(self.hits, axis=0)]
        )

    @property
    def points(self) -> int:
        return self.buckets * self.series

    @property
    def t_last(self) -> int:
        return int(self.ts[-1])

    def svc_names(self) -> list[str]:
        return ["svc_%06d" % i for i in range(self.series)]

    def region_names(self) -> list[str]:
        return ["r%d" % i for i in range(self.regions)]

    def rows(self, s: int, e: int) -> dict:
        """Rows [s, e) of the time-major flattening, as write columns."""
        idx = np.arange(s, e, dtype=np.int64)
        svc = (idx % self.series).astype(np.int32)
        return {
            "ts": self.ts[idx // self.series],
            "svc": svc,
            "region": self.region_of[svc],
            "value": self.value.reshape(-1)[s:e],
            "hits": self.hits.reshape(-1)[s:e],
        }

    # -- the oracle ------------------------------------------------------------
    def bucket_range(self, lo: int, hi: int) -> tuple[int, int]:
        """Buckets whose timestamp lies in [lo, hi] (BETWEEN is inclusive)."""
        return (
            int(np.searchsorted(self.ts, lo, "left")),
            int(np.searchsorted(self.ts, hi, "right")),
        )

    def _mask(self, where: dict | None) -> np.ndarray:
        if not where:
            return np.ones(self.series, bool)
        tag, op, val = where["tag"], where["op"], where["value"]
        if tag == "region":
            hit = self.region_of == int(val[1:])
        elif tag == "svc":
            hit = np.arange(self.series) == int(val.split("_")[1])
        else:
            raise ValueError(f"oracle: no tag {tag!r}")
        if op == "=":
            return hit
        if op == "!=":
            return ~hit
        raise ValueError(f"oracle: no operator {op!r}")

    def answer(self, q: dict) -> dict:
        """Exact answer to query spec `q` ->
        {"names": group names, "count": int per group, "metric": value per
        group ([G] or [G, len(quantiles)]), "top": n or None, "points":
        points inside the time range, "pct_tol": one histogram bucket}."""
        b0, b1 = self.bucket_range(q["lo"], q["hi"])
        nb = b1 - b0
        mask = self._mask(q.get("where"))
        agg, field = q["agg"], q["field"]
        col = self.hits if field == "hits" else self.value
        win = col[b0:b1]
        by_svc = q["group_by"] == "svc"
        pct_tol = 0.0
        if agg == "percentile":
            qs = q["quantiles"]
            pct_tol = float(win.max() - win.min()) / HIST_BUCKETS if nb else 0.0
            if by_svc:
                groups = np.nonzero(mask)[0]
                v = np.sort(win[:, groups], axis=0)
                count = np.full(groups.size, nb, np.int64)
                metric = np.stack([v[_rank(x, nb) - 1] for x in qs], axis=1)
            else:
                groups, count, rows = [], [], []
                for r in range(self.regions):
                    sel = mask & (self.region_of == r)
                    if not sel.any() or not nb:
                        continue
                    v = np.sort(win[:, sel].reshape(-1))
                    groups.append(r)
                    count.append(v.size)
                    rows.append([v[_rank(x, v.size) - 1] for x in qs])
                groups = np.array(groups, np.int64)
                count = np.array(count, np.int64)
                metric = np.array(rows, np.float64).reshape(len(groups), len(qs))
        else:
            per_count = np.where(mask, nb, 0).astype(np.int64)
            if agg in ("sum", "mean", "count"):
                if field == "hits":
                    per = (self._hits_csum[b1] - self._hits_csum[b0]).astype(np.float64)
                else:
                    per = win.sum(axis=0)
            elif agg in ("min", "max"):
                per = getattr(win, agg)(axis=0) if nb else np.zeros(self.series)
            else:
                raise ValueError(f"oracle: no aggregate {agg!r}")
            if by_svc:
                groups = np.nonzero(mask & (per_count > 0))[0]
                count, metric = per_count[groups], per[groups]
            else:
                reg = self.region_of[mask]
                count = np.bincount(reg, weights=per_count[mask], minlength=self.regions)
                if agg in ("min", "max"):
                    fold = np.minimum if agg == "min" else np.maximum
                    metric = np.full(self.regions, np.inf if agg == "min" else -np.inf)
                    fold.at(metric, reg, per[mask])
                else:
                    metric = np.bincount(reg, weights=per[mask], minlength=self.regions)
                groups = np.nonzero(count > 0)[0]
                count, metric = count[groups].astype(np.int64), metric[groups]
            if agg == "mean":
                metric = metric / np.maximum(count, 1)
            elif agg == "count":
                metric = count.astype(np.float64)
        names = (
            ["svc_%06d" % g for g in groups] if by_svc else ["r%d" % g for g in groups]
        )
        return {
            "names": names,
            "count": count,
            "metric": metric,
            "top": q.get("top"),
            "points": nb * self.series,
            "pct_tol": pct_tol,
        }


def _rank(q: float, n: int) -> int:
    """The q-quantile is the value of rank ceil(q*N), clamped to [1, N]."""
    return min(max(math.ceil(q * n), 1), n)


def answer_of(result: dict) -> dict:
    """Server result JSON -> {group: (count, value)}."""
    values = dict(result["values"])
    counts = values.pop("count")
    if values:
        (agg_vals,) = values.values()
    else:  # a bare count(...) carries only the count column
        agg_vals = counts
    return {
        g[0]: (int(c), v) for g, c, v in zip(result["groups"], counts, agg_vals)
    }


def _tolerance(q: dict, want: dict) -> tuple[float, float]:
    """(rtol, atol) of the path that computed `q`'s values."""
    if q["agg"] == "percentile":
        return 0.0, want["pct_tol"] * 1.001
    if q["field"] == "hits" or q["agg"] in ("min", "max"):
        return SUM_RTOL, 0.0
    return FLOAT_RTOL, 0.0


def _in_tolerances(diff, tol):
    """diff / tol; a NaN, or a gap over a tolerance of 0, reads GAP_CAP."""
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        rel = np.asarray(diff, np.float64) / tol
    return np.minimum(np.nan_to_num(rel, nan=GAP_CAP, posinf=GAP_CAP), GAP_CAP)


def gaps(q: dict, got: dict, want: dict) -> dict:
    """The numbers `check` compares, each the worst over the answer and
    held to LIMITS: groups returned that the data does not hold plus
    groups short of (or over) what is due, the widest count difference,
    the widest value difference in units of its tolerance, and for TOP n
    by how many tolerances the best group left out beats the worst
    returned."""
    rtol, atol = _tolerance(q, want)
    index = {n: i for i, n in enumerate(want["names"])}
    known = {g: v for g, v in got.items() if g in index}
    top = want["top"]
    due = min(top, len(index)) if top else len(index)
    out = {"groups_gap": len(got) - len(known) + abs(len(got) - due), "count_gap": 0,
           "value_gap_tol": 0.0}
    at = np.array([index[g] for g in known], np.int64)
    if known:
        counts = np.array([c for c, _ in known.values()], np.int64)
        values = np.array([v for _, v in known.values()], np.float64)
        w = np.asarray(want["metric"], np.float64)[at]
        diff, tol = np.abs(values - w), atol + rtol * np.abs(w)
        rel = np.where(diff == 0, 0.0, _in_tolerances(diff, tol))
        out["count_gap"] = int(np.abs(counts - np.asarray(want["count"])[at]).max())
        out["value_gap_tol"] = float(rel.max())
    if top:
        metric = np.asarray(want["metric"], np.float64)
        inside = np.zeros(metric.size, bool)
        inside[at] = True
        out["top_gap_tol"] = 0.0
        if inside.any() and not inside.all():
            worst_in, best_out = metric[inside].min(), metric[~inside].max()
            if best_out > worst_in:
                out["top_gap_tol"] = float(
                    _in_tolerances(best_out - worst_in, rtol * abs(best_out) + atol)
                )
    return out


def check(q: dict, got: dict, want: dict) -> str | None:
    """None when `got` (answer_of a reply) meets the configuration's
    guarantees against `want` (Dataset.answer), else what differs:
    counts exact, INT sums within SUM_RTOL, FLOAT sums within
    FLOAT_RTOL, percentiles within one histogram bucket, and TOP n
    membership exact except between groups closer than the tolerance."""
    index = {n: i for i, n in enumerate(want["names"])}
    unknown = [g for g in got if g not in index]
    if unknown:
        return f"groups the data does not hold: {sorted(unknown)[:5]}"
    rtol, atol = _tolerance(q, want)
    for g, (count, value) in got.items():
        i = index[g]
        if count != int(want["count"][i]):
            return f"{g}: count {count}, oracle {int(want['count'][i])}"
        if not np.allclose(value, want["metric"][i], rtol=rtol, atol=atol):
            return f"{g}: value {value}, oracle {want['metric'][i]}"
    top = want["top"]
    if not top:
        if len(got) != len(index):
            return f"{len(got)} groups, oracle {len(index)}"
        return None
    if len(got) != min(top, len(index)):
        return f"TOP {top} returned {len(got)} of {len(index)} groups"
    metric = np.asarray(want["metric"], np.float64)
    inside = np.zeros(metric.size, bool)
    inside[[index[g] for g in got]] = True
    if inside.all():
        return None
    # a valid top-n: nothing left out beats anything returned by more
    # than the tolerance of the path that computed it
    worst_in, best_out = metric[inside].min(), metric[~inside].max()
    if best_out > worst_in + rtol * abs(best_out) + atol:
        return f"TOP {top}: left out {best_out}, returned {worst_in}"
    return None
