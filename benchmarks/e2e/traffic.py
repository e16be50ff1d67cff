"""traffic.py - the one general query generator.

A traffic mix (traffic/<name>.json) is data: `clients` closed-loop
clients, each cycling through `cycle`, a list of panel names; `panels`
maps a name to a query spec:

    agg        sum | count | mean | min | max | percentile
    field      hits | value
    quantiles  for percentile
    group_by   svc | region
    where      optional {"tag", "op": "=" | "!=", "value"}; the value
               "draw" draws one of the deployment's regions per query
    top        optional n (TOP n BY field, descending)
    limit      optional n (LIMIT n): the groups the panel asks for.  The
               server's default is 100 groups (BydbQL, as upstream's
               measure query), so a panel over more groups without
               `top` must name its limit (selfcheck.py refuses it else)
    range_ms   length of the time range
    lo         "last": the range ends at the newest point (a repeating
               panel), or {"draw_ms": [a, b]}: the range starts a..b ms
               after the oldest point, drawn per query at millisecond
               resolution and never on a bucket edge, so every query
               holds the same number of buckets

`warm_spread` (optional, default 4) is how many warm-up queries set-up
spreads evenly over each drawn panel's range of starts before it draws
more at random, so that every count of parts and every padded size a
start can meet is compiled before the window opens.  `warm_at` (optional)
lists further places, as shares of that range, for the starts whose shape
an even spread misses: pctl-6h's are the starts less than one bucket
before a part boundary, which read one part per shard instead of two.

`spec` turns a panel into one concrete query (spec dict with lo/hi and
the drawn values, the BydbQL text from it); `stream` is a client's
endless sequence.  The oracle reads the same spec dict, so the text and
the reference cannot drift apart.
"""

from __future__ import annotations

import itertools

import numpy as np


def ql_of(q: dict, group: str, measure: str) -> str:
    if q["agg"] == "percentile":
        qs = ", ".join(repr(float(x)) for x in q["quantiles"])
        sel = f"PERCENTILE({q['field']}, {qs})"
    else:
        sel = f"{q['agg']}({q['field']})"
    ql = (
        f"SELECT {sel} FROM MEASURE {measure} IN {group} "
        f"TIME BETWEEN {q['lo']} AND {q['hi']}"
    )
    w = q.get("where")
    if w:
        ql += f" WHERE {w['tag']} {w['op']} '{w['value']}'"
    ql += f" GROUP BY {q['group_by']}"
    if q.get("top"):
        ql += f" TOP {q['top']} BY {q['field']}"
    if q.get("limit"):
        ql += f" LIMIT {q['limit']}"
    return ql


def repeats(panel: dict) -> bool:
    """A panel whose text is the same every time (cacheable)."""
    drawn_where = (panel.get("where") or {}).get("value") == "draw"
    return panel["lo"] == "last" and not drawn_where


def spec(
    panel_name: str, panel: dict, ds, rng: np.random.Generator,
    at: float | None = None,
) -> dict:
    """One concrete query of `panel` on dataset `ds`.  `at` in [0, 1)
    places a drawn start at that share of its range instead of drawing
    it (the warm-up's even spread)."""
    q = {
        k: panel[k]
        for k in ("agg", "field", "quantiles", "group_by", "top", "limit")
        if panel.get(k) is not None
    }
    q["panel"] = panel_name
    span = int(panel["range_ms"])
    if panel["lo"] == "last":
        hi = ds.t_last
        lo = hi - span + 1
    else:
        a, b = panel["lo"]["draw_ms"]
        off = int(rng.integers(a, b)) if at is None else a + int((b - a) * at)
        if off % ds.bucket_ms == 0:
            off += 1
        lo = ds.t0 + off
        hi = lo + span
    q["lo"], q["hi"] = int(lo), int(hi)
    w = panel.get("where")
    if w:
        w = dict(w)
        if w["value"] == "draw":
            w["value"] = "r%d" % int(rng.integers(0, ds.regions))
        q["where"] = w
    return q


def draws(seed: int, client: int, warm: bool = False) -> np.random.Generator:
    """The generator a client's draws come from; set-up's warm-up has
    one of its own, so no window query repeats a warm-up query."""
    return np.random.default_rng([seed, client, int(warm)])


def stream(mix: dict, ds, seed: int, client: int):
    """Client `client`'s endless sequence of query specs."""
    rng = draws(seed, client)
    for name in itertools.cycle(mix["cycle"]):
        yield spec(name, mix["panels"][name], ds, rng)
