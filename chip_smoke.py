"""chip_smoke.py — the quickest proof that the served measure path still
starts and answers correctly on the chip.

What it drives (the standalone server's main path, through the entry
points a user calls, at the size of BASELINE.json config 3 — "TopN
endpoint_cpm, 100k series, 24h range, 4 shards"):

    build cpp/libbydb_native.so from source
    -> python -m banyandb_tpu.server --root <dir>      (DEFAULT flags)
    -> diagnostics: every query-executing process must report the TPU
    -> registry: group g (4 shards), measure m
    -> 10,000,000 points / 100,000 series through the columnar write
       topic, every ack checked; snapshot flushes
    -> precompile warm finished with precompile_errors == 0
    -> four BydbQL queries, each checked against a NumPy oracle computed
       from the same arrays; the three device queries must show
       device_ms > 0 on a path other than host_f64
    -> SIGTERM, restart on the same root, the four queries again: every
       acknowledged write read back, second boot's compile cache hits
    -> on a host with >= 4 TPU devices, after the server exited: the
       mesh step (__graft_entry__) in one child on the real chips

This process stays off JAX (NumPy data + oracle, gRPC client only): a
chip belongs to one process, and that process is the server.  Any failed
phase exits non-zero with no result line.  The last stdout line of a
passing run is one JSON object naming the device as the server's JAX
reports it.  Printed timings are orientation, not records.
"""

from __future__ import annotations

import argparse
import base64
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
from importlib import metadata

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

FULL_ROWS = 10_000_000
N_SERIES = 100_000
N_REGION = 8
SHARDS = 4
T0 = 1_700_000_000_000
SPAN_MS = 24 * 3600 * 1000
BATCH_ROWS = 500_000  # one columnar envelope; < the bus's 64 MiB frame
FLUSH_EVERY = 2_000_000  # several parts per shard, not one mega-part
ADDR = "127.0.0.1:17912"  # the server's default --port
BUDGET_S = 1150.0  # the contract allows 1200 s, compilation included
HIST_BUCKETS = 512  # device percentile histogram width (ops/percentile)
SUM_RTOL = 1e-5  # tests/test_precision.py: f32 tile partials + Kahan


class SmokeFailure(Exception):
    """A phase failed; the message says which and why."""


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="dataset seed")
    ap.add_argument(
        "--rows", type=int, default=FULL_ROWS,
        help="points to load (a cut below the full size is printed; "
        "series, shards and fields are never cut)",
    )
    args = ap.parse_args(argv)
    if args.rows < 1:
        ap.error("--rows must be positive")
    return args


# -- dataset (from --seed) -----------------------------


def make_dataset(seed: int, rows: int) -> dict:
    """rows points over N_SERIES services x N_REGION regions spread over
    24 h: svc/region codes, a FLOAT field `value`, an INT field `hits`."""
    rng = np.random.default_rng(seed)
    step = max(1, SPAN_MS // rows)
    return {
        "step": step,
        "ts": T0 + np.arange(rows, dtype=np.int64) * step,
        "svc": rng.integers(0, N_SERIES, rows).astype(np.int32),
        "region": rng.integers(0, N_REGION, rows).astype(np.int32),
        "value": rng.gamma(2.0, 40.0, rows).astype(np.float64),
        "hits": rng.integers(0, 1000, rows).astype(np.int64),
    }


def svc_name(i: int) -> str:
    return "svc_%06d" % i


def region_name(i: int) -> str:
    return "r%d" % i


def queries(data: dict) -> dict:
    lo, hi = T0, int(data["ts"][-1]) + 1
    rng = f"FROM MEASURE m IN g TIME BETWEEN {lo} AND {hi}"
    return {
        "sum_by_region": f"SELECT sum(hits) {rng} GROUP BY region",
        "topn_sum_by_svc": (
            f"SELECT sum(hits) {rng} WHERE region != 'r3' "
            "GROUP BY svc TOP 10 BY hits"
        ),
        "percentile_by_region": (
            f"SELECT PERCENTILE(value, 0.5, 0.99) {rng} GROUP BY region"
        ),
        "topn_mean_by_svc": (
            f"SELECT mean(value) {rng} GROUP BY svc TOP 10 BY value"
        ),
    }


# queries whose reduction must run on the device; topn_mean_by_svc is a
# FLOAT aggregate, exact-f64 on the host by design (ROADMAP S2)
DEVICE_QUERIES = ("sum_by_region", "topn_sum_by_svc", "percentile_by_region")


# -- the NumPy oracle ----------------------------------------------------------


def _top_groups(metric: np.ndarray, count: np.ndarray, k: int) -> list[int]:
    """Top-k group ids by metric desc; ties at the boundary resolve by
    group key ascending (svc names sort like their indices)."""
    ids = np.nonzero(count > 0)[0]
    order = np.lexsort((ids, -metric[ids]))
    return [int(i) for i in ids[order][:k]]


def oracle(data: dict) -> dict:
    """Exact answers to `queries(data)` from the same arrays."""
    svc, region = data["svc"], data["region"]
    hits, value = data["hits"], data["value"]
    out: dict = {}

    cnt = np.bincount(region, minlength=N_REGION)
    sums = np.bincount(region, weights=hits, minlength=N_REGION)
    out["sum_by_region"] = {
        region_name(r): {"count": int(cnt[r]), "value": float(sums[r])}
        for r in range(N_REGION)
        if cnt[r]
    }

    keep = region != 3
    cnt = np.bincount(svc[keep], minlength=N_SERIES)
    sums = np.bincount(svc[keep], weights=hits[keep], minlength=N_SERIES)
    out["topn_sum_by_svc"] = {
        svc_name(g): {"count": int(cnt[g]), "value": float(sums[g])}
        for g in _top_groups(sums, cnt, 10)
    }

    pct: dict = {}
    for r in range(N_REGION):
        v = np.sort(value[region == r])
        if not v.size:
            continue
        # the q-quantile is the value of rank ceil(q*N), clamped to [1, N]
        ranks = [min(max(math.ceil(q * v.size), 1), v.size) for q in (0.5, 0.99)]
        pct[region_name(r)] = {
            "count": int(v.size),
            "value": [float(v[k - 1]) for k in ranks],
        }
    out["percentile_by_region"] = pct
    # the device histogram spans the scanned field range; its exactness
    # contract is one bucket width (ops/percentile.py)
    out["percentile_tolerance"] = float(
        (value.max() - value.min()) / HIST_BUCKETS
    )

    cnt = np.bincount(svc, minlength=N_SERIES)
    sums = np.bincount(svc, weights=value, minlength=N_SERIES)
    mean = sums / np.maximum(cnt, 1)
    out["topn_mean_by_svc"] = {
        svc_name(g): {"count": int(cnt[g]), "value": float(mean[g])}
        for g in _top_groups(mean, cnt, 10)
    }
    return out


def answer_of(result: dict) -> dict:
    """Server result JSON -> {group: {"count", "value"}} (the oracle's shape)."""
    values = dict(result["values"])
    counts = values.pop("count")
    (agg_vals,) = values.values()
    return {
        g[0]: {"count": int(c), "value": v}
        for g, c, v in zip(result["groups"], counts, agg_vals)
    }


def check_answer(
    name: str, got: dict, want: dict, pct_tol: float, ref: str = "oracle"
) -> None:
    """Counts exact, group membership equal, values to the contract of
    the path that produced them."""
    if set(got) != set(want):
        raise SmokeFailure(
            f"{name}: groups differ: got {sorted(got)}, {ref} {sorted(want)}"
        )
    for g, w in want.items():
        if got[g]["count"] != w["count"]:
            raise SmokeFailure(
                f"{name}[{g}]: count {got[g]['count']}, {ref} {w['count']}"
            )
        if name == "percentile_by_region":
            ok = np.allclose(got[g]["value"], w["value"], rtol=0, atol=pct_tol)
        elif name == "topn_mean_by_svc":
            ok = np.isclose(got[g]["value"], w["value"], rtol=1e-9, atol=0)
        else:
            ok = np.isclose(got[g]["value"], w["value"], rtol=SUM_RTOL, atol=0)
        if not ok:
            raise SmokeFailure(
                f"{name}[{g}]: value {got[g]['value']}, {ref} {w['value']}"
            )


# -- server child --------------------------------------------------------------


def _child_env() -> dict:
    """The ambient environment with the checkout importable."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (REPO, env.get("PYTHONPATH", "")) if p
    )
    return env


class Server:
    """`python -m banyandb_tpu.server --root <root>` with default flags."""

    def __init__(self, root: str, boot: int):
        self.log_path = os.path.join(root, f"server-boot{boot}.log")
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "banyandb_tpu.server", "--root", root],
            cwd=REPO,
            env=_child_env(),
            stdout=self._log,
            stderr=subprocess.STDOUT,
            start_new_session=True,  # killable as a group
        )

    def log_tail(self, n: int = 6000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                return f.read()[-n:].decode(errors="replace")
        except OSError:
            return ""

    def terminate(self, timeout: float = 90.0) -> None:
        """SIGTERM and wait for a clean exit."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            self.kill()
            raise SmokeFailure(
                f"server did not exit {timeout:.0f}s after SIGTERM"
            ) from None
        self._log.close()
        if rc != 0:
            raise SmokeFailure(f"server exited rc={rc} on SIGTERM")

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except OSError:
                self.proc.kill()
            self.proc.wait()
        self._log.close()


class Smoke:
    def __init__(self, args: argparse.Namespace, platform: str):
        from banyandb_tpu.cluster.rpc import GrpcTransport

        self.args = args
        self.platform = platform
        self.deadline = time.monotonic() + BUDGET_S
        self.tr = GrpcTransport()
        self.server: Server | None = None
        self.root = tempfile.mkdtemp(prefix="bydb-chip-smoke-")

    # -- plumbing ----------------------------------------------------------
    def left(self) -> float:
        left = self.deadline - time.monotonic()
        if left <= 0:
            raise SmokeFailure(f"out of time ({BUDGET_S:.0f}s budget)")
        return left

    def call(self, topic: str, env: dict, timeout: float = 60.0) -> dict:
        return self.tr.call(ADDR, topic, env, timeout=min(timeout, self.left()))

    def metrics(self) -> dict:
        """The server's unlabeled gauges/counters, `banyandb_` stripped."""
        from banyandb_tpu.obs.prom import parse_exposition

        # generous: the scrape queues behind a streamagg backfill that
        # holds the registry lock (tens of seconds at this size)
        text = self.call("metrics", {}, timeout=300.0)["prometheus"]
        return {
            name.removeprefix("banyandb_"): value
            for name, labels, value in parse_exposition(text)
            if not labels
        }

    def boot(self, n: int) -> dict:
        """Start the server, wait for health, check what it runs on."""
        from banyandb_tpu.cluster.rpc import TransportError

        t0 = time.monotonic()
        self.server = Server(self.root, n)
        while True:
            if self.server.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited rc={self.server.proc.returncode} at boot:\n"
                    + self.server.log_tail()
                )
            try:
                self.call("health", {}, timeout=5.0)
                break
            except TransportError:
                self.left()
                time.sleep(0.5)
        diag = self.call("diagnostics", {})
        procs = {"server": diag["runtime"]}
        for name, rt in (diag.get("workers") or {}).items():
            procs[f"worker {name}"] = rt
        for who, rt in procs.items():
            if not rt or rt.get("backend") != self.platform:
                raise SmokeFailure(
                    f"{who} runs on {rt and rt.get('backend')!r}, not "
                    f"{self.platform!r}: {rt}"
                )
        rt = diag["runtime"]
        print(
            f"boot {n}: {time.monotonic() - t0:.1f}s  platform={rt['backend']} "
            f"device_kind={rt['device_kind']!r} devices={rt['device_count']} "
            f"codec={rt['codec']} processes={len(procs)}",
            flush=True,
        )
        return rt

    def wait_warm(self) -> None:
        """Wait for the precompile warm to drain; fail on any error."""
        t0 = time.monotonic()
        quiet = 0
        while quiet < 2:
            m = self.metrics()
            quiet = quiet + 1 if not m.get("precompile_warming") else 0
            self.left()
            time.sleep(1.0)
        if m.get("precompile_errors"):
            raise SmokeFailure(
                f"precompile_errors={m['precompile_errors']:.0f} "
                "(a kernel failed to compile):\n" + self.server.log_tail()
            )
        if not m.get("precompile_compiled"):
            raise SmokeFailure(f"precompile warmed nothing: {m}")
        print(
            f"precompile warm: waited {time.monotonic() - t0:.1f}s, "
            f"compiled={m['precompile_compiled']:.0f} errors=0 "
            f"compile_cache hits={m.get('compile_cache_hits', 0):.0f} "
            f"misses={m.get('compile_cache_misses', 0):.0f}",
            flush=True,
        )

    # -- phases --------------------------------------------------------------
    def create_schema(self) -> None:
        self.call("registry", {"op": "create", "kind": "group", "item": {
            "name": "g", "catalog": "measure",
            "resource_opts": {
                "shard_num": SHARDS, "replicas": 0,
                "segment_interval": {"num": 1, "unit": "day"},
                # the dataset's day is fixed (T0) so the segment layout
                # is the same in every run; a TTL counted from the wall
                # clock would let the retention loop expire it mid-run
                "ttl": {"num": 36500, "unit": "day"}, "stages": [],
            },
        }})
        self.call("registry", {"op": "create", "kind": "measure", "item": {
            "group": "g", "name": "m",
            "tags": [{"name": "svc", "type": "string"},
                     {"name": "region", "type": "string"}],
            "fields": [{"name": "value", "type": "float"},
                       {"name": "hits", "type": "int"}],
            "entity": {"tag_names": ["svc"]}, "interval": "",
            "index_mode": False,
        }})

    def load(self, data: dict) -> None:
        """Columnar write topic, every ack checked; snapshot flushes."""
        def b64(a: np.ndarray, dtype: str) -> str:
            return base64.b64encode(a.astype(dtype).tobytes()).decode()

        rows = data["ts"].size
        svc_dict = [svc_name(i) for i in range(N_SERIES)]
        region_dict = [region_name(i) for i in range(N_REGION)]
        t0 = time.monotonic()
        since_flush = 0
        for s in range(0, rows, BATCH_ROWS):
            e = min(s + BATCH_ROWS, rows)
            ack = self.call("measure-write-cols", {
                "group": "g", "name": "m",
                "ts": b64(data["ts"][s:e], "<i8"),
                "versions": b64(np.ones(e - s, np.int64), "<i8"),
                "tags": {
                    "svc": {"dict": svc_dict,
                            "codes": b64(data["svc"][s:e], "<i4")},
                    "region": {"dict": region_dict,
                               "codes": b64(data["region"][s:e], "<i4")},
                },
                "fields": {
                    "value": b64(data["value"][s:e], "<f8"),
                    "hits": b64(data["hits"][s:e], "<f8"),
                },
            }, timeout=300.0)
            if ack.get("written") != e - s:
                raise SmokeFailure(f"write [{s}:{e}) acked {ack}")
            since_flush += e - s
            if since_flush >= FLUSH_EVERY or e == rows:
                self.call("snapshot", {}, timeout=600.0)
                since_flush = 0
        dt = time.monotonic() - t0
        print(
            f"load: {rows} points acked + flushed in {dt:.1f}s "
            f"({rows / dt:,.0f} points/s)",
            flush=True,
        )

    def run_queries(
        self, data: dict, want: dict, label: str, device_legs: bool
    ) -> dict:
        """The four queries, each against the oracle; -> result JSONs.
        ``device_legs``: the three device queries must show a device
        leg.  True for the first pass, where nothing is cached or
        materialized yet; after it the server's default-on autoreg loop
        may answer a repeated signature from materialized windows, which
        is a correct answer with no scan to time."""
        from banyandb_tpu.obs.tracer import iter_spans

        results = {}
        for name, ql in queries(data).items():
            t0 = time.monotonic()
            reply = self.call("bydbql", {"ql": ql, "trace": True}, timeout=600.0)
            ms = (time.monotonic() - t0) * 1000
            result = reply["result"]
            tree = (result.pop("trace", None) or {}).get("span_tree")
            tags = [
                s.get("tags") or {}
                for s in iter_spans(tree)
                if s.get("name") == "reduce"
            ]
            device_ms = sum(float(t.get("device_ms") or 0.0) for t in tags)
            paths = sorted({str(t.get("path")) for t in tags})
            print(
                f"{label} {name}: {ms:.0f} ms  served={reply.get('served')} "
                f"path={'+'.join(paths) or '-'} device_ms={device_ms:.1f} "
                f"chunks={sum(int(t.get('chunks') or 0) for t in tags)} "
                f"dispatches={sum(int(t.get('dispatches') or 0) for t in tags)}",
                flush=True,
            )
            if device_legs and name in DEVICE_QUERIES and not any(
                t.get("path") != "host_f64"
                and float(t.get("device_ms") or 0.0) > 0
                for t in tags
            ):
                raise SmokeFailure(
                    f"{name}: no device leg (paths={paths}, "
                    f"device_ms={device_ms})"
                )
            check_answer(
                name, answer_of(result), want[name],
                want["percentile_tolerance"],
            )
            results[name] = result
        return results

    def mesh_leg(self, device_count: int) -> None:
        """The mesh step on >= 4 real chips, after the server released
        them; never on an emulated mesh."""
        if device_count < 4:
            print(f"mesh leg not run ({device_count} device)", flush=True)
            return
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "__graft_entry__.py")],
            cwd=REPO, env=_child_env(), capture_output=True, text=True,
            timeout=self.left(),
        )
        report = None
        for line in proc.stdout.splitlines():
            if line.startswith('{"dryrun_multichip"'):
                report = json.loads(line)["dryrun_multichip"]
        if proc.returncode != 0 or report is None:
            raise SmokeFailure(
                f"mesh leg failed rc={proc.returncode}:\n"
                f"{proc.stdout[-3000:]}\n{proc.stderr[-6000:]}"
            )
        if (
            report["platform"] != self.platform
            or len(set(report["input_devices"])) < 4
            or report["count"] != report["host_union"]
        ):
            raise SmokeFailure(f"mesh leg wrong: {report}")
        print(f"mesh leg: {json.dumps(report)}", flush=True)

    # -- the run ---------------------------------------------------------------
    def run(self) -> dict:
        from banyandb_tpu.utils import native

        rows = self.args.rows
        if rows != FULL_ROWS:
            print(
                f"CUT: rows={rows} of {FULL_ROWS} (series={N_SERIES}, "
                f"shards={SHARDS}, fields uncut)",
                flush=True,
            )
        print(
            "versions: python=%s jax=%s jaxlib=%s libtpu=%s numpy=%s" % (
                sys.version.split()[0], _version("jax"), _version("jaxlib"),
                _version("libtpu"), np.__version__,
            ),
            flush=True,
        )
        so = native.build(force=True)
        if native.lib() is None:
            raise SmokeFailure(f"{so} was built but does not load")
        print(f"native codec built from source: {so}", flush=True)

        t0 = time.monotonic()
        data = make_dataset(self.args.seed, rows)
        want = oracle(data)
        print(f"dataset + oracle: {time.monotonic() - t0:.1f}s", flush=True)

        rt = self.boot(1)
        if rt["codec"] != "native":
            raise SmokeFailure(f"server runs the {rt['codec']} codec")
        self.create_schema()
        self.load(data)
        self.wait_warm()
        first = self.run_queries(data, want, "pass 1", device_legs=True)
        self.server.terminate()

        self.boot(2)
        self.wait_warm()
        second = self.run_queries(
            data, want, "pass 2 (after restart)", device_legs=False
        )
        m = self.metrics()
        if not m.get("compile_cache_hits"):
            raise SmokeFailure(f"second boot: no compile cache hits: {m}")
        print(
            f"second boot compile_cache_hits={m['compile_cache_hits']:.0f}",
            flush=True,
        )
        # both passes already matched the oracle (counts exact: every
        # acknowledged write was read back after the restart); between
        # them the answers must agree to the same contract.  Byte
        # identity is reported, not required: a background merge between
        # the passes reorders the f32 accumulation, and a signature the
        # autoreg loop materialized meanwhile answers in exact f64.
        for name in first:
            check_answer(
                name, answer_of(second[name]), answer_of(first[name]),
                want["percentile_tolerance"], ref="pass 1",
            )
        identical = json.dumps(first, sort_keys=True) == json.dumps(
            second, sort_keys=True
        )
        print(f"answers byte-identical across restart: {identical}", flush=True)
        self.server.terminate()
        self.mesh_leg(int(rt["device_count"]))
        return {
            "platform": rt["backend"],
            "kind": rt["device_kind"],
            "count": int(rt["device_count"]),
        }

    def close(self) -> None:
        self.tr.close()
        if self.server is not None:
            self.server.kill()
        shutil.rmtree(self.root, ignore_errors=True)


def _version(dist: str) -> str:
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return "absent"


def run_smoke(args: argparse.Namespace, platform: str = "tpu") -> dict:
    """Run every phase against a server that must report `platform`;
    -> the device block.  Raises on the first failed phase."""
    smoke = Smoke(args, platform)
    try:
        device = smoke.run()
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise SmokeFailure("chip_smoke itself initialised a JAX backend")
        return device
    except BaseException:
        if smoke.server is not None:
            print(
                f"--- server log tail ({smoke.server.log_path}) ---\n"
                + smoke.server.log_tail(),
                file=sys.stderr, flush=True,
            )
        raise
    finally:
        smoke.close()


def result_line(device: dict) -> str:
    """The last stdout line of a passing run: exactly the keys `ok` and
    `device` {platform, kind, count} — the checker admits no others."""
    return json.dumps({
        "ok": True,
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    })


def main(argv=None) -> int:
    args = parse_args(argv)
    t0 = time.monotonic()
    try:
        device = run_smoke(args)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(f"chip_smoke passed in {time.monotonic() - t0:.0f}s", flush=True)
    print(
        "summary: " + json.dumps(
            {"rows": args.rows, "seed": args.seed, "claim": None}
        ),
        flush=True,
    )
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
