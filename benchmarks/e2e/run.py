"""run.py - one run of one cell of BENCHMARK.json on the served path.

    python benchmarks/e2e/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process stays off JAX (NumPy data and oracle, a gRPC client).  It
starts ONE child, served.py, which runs the program's own server with
default flags on a fresh root and is the process that holds the chip.

    set-up : build cpp/libbydb_native.so if absent -> boot -> schema ->
             load (every ack checked) -> count every acked point back ->
             precompile warm drained, no errors -> warm-up queries of
             the cell's own shapes until two in a row compile nothing
    window : the traffic mix's closed-loop clients for --seconds
    after  : stop the server, check every answer against the oracle,
             reduce spans / counters / the profiler trace, print each
             number compared beside its limit (stderr, and `compared`
             in the result line)

`setup_s` is process start to window start.  The last stdout line is the
one JSON result; everything else is on earlier lines and in
chiprun_out/e2e/<cell>-seed<n>-trace<t>.{summary.json,queries.jsonl}.
No TPU (or a server on another backend) -> exit 1 and no result line;
BENCH_E2E_REHEARSE=1 asks for a CPU rehearsal at a cut size instead.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # setup_s counts from here

import argparse  # noqa: E402
import base64  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import dataset  # noqa: E402
import readers  # noqa: E402
import tracereduce  # noqa: E402
import traffic  # noqa: E402

ADDR = "127.0.0.1:17912"  # the server's default --port
BUS_METHOD = "/banyandb.Bus/Call"
QUERY_TIMEOUT_S = 60.0
BUDGET_S = 1150.0  # a first (compiling) run may take 1200 s
TRACE_SLICE_S = 10.0
P95_MIN_QUERIES = 200
MAX_WARM_QUERIES = 32
# the server's merge sweep runs every 10 s (storage/loops.py); a merge changes the
# number of parts a query reads, and with it the shapes it compiles
SETTLE_S = 12.0
MAX_FILL_ROUNDS = 60
OUT_DIR = os.path.join(CHECKOUT, "chiprun_out", "e2e")
_PROM_LINE = re.compile(r"^([a-zA-Z_:][\w:]*)\s+(\S+)$")


class BenchFailure(Exception):
    """The run cannot give a result; the message says why."""


def say(msg: str) -> None:
    print(msg, flush=True)


# -- files found by name ---------------------------------------------------------


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell `name` of BENCHMARK.json with its configuration, its
    traffic mix and the metric files that apply to it."""
    bench = load_json(CHECKOUT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}

    def applies(m: dict) -> bool:
        return not m.get("workloads") or name in m["workloads"]

    per_layer = {m["name"] for m in bench["per_layer"] if applies(m)}
    metric_files = [
        load_json(path) for path in sorted(glob.glob(os.path.join(HERE, "metrics", "*.json")))
    ]
    return {
        "name": name,
        "chips": cell["chips"],
        "config": load_json(CHECKOUT, files[cell["config"]]),
        "mix": load_json(HERE, "traffic", cell["traffic"] + ".json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "metric_files": [m for m in metric_files if m["name"] in per_layer],
    }


# -- the client --------------------------------------------------------------------


class Client:
    """The bus's one gRPC method with a JSON envelope, as
    cluster/rpc.py's GrpcTransport speaks it."""

    def __init__(self) -> None:
        import grpc

        self._grpc = grpc
        limit = 64 * 1024 * 1024
        self._channel = grpc.insecure_channel(ADDR, options=[
            ("grpc.max_receive_message_length", limit),
            ("grpc.max_send_message_length", limit),
        ])
        self._stub = self._channel.unary_unary(
            BUS_METHOD,
            request_serializer=lambda b: b,
            response_deserializer=lambda b: b,
        )

    def call(self, topic: str, envelope: dict, timeout: float = 60.0) -> dict:
        payload = json.dumps({"topic": topic, "envelope": envelope}).encode()
        try:
            raw = self._stub(payload, timeout=timeout)
        except self._grpc.RpcError as e:
            raise BenchFailure(f"rpc {topic}: {e.code()}") from e
        msg = json.loads(raw)
        if not msg.get("ok"):
            raise BenchFailure(f"rpc {topic}: {msg.get('error', 'remote error')}")
        return msg["reply"]

    def close(self) -> None:
        self._channel.close()


class Server:
    """served.py <control dir> --root <root>: the program's server with
    default flags, plus the benchmark's control thread."""

    def __init__(self, root: str, ctl: str):
        self.ctl = ctl
        self._sent = 0
        self.log_path = os.path.join(root, "server.log")
        self._log = open(self.log_path, "wb")
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (CHECKOUT, env.get("PYTHONPATH", "")) if p
        )
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "served.py"), ctl, "--root", root],
            cwd=CHECKOUT, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True,  # killable as a group
        )

    def ask(self, req: dict, timeout: float = 120.0) -> dict:
        """One request to served.py's control thread."""
        self._sent += 1
        tmp = os.path.join(self.ctl, f"req-{self._sent}.tmp")
        with open(tmp, "w") as f:
            json.dump(req, f)
        os.replace(tmp, os.path.join(self.ctl, f"req-{self._sent}.json"))
        rsp_path = os.path.join(self.ctl, f"rsp-{self._sent}.json")
        deadline = time.monotonic() + timeout
        while not os.path.exists(rsp_path):
            if self.proc.poll() is not None or time.monotonic() > deadline:
                raise BenchFailure(f"served.py did not answer {req['op']}")
            time.sleep(0.02)
        rsp = load_json(rsp_path)
        if "error" in rsp:
            raise BenchFailure(f"served.py {req['op']}: {rsp['error']}")
        return rsp

    def log_tail(self, n: int = 6000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(f.tell() - n, 0))
                return f.read().decode(errors="replace")
        except OSError:
            return ""

    def stop(self, timeout: float = 90.0) -> None:
        """SIGTERM, wait; SIGKILL the group if it does not go."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout)
            except subprocess.TimeoutExpired:
                pass
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except OSError:
                self.proc.kill()
            self.proc.wait()
        self._log.close()


# -- one run -----------------------------------------------------------------------


class Run:
    def __init__(self, args: argparse.Namespace, cell: dict, rehearse: bool):
        self.args, self.cell, self.rehearse = args, cell, rehearse
        self.platform = "cpu" if rehearse else "tpu"
        self.deadline = T_START + BUDGET_S
        self.tmp = tempfile.mkdtemp(prefix="bench-e2e-")
        self.root = os.path.join(self.tmp, "root")
        self.ctl = os.path.join(self.tmp, "ctl")
        self.trace_dir = os.path.join(self.tmp, "trace")
        for d in (self.root, self.ctl):
            os.makedirs(d)
        self.cli = Client()
        self.server: Server | None = None
        self.setup: dict = {}
        self.settled_at = 0.0  # when the load's last merge sweep has passed
        self.records: list[dict] = []  # every query of the run, set-up's too
        self._lock = threading.Lock()
        sch = cell["config"]["schema"]
        self.group, self.measure = sch["group"], sch["measure"]

    def left(self) -> float:
        left = self.deadline - time.monotonic()
        if left <= 0:
            raise BenchFailure(f"out of time ({BUDGET_S:.0f}s budget)")
        return left

    def call(self, topic: str, env: dict, timeout: float = 60.0) -> dict:
        return self.cli.call(topic, env, timeout=min(timeout, self.left()))

    def prom(self) -> dict:
        """The server's unlabeled /metrics samples, `banyandb_` stripped."""
        text = self.call("metrics", {}, timeout=300.0)["prometheus"]
        out = {}
        for line in text.splitlines():
            m = _PROM_LINE.match(line.strip())
            if m:
                try:
                    out[m.group(1).removeprefix("banyandb_")] = float(m.group(2))
                except ValueError:
                    pass
        return out

    # -- set-up ------------------------------------------------------------------
    def build_native(self) -> None:
        so = os.path.join(CHECKOUT, "cpp", "libbydb_native.so")
        if not os.path.exists(so):
            subprocess.run(
                ["make", "-C", os.path.join(CHECKOUT, "cpp")],
                check=True, capture_output=True, timeout=180,
            )

    def boot(self) -> dict:
        t0 = time.monotonic()
        self.server = Server(self.root, self.ctl)
        while True:
            if self.server.proc.poll() is not None:
                raise BenchFailure(
                    f"server exited rc={self.server.proc.returncode} at boot:\n"
                    + self.server.log_tail()
                )
            try:
                self.call("health", {}, timeout=5.0)
                break
            except BenchFailure:
                self.left()
                time.sleep(0.25)
        diag = self.call("diagnostics", {})
        rt = diag["runtime"]
        if rt.get("backend") != self.platform:
            raise BenchFailure(
                f"server runs on {rt.get('backend')!r}, not {self.platform!r}: {rt}"
            )
        if diag.get("workers"):
            raise BenchFailure(f"server started workers: {sorted(diag['workers'])}")
        if int(rt["device_count"]) < self.cell["chips"]:
            raise BenchFailure(
                f"{rt['device_count']} device, the cell asks for {self.cell['chips']}"
            )
        peaks = load_json(HERE, "peaks.json")
        if not self.rehearse and rt["device_kind"] not in peaks:
            raise BenchFailure(f"device kind {rt['device_kind']!r} is not in peaks.json")
        if rt.get("codec") != "native":
            raise BenchFailure(f"server runs the {rt.get('codec')} codec")
        self.setup["boot_s"] = time.monotonic() - t0
        say(
            f"boot: {self.setup['boot_s']:.1f}s platform={rt['backend']} "
            f"device_kind={rt['device_kind']!r} devices={rt['device_count']} codec={rt['codec']}"
        )
        return rt

    def create_schema(self) -> None:
        sch = self.cell["config"]["schema"]
        self.call("registry", {"op": "create", "kind": "group", "item": {
            "name": sch["group"], "catalog": "measure",
            "resource_opts": {
                "shard_num": sch["shards"], "replicas": sch["replicas"],
                "segment_interval": {"num": sch["segment_interval_days"], "unit": "day"},
                # the data's day is fixed; a TTL counted from the wall
                # clock would let retention delete it mid-run
                "ttl": {"num": sch["ttl_days"], "unit": "day"}, "stages": [],
            },
        }})
        self.call("registry", {"op": "create", "kind": "measure", "item": {
            "group": sch["group"], "name": sch["measure"],
            "tags": sch["tags"], "fields": sch["fields"],
            "entity": {"tag_names": sch["entity"]}, "interval": "",
            "index_mode": False,
        }})

    def load(self, ds: dataset.Dataset) -> None:
        """Columnar write topic, every ack checked; snapshot as the
        configuration says; then every acked point counted back."""
        def b64(a: np.ndarray, dtype: str) -> str:
            return base64.b64encode(np.ascontiguousarray(a, dtype).tobytes()).decode()

        svc_dict, region_dict = ds.svc_names(), ds.region_names()
        t0 = time.monotonic()
        acked = since_flush = 0
        for s in range(0, ds.points, ds.batch_rows):
            e = min(s + ds.batch_rows, ds.points)
            rows = ds.rows(s, e)
            ack = self.call("measure-write-cols", {
                "group": self.group, "name": self.measure,
                "ts": b64(rows["ts"], "<i8"),
                "versions": b64(np.ones(e - s, np.int64), "<i8"),
                "tags": {
                    "svc": {"dict": svc_dict, "codes": b64(rows["svc"], "<i4")},
                    "region": {"dict": region_dict, "codes": b64(rows["region"], "<i4")},
                },
                "fields": {
                    "value": b64(rows["value"], "<f8"),
                    "hits": b64(rows["hits"], "<f8"),
                },
            }, timeout=300.0)
            if ack.get("written") != e - s:
                raise BenchFailure(f"write [{s}:{e}) acked {ack}")
            acked += e - s
            since_flush += e - s
            if since_flush >= ds.snapshot_every_rows or e == ds.points:
                self.call("snapshot", {}, timeout=600.0)
                since_flush = 0
        dt = time.monotonic() - t0
        self.settled_at = time.monotonic() + SETTLE_S
        self.setup.update(points=acked, load_s=dt, load_points_per_s=acked / dt)
        say(f"load: {acked} points acked + flushed in {dt:.1f}s ({acked / dt:,.0f} points/s)")
        # an acknowledged write is read back: count(hits) by region over
        # the whole range, summed, is the points acked
        t0 = time.monotonic()
        ql = (
            f"SELECT count(hits) FROM MEASURE {self.measure} IN {self.group} "
            f"TIME BETWEEN {ds.t0} AND {ds.t_last} GROUP BY region"
        )
        reply = self.call("bydbql", {"ql": ql}, timeout=600.0)
        seen = sum(int(c) for c in reply["result"]["values"]["count"])
        self.setup["readback_s"] = time.monotonic() - t0
        self.setup["readback_seen"] = seen
        say(f"read back: {seen} of {acked} acked points in {self.setup['readback_s']:.1f}s")

    def wait_warm(self) -> None:
        """Until the precompile warm has drained; any error fails the run."""
        t0 = time.monotonic()
        quiet = 0
        while quiet < 2:
            m = self.prom()
            quiet = quiet + 1 if not m.get("precompile_warming") else 0
            self.left()
            if quiet < 2:
                time.sleep(0.5)
        if m.get("precompile_errors"):
            raise BenchFailure(
                f"precompile_errors={m['precompile_errors']:.0f}:\n" + self.server.log_tail()
            )
        self.setup["quiet_wait_s"] = time.monotonic() - t0
        say(
            f"precompile warm: waited {self.setup['quiet_wait_s']:.1f}s "
            f"compiled={m.get('precompile_compiled', 0):.0f} "
            f"misses={m.get('compile_cache_misses', 0):.0f} "
            f"hits={m.get('compile_cache_hits', 0):.0f}"
        )

    def query(self, cli: Client, q: dict, phase: str) -> dict:
        """One query -> its record (kept for the oracle check)."""
        ql = traffic.ql_of(q, self.group, self.measure)
        env = {"ql": ql}
        if self.args.trace:
            env["trace"] = True
        rec = {"q": q, "ql": ql, "phase": phase, "sent": time.time()}
        t0 = time.perf_counter()
        try:
            reply = cli.call("bydbql", env, timeout=QUERY_TIMEOUT_S)
        except BenchFailure as e:
            reply = None
            rec["error"] = str(e)
        rec["latency_ms"] = (time.perf_counter() - t0) * 1000.0
        rec["done"] = time.time()
        if reply is not None:
            result = reply["result"]
            tree = (result.pop("trace", None) or {}).get("span_tree")
            rec["served"] = reply.get("served")
            rec["answer"] = dataset.answer_of(result)
            if tree:
                rec["tree"] = tree
                rec["root_ms"] = float(tree.get("duration_ms") or 0.0)
        with self._lock:
            self.records.append(rec)
        return rec

    def programs_loaded(self) -> float:
        """Programs XLA compiled or took from the persistent cache."""
        m = self.prom()
        return m.get("compile_cache_misses", 0.0) + m.get("compile_cache_hits", 0.0)

    def warm_up(self, ds: dataset.Dataset) -> None:
        """The cell's own shapes, until nothing compiles.  Drawn panels
        run `warm_spread` queries spread evenly over their range of
        starts and one at each share of it in `warm_at`, then more at
        random until a merge sweep has passed since the load and two in
        a row load no new program (compiled or from the cache);
        repeating panels run in rounds until none is answered by a scan
        and the materialized windows stand still."""
        t0 = time.monotonic()
        mix = self.cell["mix"]
        panels = {n: mix["panels"][n] for n in dict.fromkeys(mix["cycle"])}
        rng = traffic.draws(self.args.seed, 0, warm=True)
        spread = int(mix.get("warm_spread", 4))
        places = [(n + 0.5) / spread for n in range(spread)] + list(mix.get("warm_at", []))
        loaded = self.programs_loaded()
        for name, panel in panels.items():
            if traffic.repeats(panel):
                continue
            quiet = n = 0
            while n < MAX_WARM_QUERIES:
                if n >= len(places) and quiet >= 2:
                    wait = self.settled_at - time.monotonic()
                    if wait <= 0:
                        break
                    time.sleep(wait)
                    quiet = 0  # a merge may have changed the parts: two more
                at = places[n] if n < len(places) else None
                rec = self.query(self.cli, traffic.spec(name, panel, ds, rng, at), "setup")
                if "error" in rec:
                    raise BenchFailure(f"warm-up query failed: {rec['error']}\n{rec['ql']}")
                self.setup.setdefault("cold_first_query_ms", rec["latency_ms"])
                now = self.programs_loaded()
                quiet = quiet + 1 if now == loaded else 0
                loaded = now
                n += 1
                say(
                    f"warm-up {name}: {rec['latency_ms']:.0f} ms "
                    f"served={rec['served']} programs={now:.0f}"
                )
        fixed = {n: p for n, p in panels.items() if traffic.repeats(p)}
        state, still, rounds = None, 0, 0
        while fixed and still < 2:
            rounds += 1
            if rounds > MAX_FILL_ROUNDS:
                raise BenchFailure(
                    f"repeating panels still scan after {MAX_FILL_ROUNDS} rounds: {state}"
                )
            served = []
            for name, panel in fixed.items():
                rec = self.query(self.cli, traffic.spec(name, panel, ds, rng), "setup")
                if "error" in rec:
                    raise BenchFailure(f"fill query failed: {rec['error']}\n{rec['ql']}")
                self.setup.setdefault("cold_first_query_ms", rec["latency_ms"])
                served.append(rec["served"])
            m = self.prom()
            now = (
                tuple(served), m.get("streamagg_signatures"), m.get("streamagg_windows"),
                m.get("compile_cache_misses"),
            )
            still = still + 1 if now == state and "scan" not in served else 0
            state = now
            say(f"fill round {rounds}: served={served} windows={m.get('streamagg_windows')}")
            if still < 2:
                time.sleep(1.0)  # the autoreg loop ticks every 2 s
        self.setup["fill_rounds"] = rounds
        self.wait_quiet()
        self.setup["warm_up_s"] = time.monotonic() - t0

    def wait_quiet(self) -> None:
        """Until the server's own background work stands still: two
        /metrics scrapes more than an autoreg tick (2 s) apart that show
        the same materialized windows and compile counts and no warm in
        flight.  A scrape queues behind a streamagg backfill, so a
        backfill in flight holds this back by itself."""
        state, since = None, time.monotonic()
        while True:
            m = self.prom()
            now = tuple(m.get(k) for k in (
                "streamagg_signatures", "streamagg_windows", "streamagg_states",
                "compile_cache_misses", "precompile_warming",
            ))
            if now != state:
                state, since = now, time.monotonic()
            elif not m.get("precompile_warming") and time.monotonic() - since >= 2.5:
                return
            self.left()
            time.sleep(0.5)

    # -- the window -----------------------------------------------------------------
    def window(self, ds: dataset.Dataset) -> dict:
        mix = self.cell["mix"]
        if mix["loop"] != "closed":
            raise BenchFailure("the generator drives closed loops only")
        seconds = float(self.args.seconds)
        before = self.prom()
        t_open = time.monotonic()
        t_close = t_open + seconds

        ends = []  # when each client's last query was done

        def client(k: int) -> None:
            cli = Client()
            try:
                for q in traffic.stream(mix, ds, self.args.seed, k):
                    if time.monotonic() >= t_close:
                        return
                    self.query(cli, q, "window")
            finally:
                ends.append(time.monotonic())
                cli.close()

        threads = [
            threading.Thread(target=client, args=(k,), name=f"client-{k}")
            for k in range(int(mix["clients"]))
        ]
        # the records kept for the oracle are millions of objects by the end of a
        # window of 1,000-group answers, and each full collection over them would
        # stop the clients for ~0.1 s, which no server made them wait (PERF.md
        # section 6, PR 27); nothing the window allocates is cyclic
        gc.disable()
        try:
            for t in threads:
                t.start()
            trace = None
            if self.args.trace:
                trace = self.trace_slice(t_open, seconds)
            for t in threads:
                t.join()
        finally:
            gc.enable()
        window_s = max(ends) - t_open  # all the work, all the time it took
        prom = {"before": before, "after": self.prom()}
        return {"window_s": window_s, "prom": prom, "trace": trace}

    def trace_slice(self, t_open: float, seconds: float) -> dict:
        """The profiler on for a slice in the middle of the window."""
        slice_s = min(TRACE_SLICE_S, seconds / 3.0)
        time.sleep(max(t_open + (seconds - slice_s) / 2.0 - time.monotonic(), 0.0))
        started = self.server.ask({"op": "trace_start", "dir": self.trace_dir})
        time.sleep(slice_s)
        stopped = self.server.ask({"op": "trace_stop"}, timeout=300.0)
        return {
            # the trace's zero lies between the call and its return
            "t0": (started["t_before"] + started["t_after"]) / 2.0,
            "t1": stopped["t_before"],
            "start_s": started["t_after"] - started["t_before"],
            "stop_s": stopped["t_after"] - stopped["t_before"],
        }

    # -- after the window --------------------------------------------------------------
    def check_answers(self, ds: dataset.Dataset) -> dict:
        """Every answered query of the run against the oracle ->
        {name: [the worst the run read, its limit]} of what was compared."""
        worst = {
            "readback_missing": self.setup["points"] - self.setup["readback_seen"],
            "unanswered": 0,
        }
        for rec in self.records:
            if "error" in rec:
                rec["ok"] = False
                worst["unanswered"] += 1
                continue
            want = ds.answer(rec["q"])
            got = rec.pop("answer")
            rec["points"] = want["points"]
            rec["wrong"] = dataset.check(rec["q"], got, want)
            rec["ok"] = rec["wrong"] is None
            for k, v in dataset.gaps(rec["q"], got, want).items():
                worst[k] = max(worst.get(k, 0), v)
        return {k: [v, dataset.LIMITS[k]] for k, v in worst.items()}

    def reduce_trace(self, trace: dict, win: list[dict]) -> dict | None:
        files = glob.glob(os.path.join(self.trace_dir, "**", "*.xplane.pb"), recursive=True)
        if not files:
            raise BenchFailure("the profiler wrote no .xplane.pb")
        events = tracereduce.load_xplane(files[0])
        if self.args.keep_trace:
            shutil.copy(files[0], self.out_path("xplane.pb"))
        if not events:
            return None  # no device plane (a CPU rehearsal)
        window_ns = max(
            int((trace["t1"] - trace["t0"]) * 1e9),
            max(ev["start_ns"] + ev["dur_ns"] for ev in events),
        )
        red = tracereduce.reduce_events(events, window_ns)
        inside = [r for r in win if trace["t0"] <= r["done"] <= trace["t1"]]
        timelines = [
            tracereduce.span_timeline(
                r["tree"], r["done"] - (r["latency_ms"] + r["root_ms"]) / 2000.0
            )
            for r in win
            if r.get("tree") and r["done"] >= trace["t0"] and r["sent"] <= trace["t1"]
        ]
        gaps = next(iter(red.pop("gaps").values()), [])
        red["idle_gaps"] = tracereduce.attribute_gaps(gaps, trace["t0"], timelines)
        red["queries_finished"] = len(inside)
        red["window_s"] = window_ns / 1e9
        return red

    def out_path(self, suffix: str) -> str:
        a = self.args
        return os.path.join(OUT_DIR, f"{self.cell['name']}-seed{a.seed}-trace{a.trace}.{suffix}")

    # -- the whole run ---------------------------------------------------------------------
    def run(self) -> dict:
        cfg = self.cell["config"]
        t0 = time.monotonic()
        self.build_native()
        self.setup["native_build_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        ds = dataset.Dataset(cfg, self.args.seed, self.rehearse)
        self.setup["dataset_s"] = time.monotonic() - t0
        rt = self.boot()
        self.create_schema()
        self.load(ds)
        self.wait_warm()
        self.warm_up(ds)
        setup_s = time.monotonic() - T_START
        say(f"setup_s = {setup_s:.1f}  {json.dumps(self.setup)}")

        w = self.window(ds)
        peak = self.server.ask({"op": "memory"})["peak_bytes"]
        self.cli.close()  # before the server goes, or grpc logs a GOAWAY
        self.server.stop()

        compared = self.check_answers(ds)
        win = [r for r in self.records if r["phase"] == "window"]
        answered = [r for r in win if "error" not in r]
        failed = [r for r in win if not r["ok"]]
        setup_wrong = [r for r in self.records if r["phase"] == "setup" and not r["ok"]]
        xplane = self.reduce_trace(w["trace"], answered) if w["trace"] else None

        lat = [r["latency_ms"] for r in answered]
        e2e = {"setup_s": setup_s}
        if lat:
            e2e["query_p50_ms"] = statistics.median(lat)
        if len(lat) >= P95_MIN_QUERIES:
            e2e["query_p95_ms"] = float(np.percentile(lat, 95))
        e2e["scanned_points_per_s"] = sum(r["points"] for r in answered if r["ok"]) / w["window_s"]
        units = {m["name"]: m["unit"] for m in self.cell["end_to_end"]}
        end_to_end = {
            k: {"value": v, "unit": units[k]} for k, v in e2e.items() if k in units
        }
        rec = {"queries": answered, "setup": self.setup, "prom": w["prom"], "xplane": xplane}
        per_layer = {}
        for m in self.cell["metric_files"]:
            v = readers.read(m, rec)
            if v is not None:
                per_layer[m["name"]] = {"value": float(v), "unit": m["unit"]}

        device = {
            "platform": str(rt["backend"]), "kind": str(rt["device_kind"]),
            "count": int(rt["device_count"]), "memory_peak_bytes": int(peak),
        }
        result = {
            "correct": bool(answered) and not failed and not setup_wrong
            and compared["readback_missing"][0] == 0,
            "attempted": len(win),
            "failed": len(failed),
            "metrics": per_layer if self.args.trace else end_to_end,
            "device": device,
        }
        if xplane:
            device["busy_s"] = xplane["busy_s"]
            device["window_s"] = xplane["window_s"]
            result["breakdown"] = {
                "device_ops": xplane["device_ops"], "idle_gaps": xplane["idle_gaps"],
            }
        result["compared"] = compared  # the last key of the line
        served: dict[str, int] = {}
        for r in answered:
            served[str(r.get("served"))] = served.get(str(r.get("served")), 0) + 1
        summary = {
            "workload": self.cell["name"], "seed": self.args.seed,
            "seconds": self.args.seconds, "trace": self.args.trace,
            "rehearsal": self.rehearse, "window_s": w["window_s"],
            "setup": self.setup, "end_to_end": end_to_end, "per_layer": per_layer,
            "served": served, "trace_slice": w["trace"],
            # compiled or taken from the persistent cache inside the window
            "programs_loaded_in_window": sum(
                w["prom"]["after"].get(k, 0.0) - w["prom"]["before"].get(k, 0.0)
                for k in ("compile_cache_misses", "compile_cache_hits")
            ),
            "wrong": [
                {"ql": r["ql"], "why": r.get("wrong") or r.get("error"), "phase": r["phase"]}
                for r in failed + setup_wrong
            ][:20],
            "result": result,
        }
        self.write_out(summary)
        say(
            f"window: {w['window_s']:.2f}s, {len(answered)} answered of {len(win)}, "
            f"{len(failed)} failed, served={served}"
        )
        for k, v in {**end_to_end, **per_layer}.items():
            say(f"  {k} = {v['value']:.6g} {v['unit']}")
        return result

    def write_out(self, summary: dict) -> None:
        os.makedirs(OUT_DIR, exist_ok=True)
        with open(self.out_path("summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
        with open(self.out_path("queries.jsonl"), "w") as f:
            for r in self.records:
                keep = {k: v for k, v in r.items() if k not in ("answer", "q")}
                f.write(json.dumps(keep) + "\n")
        with open(self.out_path("server.log"), "w") as f:
            f.write(self.server.log_tail(200_000))

    def close(self) -> None:
        self.cli.close()
        if self.server is not None:
            self.server.stop(timeout=5.0)
        shutil.rmtree(self.tmp, ignore_errors=True)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--keep-trace", action="store_true",
        help="copy the .xplane.pb of a traced run beside the summary",
    )
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    rehearse = os.environ.get("BENCH_E2E_REHEARSE", "") == "1"
    run = None
    try:
        cell = load_cell(args.workload)
        if rehearse:
            say('"rehearsal": a CPU run at a cut size; none of its numbers is a device number')
        run = Run(args, cell, rehearse)
        result = run.run()
    except (BenchFailure, subprocess.SubprocessError, OSError, KeyError) as e:
        print(f"run.py FAILED: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        if run is not None and run.server is not None:
            print("--- server log tail ---\n" + run.server.log_tail(), file=sys.stderr, flush=True)
        return 1
    finally:
        if run is not None:
            run.close()
    for k, (v, limit) in result["compared"].items():
        print(f"compared {k} = {v:.6g} (limit {limit:g})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
