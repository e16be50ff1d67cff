"""served.py - the one child of run.py, and the process that holds the chip.

    python served.py <control-dir> [server arguments...]

Runs the program's own `banyandb_tpu.server.main()` unchanged with the
arguments given (run.py passes `--root <dir>` and nothing else: default
flags).  Beside it one thread answers run.py's requests, which only the
process that holds the chip can answer: start and stop `jax.profiler`,
and the device's peak memory.  A request is a file `req-<n>.json` in the
control directory, its answer `rsp-<n>.json`; both are written under
another name and renamed, so neither side reads half a file.  This is a
benchmark file, not a server topic: the program does not know it is
being measured.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

POLL_S = 0.05


def _answer(req: dict) -> dict:
    import jax

    op = req["op"]
    if op == "trace_start":
        # device planes and XLA's own host events only: Python's call
        # tracer slows the host it measures and makes the trace tens of MB
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        before = time.time()
        jax.profiler.start_trace(req["dir"], profiler_options=options)
        return {"t_before": before, "t_after": time.time()}
    if op == "trace_stop":
        before = time.time()
        jax.profiler.stop_trace()
        return {"t_before": before, "t_after": time.time()}
    if op == "memory":
        peaks = []
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        return {"peak_bytes": max(peaks) if peaks else 0}
    raise ValueError(f"served.py: no operation {op!r}")


def _control_loop(ctl: str) -> None:
    done = 0
    while True:
        path = os.path.join(ctl, f"req-{done + 1}.json")
        if not os.path.exists(path):
            time.sleep(POLL_S)
            continue
        done += 1
        try:
            with open(path) as f:
                rsp = _answer(json.load(f))
        except Exception as e:  # noqa: BLE001 - reported to run.py, which fails the run
            rsp = {"error": f"{type(e).__name__}: {e}"}
        tmp = os.path.join(ctl, f"rsp-{done}.tmp")
        with open(tmp, "w") as f:
            json.dump(rsp, f)
        os.replace(tmp, os.path.join(ctl, f"rsp-{done}.json"))


def main(argv: list[str]) -> None:
    ctl, server_args = argv[0], argv[1:]
    threading.Thread(
        target=_control_loop, args=(ctl,), name="bench-control", daemon=True
    ).start()
    from banyandb_tpu.server import main as server_main

    server_main(server_args)


if __name__ == "__main__":
    main(sys.argv[1:])
