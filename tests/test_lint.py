"""bdlint: per-rule fixtures (positive / negative / suppressed) plus the
meta-test that the shipped tree itself is clean.

Fixtures are linted via lint_source with a virtual package-relative
path, so rule scoping (hot modules vs whole package) is exercised
without touching the filesystem.
"""

from __future__ import annotations

import json
from pathlib import Path

from banyandb_tpu.lint import lint_paths, lint_source, render_json


def _rules(src: str, rel: str = "query/x.py") -> list[str]:
    findings, _ = lint_source(src, rel=rel)
    return [f.rule for f in findings]


def _count(src: str, rule: str, rel: str = "query/x.py") -> int:
    return _rules(src, rel=rel).count(rule)


# -- host-sync ---------------------------------------------------------------


def test_host_sync_block_until_ready():
    src = "def f(x):\n    return x.block_until_ready()\n"
    assert _count(src, "host-sync") == 1
    # out of hot scope: nothing fires
    assert _count(src, "host-sync", rel="admin/x.py") == 0


def test_host_sync_device_get_flagged():
    src = "import jax\n\ndef f(x):\n    return jax.device_get(x)\n"
    assert _count(src, "host-sync") == 1


def test_host_sync_asarray_on_kernel_result():
    src = (
        "import numpy as np\n"
        "def run(kernel, chunk):\n"
        "    out = kernel(chunk)\n"
        "    return np.asarray(out['count'])\n"
    )
    assert _count(src, "host-sync") == 1


def test_host_sync_cast_on_jnp_result():
    src = (
        "import jax.numpy as jnp\n"
        "def f(a):\n"
        "    s = jnp.sum(a)\n"
        "    return float(s)\n"
    )
    assert _count(src, "host-sync") == 1


def test_host_sync_asarray_on_host_value_clean():
    src = (
        "import numpy as np\n"
        "def f(rows):\n"
        "    return np.asarray(rows, dtype=np.int64)\n"
    )
    assert _count(src, "host-sync") == 0


def test_host_sync_jitted_local_name():
    src = (
        "import jax, numpy as np\n"
        "def f(g, x):\n"
        "    run = jax.jit(g)\n"
        "    out = run(x)\n"
        "    return np.asarray(out)\n"
    )
    assert _count(src, "host-sync") == 1


def test_host_sync_clock_in_traced_fn():
    src = (
        "import jax, time\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    t = time.time()\n"
        "    return x + t\n"
    )
    assert _count(src, "host-sync") == 1


def test_host_sync_clock_in_jitted_by_name():
    # the nested build pattern: def kernel ... jax.jit(kernel)
    src = (
        "import jax, time\n"
        "def build():\n"
        "    def kernel(x):\n"
        "        return x * time.monotonic()\n"
        "    return jax.jit(kernel)\n"
    )
    assert _count(src, "host-sync") == 1


def test_host_sync_clock_outside_trace_clean():
    src = "import time\n\ndef f():\n    return time.time()\n"
    assert _count(src, "host-sync") == 0


def test_host_sync_suppressed_same_line():
    src = (
        "import jax\n"
        "def f(x):\n"
        "    return jax.device_get(x)  # bdlint: disable=host-sync -- boundary\n"
    )
    findings, suppressed = lint_source(src, rel="query/x.py")
    assert [f.rule for f in findings] == []
    assert suppressed == 1


def test_host_sync_suppressed_previous_comment_line():
    src = (
        "import jax\n"
        "def f(x):\n"
        "    # bdlint: disable=host-sync -- result boundary, reason here\n"
        "    return jax.device_get(x)\n"
    )
    findings, suppressed = lint_source(src, rel="query/x.py")
    assert not findings
    assert suppressed == 1


# -- recompile-hazard --------------------------------------------------------


def test_recompile_jit_of_lambda():
    src = "import jax\nf = jax.jit(lambda x: x + 1)\n"
    assert _count(src, "recompile-hazard") == 1


def test_recompile_jit_immediately_called():
    src = "import jax\n\ndef f(g, x):\n    return jax.jit(g)(x)\n"
    assert _count(src, "recompile-hazard") == 1


def test_recompile_jit_in_loop():
    src = (
        "import jax\n"
        "def f(fns, x):\n"
        "    outs = []\n"
        "    for g in fns:\n"
        "        h = jax.jit(g)\n"
        "        outs.append(h)\n"
        "    return outs\n"
    )
    assert _count(src, "recompile-hazard") == 1


def test_recompile_cached_build_pattern_clean():
    # the blessed measure_exec pattern: build once per plan spec
    src = (
        "import jax\n"
        "def build(spec):\n"
        "    def kernel(c):\n"
        "        return c\n"
        "    return jax.jit(kernel)\n"
    )
    assert _count(src, "recompile-hazard") == 0


def test_recompile_fstring_over_traced_arg():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    name = f'value {x}'\n"
        "    return x\n"
    )
    assert _count(src, "recompile-hazard") == 1


def test_recompile_fstring_over_closure_var_clean():
    src = (
        "import jax\n"
        "def build(i):\n"
        "    @jax.jit\n"
        "    def f(x):\n"
        "        return x + len(f'p{i}')\n"
        "    return f\n"
    )
    assert _count(src, "recompile-hazard") == 0


# -- precision-drift ---------------------------------------------------------


def test_precision_dtypeless_zeros():
    src = "import numpy as np\nbuf = np.zeros(4)\n"
    assert _count(src, "precision-drift", rel="ops/x.py") == 1
    # cluster code is out of scope for the kernel-path rule
    assert _count(src, "precision-drift", rel="cluster/x.py") == 0


def test_precision_explicit_dtype_clean():
    src = (
        "import numpy as np\n"
        "a = np.zeros(4, dtype=np.float64)\n"
        "b = np.zeros(4, np.int32)\n"
        "c = np.full(3, np.inf, dtype=np.float64)\n"
    )
    assert _count(src, "precision-drift", rel="ops/x.py") == 0


def test_precision_dtypeless_full_and_arange():
    src = "import numpy as np\na = np.full(3, 0.0)\nb = np.arange(7)\n"
    assert _count(src, "precision-drift", rel="ops/x.py") == 2


# -- rpc-timeout -------------------------------------------------------------


def test_rpc_timeout_transport_call():
    src = (
        "class C:\n"
        "    def f(self, addr, env):\n"
        "        return self.transport.call(addr, 'topic', env)\n"
    )
    assert _count(src, "rpc-timeout", rel="cluster/x.py") == 1


def test_rpc_timeout_with_timeout_clean():
    src = (
        "class C:\n"
        "    def f(self, addr, env):\n"
        "        return self.transport.call(addr, 'topic', env, timeout=5)\n"
    )
    assert _count(src, "rpc-timeout", rel="cluster/x.py") == 0


def test_rpc_timeout_urlopen():
    src = (
        "import urllib.request\n"
        "def fetch(req):\n"
        "    return urllib.request.urlopen(req).read()\n"
    )
    assert _count(src, "rpc-timeout", rel="utils/x.py") == 1


def test_rpc_timeout_non_transport_call_clean():
    src = (
        "class C:\n"
        "    def f(self, cb):\n"
        "        return self.registry.call(cb)\n"
    )
    assert _count(src, "rpc-timeout", rel="cluster/x.py") == 0


# -- lock-across-rpc ---------------------------------------------------------


def test_lock_across_rpc_flagged():
    src = (
        "class C:\n"
        "    def f(self, addr, env):\n"
        "        with self._lock:\n"
        "            return self.transport.call(addr, 't', env, timeout=5)\n"
    )
    assert _count(src, "lock-across-rpc", rel="cluster/x.py") == 1


def test_lock_then_call_outside_clean():
    src = (
        "class C:\n"
        "    def f(self, addr, env):\n"
        "        with self._lock:\n"
        "            target = self.nodes[addr]\n"
        "        return self.transport.call(target, 't', env, timeout=5)\n"
    )
    assert _count(src, "lock-across-rpc", rel="cluster/x.py") == 0


def test_lock_across_sleep_flagged():
    src = (
        "import time\n"
        "class C:\n"
        "    def f(self):\n"
        "        with self.lock:\n"
        "            time.sleep(1)\n"
    )
    assert _count(src, "lock-across-rpc", rel="storage/x.py") == 1


# -- retry-backoff -----------------------------------------------------------


def test_retry_without_backoff_flagged():
    src = (
        "def f(rpc):\n"
        "    while True:\n"
        "        try:\n"
        "            return rpc()\n"
        "        except Exception:\n"
        "            continue\n"
    )
    assert _count(src, "retry-backoff", rel="cluster/x.py") == 1


def test_retry_with_sleep_clean():
    src = (
        "import time\n"
        "def f(rpc):\n"
        "    while True:\n"
        "        try:\n"
        "            return rpc()\n"
        "        except Exception:\n"
        "            time.sleep(0.5)\n"
    )
    assert _count(src, "retry-backoff", rel="cluster/x.py") == 0


def test_retry_paced_by_bounded_get_clean():
    src = (
        "import queue\n"
        "def f(q):\n"
        "    while True:\n"
        "        try:\n"
        "            return q.get(timeout=0.2)\n"
        "        except queue.Empty:\n"
        "            continue\n"
    )
    assert _count(src, "retry-backoff", rel="cluster/x.py") == 0


def test_retry_rpc_own_timeout_is_not_backoff():
    # the rpc-timeout rule mandates timeout= on transport calls; that
    # timeout must NOT count as pacing — connection-refused returns in
    # microseconds and the loop still hammers the peer
    src = (
        "class C:\n"
        "    def f(self, addr, env):\n"
        "        while True:\n"
        "            try:\n"
        "                return self.transport.call(addr, 't', env, timeout=5)\n"
        "            except Exception:\n"
        "                pass\n"
    )
    assert _count(src, "retry-backoff", rel="cluster/x.py") == 1


def test_retry_break_on_error_clean():
    src = (
        "def f(q):\n"
        "    while True:\n"
        "        try:\n"
        "            q.pop()\n"
        "        except IndexError:\n"
        "            break\n"
    )
    assert _count(src, "retry-backoff", rel="storage/x.py") == 0


# -- resource-hygiene --------------------------------------------------------


def test_open_outside_with_flagged():
    src = "def f(p):\n    fh = open(p)\n    return fh.read()\n"
    assert _count(src, "resource-hygiene", rel="storage/x.py") == 1


def test_open_in_with_clean():
    src = "def f(p):\n    with open(p) as fh:\n        return fh.read()\n"
    assert _count(src, "resource-hygiene", rel="storage/x.py") == 0


def test_open_suppressed_with_reason():
    src = (
        "def f(p):\n"
        "    # bdlint: disable=resource-hygiene -- cache, closed by owner\n"
        "    fh = open(p)\n"
        "    return fh\n"
    )
    findings, suppressed = lint_source(src, rel="storage/x.py")
    assert not findings
    assert suppressed == 1


# -- engine behaviors --------------------------------------------------------


def test_suppression_survives_blank_line_after_comment():
    # a reflow that inserts a blank line between the suppression comment
    # and its code line must not silently detach the suppression
    src = (
        "import jax\n"
        "def f(x):\n"
        "    # bdlint: disable=host-sync -- boundary, documented\n"
        "\n"
        "    return jax.device_get(x)\n"
    )
    findings, suppressed = lint_source(src, rel="query/x.py")
    assert not findings
    assert suppressed == 1


def test_disable_file_suppresses_everywhere():
    src = (
        "# bdlint: disable-file=resource-hygiene\n"
        "a = open('x')\n"
        "b = open('y')\n"
    )
    findings, suppressed = lint_source(src, rel="storage/x.py")
    assert not [f for f in findings if f.rule == "resource-hygiene"]
    assert suppressed == 2


def test_unsuppressed_rule_still_fires():
    src = (
        "import jax\n"
        "def f(x):\n"
        "    return jax.device_get(x)  # bdlint: disable=precision-drift\n"
    )
    findings, _ = lint_source(src, rel="query/x.py")
    assert [f.rule for f in findings] == ["host-sync"]


def test_findings_sorted_and_sarif_2_1_0():
    src = (
        "import numpy as np\n"
        "a = np.zeros(3)\n"
        "b = open('x')\n"
        "c = np.ones(3)\n"
    )
    findings, _ = lint_source(src, rel="query/x.py")
    assert findings == sorted(findings)
    summary = {"files": 1, "findings": len(findings), "suppressed": 0}
    doc = json.loads(render_json(findings, summary))
    # real SARIF 2.1.0: code-scanning UIs and editors ingest this shape
    assert doc["version"] == "2.1.0" and doc["$schema"].endswith(
        "sarif-schema-2.1.0.json"
    )
    (run,) = doc["runs"]
    driver = run["tool"]["driver"]
    assert driver["name"] == "bdlint"
    rule_ids = [r["id"] for r in driver["rules"]]
    assert "host-sync" in rule_ids and "layering" in rule_ids
    assert all(r["shortDescription"]["text"] for r in driver["rules"])
    assert [r["ruleId"] for r in run["results"]] == [f.rule for f in findings]
    for res, f in zip(run["results"], findings):
        # every result carries a physical location; ruleIndex round-trips
        # into the driver rule table; columns are SARIF 1-based
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == f.path
        assert loc["region"]["startLine"] == f.line
        assert loc["region"]["startColumn"] == f.col + 1
        assert driver["rules"][res["ruleIndex"]]["id"] == f.rule
    assert run["properties"] == summary
    # serialization is deterministic (stable CI diffing)
    assert render_json(findings, summary) == render_json(findings, summary)


def test_cli_check_mode_and_rule_filter(tmp_path):
    from banyandb_tpu.lint.__main__ import main

    bad = tmp_path / "banyandb_tpu" / "query"
    bad.mkdir(parents=True)
    (bad / "x.py").write_text("import numpy as np\na = np.zeros(3)\n")
    assert main(["--check", str(bad)]) == 1
    # without --check the run is report-only: findings print, exit 0
    assert main([str(bad)]) == 0
    assert main(["--check", "--rules", "host-sync", str(bad)]) == 0
    assert main(["--rules", "nope", str(bad)]) == 2
    assert main(["--list-rules"]) == 0


def test_generated_pb_trees_skipped(tmp_path):
    pb = tmp_path / "banyandb_tpu" / "api" / "pb"
    pb.mkdir(parents=True)
    (pb / "x_pb2.py").write_text("a = open('x')\n")
    findings, stats = lint_paths([str(tmp_path)])
    assert not findings
    assert stats["files"] == 0


# -- the meta-test: the shipped tree is clean --------------------------------


def test_tree_is_bdlint_clean():
    import banyandb_tpu

    pkg = Path(banyandb_tpu.__file__).parent
    findings, stats = lint_paths([str(pkg)])
    assert findings == [], "\n".join(f.render() for f in findings)
    # every suppression in the tree is a documented decision; pin the
    # exact count so adding (or dropping) one forces a reviewed edit here
    # 11 = 7 pre-fused + the fused executor's single batched device_get
    # result boundary (query/fused_exec.run_fused) + the worker pool's
    # two lifetime handles (per-worker log file + the worker's parent
    # socket, both closed by their owners' teardown paths) + the
    # exhaustive read-failover walk (cluster/liaison._scatter): every
    # round dials a DIFFERENT replica, so inter-round backoff would
    # only burn the query's deadline budget + the percentile ranks'
    # settling (measure_exec._settle_ranks): the histogram rows of the
    # rare groups whose f32 device rank missed the host's f64 one
    assert stats["suppressed"] == 12
    assert stats["files"] > 90
