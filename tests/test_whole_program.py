"""Whole-program analyses: seeded-violation proofs for every analyzer
(upward import, transitive host-sync in jit, lock-order cycle,
dtype-promoting plan), the layer-map golden test, and the audited-tree
meta-tests.

Seeded packages are written to tmp_path and analyzed with a purpose-built
LayerConfig / Program, so detection is proven without touching the real
tree; the meta-tests then pin the real tree to zero findings.
"""

from __future__ import annotations

from pathlib import Path

import pytest

from banyandb_tpu.lint.whole_program import apply_suppressions, layer_config
from banyandb_tpu.lint.whole_program.callgraph import (
    Program,
    analyze_lock_blocking,
    analyze_sync_in_jit,
)
from banyandb_tpu.lint.whole_program.layers import (
    LayerConfig,
    analyze_layers,
    iter_py_modules,
)
from banyandb_tpu.lint.whole_program.lockorder import analyze_lock_order
from banyandb_tpu.lint.whole_program.plan_audit import KernelAudit, audit_kernel
from banyandb_tpu.lint.whole_program.shared_state import (
    analyze_shared_state,
    collect_accesses,
    discover_roots,
)


def _pkg(tmp_path: Path, files: dict[str, str], name: str = "mypkg") -> Path:
    root = tmp_path / name
    root.mkdir(parents=True, exist_ok=True)
    (root / "__init__.py").write_text("")
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        if p.name != "__init__.py" and not (p.parent / "__init__.py").exists():
            (p.parent / "__init__.py").write_text("")
        p.write_text(src)
    return root


_TWO_LAYERS = LayerConfig(
    layers=("low", "high"),
    may_import={"low": (), "high": ("low",)},
    layer_of={"": "low", "lo": "low", "hi": "high"},
)


# -- layering ----------------------------------------------------------------


def test_layering_upward_import_flagged(tmp_path):
    root = _pkg(
        tmp_path,
        {
            "lo/a.py": "from mypkg.hi.b import f\n",
            "hi/b.py": "def f():\n    return 1\n",
        },
    )
    fs = analyze_layers(root, "mypkg", _TWO_LAYERS)
    assert len(fs) == 1 and fs[0].rule == "layering"
    assert "upward import" in fs[0].message
    assert fs[0].path.endswith("lo/a.py") and fs[0].line == 1


def test_layering_downward_import_clean(tmp_path):
    root = _pkg(
        tmp_path,
        {
            "hi/b.py": "from mypkg.lo.a import g\n",
            "lo/a.py": "def g():\n    return 1\n",
        },
    )
    assert analyze_layers(root, "mypkg", _TWO_LAYERS) == []


def test_layering_skip_layer_flagged(tmp_path):
    cfg = LayerConfig(
        layers=("l0", "l1", "l2"),
        # l2 may only reach l1 — touching l0 directly is a skip
        may_import={"l0": (), "l1": ("l0",), "l2": ("l1",)},
        layer_of={"": "l0", "base": "l0", "mid": "l1", "top": "l2"},
    )
    root = _pkg(
        tmp_path,
        {
            "base/a.py": "X = 1\n",
            "top/c.py": "from mypkg.base import a\n",
        },
    )
    fs = analyze_layers(root, "mypkg", cfg)
    assert len(fs) == 1 and "skip-layer" in fs[0].message


def test_layering_lazy_and_type_checking_imports_exempt(tmp_path):
    root = _pkg(
        tmp_path,
        {
            "lo/a.py": (
                "from typing import TYPE_CHECKING\n"
                "if TYPE_CHECKING:\n"
                "    from mypkg.hi.b import f\n"
                "def g():\n"
                "    from mypkg.hi.b import f\n"
                "    return f()\n"
            ),
            "hi/b.py": "def f():\n    return 1\n",
        },
    )
    assert analyze_layers(root, "mypkg", _TWO_LAYERS) == []


def test_layering_unknown_module_is_failure(tmp_path):
    root = _pkg(tmp_path, {"elsewhere/x.py": "X = 1\n"})
    fs = analyze_layers(root, "mypkg", _TWO_LAYERS)
    assert [f for f in fs if "maps to no layer" in f.message]


def test_layering_ratchet_baseline(tmp_path):
    files = {
        "lo/a.py": "from mypkg.hi.b import f\n",
        "hi/b.py": "def f():\n    return 1\n",
    }
    root = _pkg(tmp_path, files)
    edge = frozenset({"mypkg.lo.a -> mypkg.hi.b"})
    # baselined live violation: tolerated
    assert analyze_layers(root, "mypkg", _TWO_LAYERS, baseline=edge) == []
    # fixed violation with a lingering entry: stale-baseline failure
    (root / "lo" / "a.py").write_text("A = 1\n")
    fs = analyze_layers(root, "mypkg", _TWO_LAYERS, baseline=edge)
    assert len(fs) == 1 and "stale baseline" in fs[0].message


def test_real_layer_map_is_total_and_unambiguous():
    """The golden test: every module of the real package maps to exactly
    one layer (unknown modules are gate failures by construction)."""
    import banyandb_tpu

    pkg = Path(banyandb_tpu.__file__).parent
    cfg = layer_config.CONFIG
    for mod, _path in iter_py_modules(pkg, "banyandb_tpu"):
        rel = mod[len("banyandb_tpu") + 1 :] if mod != "banyandb_tpu" else ""
        layer = cfg.module_layer(rel)
        assert layer is not None, f"{mod} maps to no layer; extend layer_config"
        assert layer in cfg.layers, f"{mod} -> {layer} is not a known layer"


def test_real_tree_layering_clean():
    import banyandb_tpu

    pkg = Path(banyandb_tpu.__file__).parent
    fs = analyze_layers(
        pkg, "banyandb_tpu", layer_config.CONFIG, layer_config.BASELINE
    )
    assert fs == [], "\n".join(f.render() for f in fs)


def test_baseline_entries_all_still_live():
    """The ratchet's other half, stated positively: every baselined edge
    still exists (stale entries would have failed the clean-tree test)."""
    import banyandb_tpu

    pkg = Path(banyandb_tpu.__file__).parent
    from banyandb_tpu.lint.whole_program.layers import scan_import_edges

    edges, _ = scan_import_edges(pkg, "banyandb_tpu")
    live = {f"{e.src} -> {e.dst}" for e in edges}
    assert layer_config.BASELINE <= live


# -- call-graph facts --------------------------------------------------------


def test_transitive_host_sync_in_jit_flagged(tmp_path):
    root = _pkg(
        tmp_path,
        {
            "a.py": (
                "import jax\n"
                "from mypkg.b import helper\n"
                "@jax.jit\n"
                "def k(x):\n"
                "    return helper(x)\n"
            ),
            "b.py": (
                "import jax\n"
                "from mypkg.c import deep\n"
                "def helper(x):\n"
                "    return deep(x)\n"
            ),
            "c.py": (
                "import jax\n"
                "def deep(x):\n"
                "    return jax.device_get(x)\n"
            ),
        },
    )
    program = Program.build(root, "mypkg")
    fs = analyze_sync_in_jit(program)
    assert len(fs) == 1 and fs[0].rule == "wp-sync-in-jit"
    assert fs[0].path.endswith("a.py") and fs[0].line == 5
    # the witness chain names the whole path to the base API
    assert "helper" in fs[0].message and "deep" in fs[0].message
    assert "jax.device_get" in fs[0].message


def test_blocking_call_in_jit_flagged(tmp_path):
    root = _pkg(
        tmp_path,
        {
            "a.py": (
                "import jax\n"
                "from mypkg.b import probe\n"
                "@jax.jit\n"
                "def k(x):\n"
                "    probe()\n"
                "    return x\n"
            ),
            "b.py": (
                "import time\n"
                "def probe():\n"
                "    time.sleep(1)\n"
            ),
        },
    )
    fs = analyze_sync_in_jit(Program.build(root, "mypkg"))
    assert len(fs) == 1 and "transitively blocks" in fs[0].message


def test_direct_sync_in_jit_not_duplicated(tmp_path):
    # depth-0 is the per-file host-sync rule's finding, not ours
    root = _pkg(
        tmp_path,
        {
            "a.py": (
                "import jax\n"
                "@jax.jit\n"
                "def k(x):\n"
                "    return jax.device_get(x)\n"
            ),
        },
    )
    assert analyze_sync_in_jit(Program.build(root, "mypkg")) == []


def test_nested_kernel_builder_traced(tmp_path):
    # the measure_exec pattern: nested kernel passed to jax.jit by name
    root = _pkg(
        tmp_path,
        {
            "a.py": (
                "import jax\n"
                "from mypkg.b import leak\n"
                "def build(spec):\n"
                "    def kernel(c):\n"
                "        return leak(c)\n"
                "    return jax.jit(kernel)\n"
            ),
            "b.py": (
                "import jax\n"
                "def leak(c):\n"
                "    return jax.device_get(c)\n"
            ),
        },
    )
    fs = analyze_sync_in_jit(Program.build(root, "mypkg"))
    assert len(fs) == 1 and fs[0].path.endswith("a.py")


def test_own_nested_helper_resolved(tmp_path):
    # a function calling its OWN nested def resolves ("outer.h", not a
    # non-existent module-level "h"), so facts propagate through the
    # common build-a-closure-and-use-it pattern
    root = _pkg(
        tmp_path,
        {
            "a.py": (
                "import jax\n"
                "from mypkg.b import outer\n"
                "@jax.jit\n"
                "def k(x):\n"
                "    return outer(x)\n"
            ),
            "b.py": (
                "import jax\n"
                "def outer(x):\n"
                "    def h(y):\n"
                "        return jax.device_get(y)\n"
                "    return h(x)\n"
            ),
        },
    )
    fs = analyze_sync_in_jit(Program.build(root, "mypkg"))
    assert len(fs) == 1 and "outer" in fs[0].message
    assert "jax.device_get" in fs[0].message


def test_lock_blocking_across_files_flagged(tmp_path):
    root = _pkg(
        tmp_path,
        {
            "a.py": (
                "from mypkg.b import push\n"
                "class S:\n"
                "    def send(self, env):\n"
                "        with self._lock:\n"
                "            return push(env)\n"
            ),
            "b.py": (
                "def push(env):\n"
                "    return env.transport.call('n1', 'topic', env, timeout=5)\n"
            ),
        },
    )
    fs = analyze_lock_blocking(Program.build(root, "mypkg"))
    assert len(fs) == 1 and fs[0].rule == "wp-lock-blocking"
    assert "S._lock" in fs[0].message and "transport.call" in fs[0].message


def test_lock_blocking_direct_call_not_duplicated(tmp_path):
    # a DIRECT blocking call under the lock is lock-across-rpc's finding
    root = _pkg(
        tmp_path,
        {
            "a.py": (
                "import time\n"
                "class S:\n"
                "    def send(self):\n"
                "        with self._lock:\n"
                "            time.sleep(1)\n"
            ),
        },
    )
    assert analyze_lock_blocking(Program.build(root, "mypkg")) == []


# -- lock-order cycles -------------------------------------------------------


def test_lock_order_cycle_flagged(tmp_path):
    root = _pkg(
        tmp_path,
        {
            "m.py": (
                "import threading\n"
                "ingest_lock = threading.Lock()\n"
                "flush_lock = threading.Lock()\n"
                "def fwd():\n"
                "    with ingest_lock:\n"
                "        with flush_lock:\n"
                "            pass\n"
                "def rev():\n"
                "    with flush_lock:\n"
                "        with ingest_lock:\n"
                "            pass\n"
            ),
        },
    )
    fs = analyze_lock_order(Program.build(root, "mypkg"))
    assert len(fs) == 1 and fs[0].rule == "lock-order"
    assert "potential deadlock cycle" in fs[0].message
    assert "ingest_lock" in fs[0].message and "flush_lock" in fs[0].message


def test_lock_order_cycle_through_call_chain(tmp_path):
    root = _pkg(
        tmp_path,
        {
            "a.py": (
                "import threading\n"
                "from mypkg.b import grab_b\n"
                "a_lock = threading.Lock()\n"
                "def fwd():\n"
                "    with a_lock:\n"
                "        grab_b()\n"
            ),
            "b.py": (
                "import threading\n"
                "import mypkg.a\n"
                "b_lock = threading.Lock()\n"
                "def grab_b():\n"
                "    with b_lock:\n"
                "        pass\n"
                "def rev():\n"
                "    with b_lock:\n"
                "        with mypkg.a.a_lock:\n"
                "            pass\n"
            ),
        },
    )
    fs = analyze_lock_order(Program.build(root, "mypkg"))
    assert len(fs) == 1 and "via grab_b" in fs[0].message


def test_lock_order_self_reacquire_flagged_for_plain_lock(tmp_path):
    root = _pkg(
        tmp_path,
        {
            "a.py": (
                "import threading\n"
                "class S:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n"
                "    def outer(self):\n"
                "        with self._lock:\n"
                "            self.inner()\n"
                "    def inner(self):\n"
                "        with self._lock:\n"
                "            pass\n"
            ),
        },
    )
    fs = analyze_lock_order(Program.build(root, "mypkg"))
    assert len(fs) == 1 and "acquired while already held" in fs[0].message


def test_lock_order_rlock_self_reacquire_exempt(tmp_path):
    root = _pkg(
        tmp_path,
        {
            "a.py": (
                "import threading\n"
                "class S:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.RLock()\n"
                "    def outer(self):\n"
                "        with self._lock:\n"
                "            self.inner()\n"
                "    def inner(self):\n"
                "        with self._lock:\n"
                "            pass\n"
            ),
        },
    )
    assert analyze_lock_order(Program.build(root, "mypkg")) == []


def test_real_tree_callgraph_analyses_clean():
    import banyandb_tpu

    pkg = Path(banyandb_tpu.__file__).parent
    program = Program.build(pkg, "banyandb_tpu")
    # the audit found real jit entry points — the analyses are not vacuous
    assert sum(1 for i in program.functions.values() if i.traced) >= 4
    assert sum(1 for i in program.functions.values() if i.block) >= 10
    fs = (
        analyze_sync_in_jit(program)
        + analyze_lock_blocking(program)
        + analyze_lock_order(program)
    )
    fs, _suppressed = apply_suppressions(fs)
    assert fs == [], "\n".join(f.render() for f in fs)


# -- shared-state race analysis ----------------------------------------------


_RACY_PKG = {
    "svc.py": (
        "import threading\n"
        "class Svc:\n"
        "    def __init__(self):\n"
        "        self.count = 0\n"
        "        self._lock = threading.Lock()\n"
        "    def on_write(self, env):\n"  # bus subscriber root
        "        self.count += 1\n"
        "        return {}\n"
        "    def _loop(self):\n"
        "        self.count = 0\n"
        "    def start(self, bus):\n"
        "        bus.subscribe('write', self.on_write)\n"
        "        threading.Thread(target=self._loop, name='svc-loop').start()\n"
    ),
}


def test_shared_state_unguarded_two_root_write_flagged(tmp_path):
    program = Program.build(_pkg(tmp_path, _RACY_PKG), "mypkg")
    roots = {r.qual for r in discover_roots(program)}
    assert "mypkg.svc:Svc.on_write" in roots  # subscriber
    assert "mypkg.svc:Svc._loop" in roots  # thread target
    fs = analyze_shared_state(program)
    assert len(fs) == 1 and fs[0].rule == "wp-shared-state"
    assert "mypkg.svc.Svc.count" in fs[0].message
    # witness chains name both roots
    assert "svc-loop" in fs[0].message and "subscriber" in fs[0].message


def test_shared_state_common_guard_is_clean(tmp_path):
    files = {
        "svc.py": _RACY_PKG["svc.py"]
        .replace(
            "        self.count += 1\n",
            "        with self._lock:\n            self.count += 1\n",
        )
        .replace(
            "        self.count = 0\n    def start",
            "        with self._lock:\n            self.count = 0\n    def start",
        )
    }
    program = Program.build(_pkg(tmp_path, files), "mypkg")
    assert analyze_shared_state(program) == []


def test_shared_state_single_root_write_is_clean(tmp_path):
    files = {
        "svc.py": (
            "import threading\n"
            "class Svc:\n"
            "    def _loop(self):\n"
            "        self.count = 0\n"  # only ONE root ever writes
            "    def on_read(self, env):\n"
            "        return {'n': self.count}\n"
            "    def start(self, bus):\n"
            "        bus.subscribe('read', self.on_read)\n"
            "        threading.Thread(target=self._loop).start()\n"
        ),
    }
    program = Program.build(_pkg(tmp_path, files), "mypkg")
    assert analyze_shared_state(program) == []


def test_shared_state_interprocedural_guard_via_must_hold(tmp_path):
    # the lock is taken by the CALLER; the helper that writes inherits it
    # through must-hold propagation across both roots
    files = {
        "svc.py": (
            "import threading\n"
            "class Svc:\n"
            "    def _bump(self):\n"
            "        self.count += 1\n"
            "    def on_write(self, env):\n"
            "        with self._lock:\n"
            "            self._bump()\n"
            "    def _loop(self):\n"
            "        with self._lock:\n"
            "            self._bump()\n"
            "    def start(self, bus):\n"
            "        bus.subscribe('write', self.on_write)\n"
            "        threading.Thread(target=self._loop).start()\n"
        ),
    }
    program = Program.build(_pkg(tmp_path, files), "mypkg")
    assert analyze_shared_state(program) == []


def test_shared_state_constructor_writes_exempt(tmp_path):
    # __init__ (and helpers only reachable through it) are pre-publication
    files = {
        "svc.py": (
            "import threading\n"
            "class Svc:\n"
            "    def __init__(self):\n"
            "        self._setup()\n"
            "    def _setup(self):\n"
            "        self.count = 0\n"
            "    def on_a(self, env):\n"
            "        s = Svc()\n"
            "        return {}\n"
            "    def on_b(self, env):\n"
            "        s = Svc()\n"
            "        return {}\n"
            "    def start(self, bus):\n"
            "        bus.subscribe('a', self.on_a)\n"
            "        bus.subscribe('b', self.on_b)\n"
        ),
    }
    program = Program.build(_pkg(tmp_path, files), "mypkg")
    assert analyze_shared_state(program) == []


def test_shared_state_sync_primitives_exempt(tmp_path):
    files = {
        "svc.py": (
            "import threading, queue\n"
            "class Svc:\n"
            "    def __init__(self):\n"
            "        self._stop = threading.Event()\n"
            "        self._q = queue.Queue()\n"
            "    def on_write(self, env):\n"
            "        self._q.put(env)\n"
            "        return {}\n"
            "    def _loop(self):\n"
            "        self._q.put(None)\n"
            "        self._stop.set()\n"
            "    def start(self, bus):\n"
            "        bus.subscribe('write', self.on_write)\n"
            "        threading.Thread(target=self._loop).start()\n"
        ),
    }
    program = Program.build(_pkg(tmp_path, files), "mypkg")
    assert analyze_shared_state(program) == []


def test_shared_state_mutator_calls_count_as_writes(tmp_path):
    files = {
        "svc.py": (
            "import threading\n"
            "class Svc:\n"
            "    def on_write(self, env):\n"
            "        self.items.append(env)\n"
            "        return {}\n"
            "    def _loop(self):\n"
            "        self.items.clear()\n"
            "    def start(self, bus):\n"
            "        bus.subscribe('write', self.on_write)\n"
            "        threading.Thread(target=self._loop).start()\n"
        ),
    }
    program = Program.build(_pkg(tmp_path, files), "mypkg")
    fs = analyze_shared_state(program)
    assert len(fs) == 1 and "Svc.items" in fs[0].message
    accesses = [
        a for a in collect_accesses(program) if a.attr.endswith("items")
    ]
    assert all(a.write for a in accesses)


def test_shared_state_baseline_ratchet(tmp_path):
    program = Program.build(_pkg(tmp_path, _RACY_PKG), "mypkg")
    live = frozenset({"mypkg.svc.Svc.count"})
    # baselined live race: tolerated
    assert analyze_shared_state(program, baseline=live) == []
    # stale entry: fails so the set only shrinks
    fs = analyze_shared_state(
        program,
        baseline=live | {"mypkg.svc.Svc.gone"},
        baseline_path="<bl>",
    )
    assert len(fs) == 1 and "stale baseline" in fs[0].message


def test_shared_state_worker_process_entries_are_roots():
    """The multi-process data plane's worker entries run as the MAIN
    thread of a spawned subprocess — exec boundaries are invisible to
    registration discovery, so shared_state declares them as process
    roots (cluster/workers.py)."""
    import banyandb_tpu
    from pathlib import Path as _P

    program = Program.build(
        _P(banyandb_tpu.__file__).parent, "banyandb_tpu"
    )
    kinds = {r.qual: r.kind for r in discover_roots(program)}
    assert kinds.get("banyandb_tpu.cluster.workers:worker_main") == "process"
    assert (
        kinds.get("banyandb_tpu.cluster.workers:_WorkerServer.serve")
        == "process"
    )


def test_shared_state_grpc_servicer_and_timer_roots(tmp_path):
    files = {
        "api.py": (
            "import threading\n"
            "class WireServices:\n"
            "    def measure_write(self, req):\n"
            "        self.total += 1\n"
            "        return req\n"
            "class Saver:\n"
            "    def _fire(self):\n"
            "        self.total = 0\n"
            "    def schedule(self):\n"
            "        threading.Timer(1.0, self._fire).start()\n"
        ),
    }
    program = Program.build(_pkg(tmp_path, files), "mypkg")
    kinds = {r.qual: r.kind for r in discover_roots(program)}
    assert kinds.get("mypkg.api:WireServices.measure_write") == "grpc"
    assert kinds.get("mypkg.api:Saver._fire") == "timer"


def test_real_tree_shared_state_clean_with_pinned_suppressions():
    """The audited-tree meta-test: zero findings, and the suppression
    population is a pinned, reviewed number — adding or dropping one
    forces an edit here (same contract as test_tree_is_bdlint_clean)."""
    import banyandb_tpu
    from banyandb_tpu.lint.whole_program import run_whole_program

    pkg = Path(banyandb_tpu.__file__).parent
    findings, stats = run_whole_program(pkg, plan_audit=False)
    assert findings == [], "\n".join(f.render() for f in findings)
    # 8 wp-shared-state suppressions: bydbql._Parser (per-call instance),
    # StreamEngine.last_scan_stats (atomic diagnostic rebind),
    # Bloom.bits (function-local during part build),
    # obs.tracer.Span.t1 (a Span belongs to ONE query's tracer; many
    # roots run queries but no two roots share a Span instance) and,
    # for the same reason, Span.tags where finish() sets off_cpu_ms /
    # minflt / tid (ISSUE 37),
    # WorkerPool._jbytes/_journal (every write holds the per-worker
    # self._jlocks[widx] — a lock in a LIST, outside the analyzer's
    # attribute-lock model),
    # _WorkerServer.applied_seq (ORDERED_TOPICS routes every ordered
    # envelope to the single writer thread, so the field is
    # single-writer and read on that same thread by the flush handler)
    assert stats["wp_suppressed"] == 8
    # root discovery is not vacuous: threads, subscribers, grpc methods
    assert stats["wp_roots"] >= 60


# -- plan auditor ------------------------------------------------------------


def _entry(fn, expect, cache_key=None, args=None):
    import jax
    import jax.numpy as jnp

    if args is None:
        args = (jax.ShapeDtypeStruct((64,), jnp.int32),)
    return KernelAudit(
        name="seeded",
        path="query/x.py",
        line=1,
        fn=fn,
        args=args,
        expect=expect,
        cache_key=cache_key,
    )


def test_plan_audit_dtype_promotion_flagged():
    # an int32 key column silently promoted to float: the contract table
    # pins int32, the audit reports the drift
    fs = audit_kernel(
        _entry(lambda x: x + 0.5, {"<out>": ("int32", (64,))})
    )
    assert len(fs) == 1 and fs[0].rule == "plan-audit"
    assert "float32" in fs[0].message and "int32" in fs[0].message


def test_plan_audit_64bit_output_flagged():
    import jax

    import jax.numpy as jnp

    with jax.enable_x64(True):
        fs = audit_kernel(
            _entry(
                lambda x: x.astype(jnp.float64),
                None,
                args=(jax.ShapeDtypeStruct((64,), jnp.float32),),
            )
        )
    assert len(fs) == 1 and "float64" in fs[0].message


def test_plan_audit_shape_mismatch_flagged():
    import jax.numpy as jnp

    fs = audit_kernel(
        # reduces away the row axis while the contract expects [64]
        _entry(lambda x: jnp.sum(x), {"<out>": ("int32", (64,))})
    )
    assert len(fs) == 1 and "shape=()" in fs[0].message


def test_plan_audit_trace_failure_flagged():
    import jax.numpy as jnp

    fs = audit_kernel(
        _entry(lambda x: x + jnp.zeros((3, 5)), {"<out>": ("int32", (64,))})
    )
    assert len(fs) == 1 and "abstract trace failed" in fs[0].message


def test_plan_audit_retrace_hazard_mutable_cache_key():
    import numpy as np

    fs = audit_kernel(
        _entry(
            lambda x: x,
            {"<out>": ("int32", (64,))},
            cache_key=("plan", np.zeros(3)),
        )
    )
    assert any("not deeply immutable" in f.message for f in fs)


def test_plan_audit_retrace_hazard_identity_hash_key():
    class IdentityKey:  # hashes by id(): equal rebuilt plans miss the cache
        pass

    fs = audit_kernel(
        _entry(lambda x: x, {"<out>": ("int32", (64,))}, cache_key=IdentityKey())
    )
    assert any("not deeply immutable" in f.message for f in fs) or any(
        "identity" in f.message for f in fs
    )


def test_plan_audit_real_matrix_clean():
    from banyandb_tpu.lint.whole_program.plan_audit import run_plan_audit

    fs = run_plan_audit()
    assert fs == [], "\n".join(f.render() for f in fs)


# -- CLI / suppressions ------------------------------------------------------


def test_wp_findings_honor_suppressions(tmp_path):
    p = tmp_path / "x.py"
    p.write_text(
        "import jax\n"
        "# bdlint: disable=wp-sync-in-jit -- seeded, documented\n"
        "y = 1\n"
    )
    from banyandb_tpu.lint.core import Finding

    f = Finding(path=str(p), line=3, col=0, rule="wp-sync-in-jit", message="m")
    kept, suppressed = apply_suppressions([f])
    assert kept == [] and suppressed == 1


def test_cli_whole_program_gate_green():
    """The acceptance run: --check over the real package exits 0 with the
    whole-program analyses folded in (kernel audit included)."""
    from banyandb_tpu.lint.__main__ import main

    import banyandb_tpu

    pkg = Path(banyandb_tpu.__file__).parent
    assert main(["--check", str(pkg)]) == 0
