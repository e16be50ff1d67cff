"""The driver contract: entry() compiles single-chip, dryrun_multichip
runs the full sharded training-step analog on an n-device mesh — in this
process, on whatever ``jax.devices()`` provides (the conftest's 8 forced
host devices here), and raises instead of emulating when there are
fewer than n.
"""

from __future__ import annotations

import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import pytest

import __graft_entry__ as graft


def test_dryrun_multichip_raises_beyond_real_devices():
    n = len(jax.devices())
    with pytest.raises(RuntimeError, match=f"needs {n + 1} devices"):
        graft.dryrun_multichip(n + 1)


def test_dryrun_multichip_in_process():
    # Full distributed step (mesh collectives + cluster mesh fast path)
    # on the conftest's 8 virtual CPU devices, in this very process.
    n = len(jax.devices())
    if n < 2:
        pytest.skip("needs a multi-device platform")
    report = graft.dryrun_multichip(n)
    assert len(report["input_devices"]) == n
    assert report["count"] == report["host_union"]


def test_entry_compiles():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    assert out["count"].shape == (1, 64)  # one chunk's partials, stacked
