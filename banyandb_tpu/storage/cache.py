"""Serving cache: decoded columns for repeat queries.

Analog of the reference's serving cache
(banyand/internal/storage/cache.go:125), redesigned around this repo's
query pipeline: the expensive host work on the read path is (1) reading
+ decoding part blocks into ColumnData and (2) gathering sources into
one deduplicated global-code chunk for the device.  Both layers cache
here, keyed on immutable identities (part directories never mutate —
merges write NEW part dirs — so entries never go stale; deleted parts
simply age out).

One process-global cache with a byte budget (BYDB_SERVING_CACHE_BYTES,
default 256 MiB) and hit/miss/eviction/refusal counters that the query
trace spans and /metrics surface.

Policy: admission and victim choice by observed reuse.  An entry is
*proven* once its key has been asked for twice within the cache's
memory — a hit while resident, or a miss whose key is still in the
bounded ghost list of keys recently refused or evicted (hashes only, no
values); everything else is *unproven*.  Victims are unproven entries
in LRU order, then proven ones in LRU order.  A candidate never seen
before that could only fit by evicting a proven entry is not retained:
its caller gets the loaded value, its key goes to the ghost list, and a
second request admits it as proven.  While there is room everything is
admitted, and a cache of unproven entries only is a plain LRU.  Why:
one plain LRU let an entry nobody asks for twice push out entries
somebody does.  At upstream's 9,000-endpoint estate (`ep9k.topn-6h`)
the decoded parts are ~280 MB, a query reads 140 - 234 MB of them and
then offers its own 94.5 MB gather, whose key carries a range drawn at
ms resolution and never repeats; admitting it evicted the parts the
next query needed, 5.8 part decodes (187 MB, ~660 ms) a query (ledger,
PR 28).  Evicting never-hit entries first is not enough: a part that
was evicted and decoded again is itself never-hit when the next gather
arrives, so the evidence of reuse has to outlive the entry — the ghost
list.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Callable, Optional

import numpy as np

from banyandb_tpu.qos import tenancy
from banyandb_tpu.utils.envflag import env_int

DEFAULT_BUDGET = env_int("BYDB_SERVING_CACHE_BYTES", 256 << 20)


def default_cap() -> int:
    """Optional ENTRY capacity on top of the byte budget: the load
    harness showed a 916-entry squeeze churning 18k evictions in 10
    minutes (docs/load_r06.json) — operators size the entry population
    explicitly with BYDB_SERVING_CACHE_CAP / --serving-cache-cap
    (0 = bytes-only).  Read at CONSTRUCTION time, matching the other
    envflag call sites, so a post-import env change or late server flag
    takes effect without re-import (tests/test_serving_cache.py pins)."""
    return env_int("BYDB_SERVING_CACHE_CAP", 0)


def sizeof(obj) -> int:
    """Approximate retained bytes of cached values (arrays dominate;
    covers numpy and jax arrays via nbytes)."""
    if isinstance(obj, np.ndarray) or hasattr(obj, "nbytes"):
        return int(obj.nbytes)
    if isinstance(obj, dict):
        return 64 + sum(sizeof(v) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return 64 + sum(sizeof(v) for v in obj)
    if isinstance(obj, (bytes, bytearray)):
        return len(obj)
    if hasattr(obj, "__dict__"):
        return 64 + sum(sizeof(v) for v in vars(obj).values())
    return 64


# Ghost list capacity, in keys: the cache's memory of what it refused or
# evicted (one int a key).  A query leaves a handful of keys behind, so
# this remembers the last several hundred queries' worth.
_GHOST_KEYS = 4096


class ServingCache:
    """Byte-budget cache that keeps what is asked for twice (policy in
    the module docstring); values must be treated as immutable."""

    def __init__(
        self,
        budget_bytes: int = DEFAULT_BUDGET,
        max_entries: Optional[int] = None,
    ):
        self.budget = budget_bytes
        # entry cap: 0 = unlimited (byte budget only); None inherits the
        # BYDB_SERVING_CACHE_CAP env default, read now (construction)
        self.cap = default_cap() if max_entries is None else int(max_entries)
        self._lock = threading.Lock()
        # key -> (value, size), each in LRU order; an entry lives in one
        self._unproven: OrderedDict[tuple, tuple[object, int]] = OrderedDict()
        self._proven: OrderedDict[tuple, tuple[object, int]] = OrderedDict()
        self._unproven_bytes = 0
        self._ghosts: OrderedDict[int, None] = OrderedDict()
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.refused = 0

    def set_cap(self, max_entries: int) -> None:
        """Reconfigure the entry cap live (server flag); evicts down to
        the new bound immediately."""
        with self._lock:
            self.cap = int(max_entries)
            self._make_room_locked(0, 0)

    def _remember_locked(self, key: tuple) -> None:
        h = hash(key)
        self._ghosts[h] = None
        self._ghosts.move_to_end(h)
        if len(self._ghosts) > _GHOST_KEYS:
            self._ghosts.popitem(last=False)

    def _drop_locked(self, key: tuple) -> bool:
        """Remove `key` if resident; was it there."""
        entry = self._unproven.pop(key, None)
        if entry is not None:
            self._unproven_bytes -= entry[1]
        else:
            entry = self._proven.pop(key, None)
        if entry is None:
            return False
        self.bytes -= entry[1]
        return True

    def _make_room_locked(self, size: int, count: int) -> None:
        """Evict until `size` more bytes in `count` more entries fit:
        unproven entries in LRU order, then proven ones."""
        while (self._unproven or self._proven) and (
            self.bytes + size > self.budget
            or (
                self.cap
                and len(self._unproven) + len(self._proven) + count > self.cap
            )
        ):
            victim = next(iter(self._unproven or self._proven))
            self._drop_locked(victim)
            self._remember_locked(victim)
            self.evictions += 1

    def fetch(self, key: tuple, loader: Callable[[], object]):
        """(value, outcome): `hit` (resident), `miss` (loaded and
        retained) or `refused` (loaded and handed to the caller only)."""
        with self._lock:
            entry = self._proven.get(key)
            if entry is not None:
                self._proven.move_to_end(key)
            else:
                entry = self._unproven.pop(key, None)
                if entry is not None:  # asked for twice: proven
                    self._unproven_bytes -= entry[1]
                    self._proven[key] = entry
            if entry is not None:
                self.hits += 1
                return entry[0], "hit"
            self.misses += 1
        # Load outside the lock (disk reads can be slow); racing loaders
        # compute the same immutable value, last-insert wins harmlessly.
        value = loader()
        size = sizeof(value)
        with self._lock:
            if size > self.budget:
                self.refused += 1  # too large to retain; serve uncached
                return value, "refused"
            # asked for before: remembered, or a racing loader's insert
            proven = self._drop_locked(key)
            h = hash(key)
            if h in self._ghosts:
                del self._ghosts[h]
                proven = True
            if (
                not proven
                and self.bytes + size - self.budget > self._unproven_bytes
            ):
                # first sight, and room only at a proven entry's cost
                self._remember_locked(key)
                self.refused += 1
                return value, "refused"
            self._make_room_locked(size, 1)
            if proven:
                self._proven[key] = (value, size)
            else:
                self._unproven[key] = (value, size)
                self._unproven_bytes += size
            self.bytes += size
        return value, "miss"

    def get_or_load(self, key: tuple, loader: Callable[[], object]):
        return self.fetch(key, loader)[0]

    def invalidate_prefix(self, prefix: tuple) -> int:
        """Drop entries whose key starts with `prefix` (rarely needed —
        part identities are immutable — but retention tests use it)."""
        with self._lock:
            doomed = [
                k
                for k in (*self._unproven, *self._proven)
                if k[: len(prefix)] == prefix
            ]
            for k in doomed:
                self._drop_locked(k)
            return len(doomed)

    def clear(self) -> None:
        with self._lock:
            self._unproven.clear()
            self._proven.clear()
            self._ghosts.clear()
            self._unproven_bytes = 0
            self.bytes = 0

    def stats(self) -> dict:
        with self._lock:
            lookups = self.hits + self.misses
            return {
                "entries": len(self._unproven) + len(self._proven),
                "bytes": self.bytes,
                "budget": self.budget,
                "cap": self.cap,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                # loads handed to the caller and not retained
                "refused": self.refused,
                # eviction churn: evictions per lookup — the r06 squeeze
                # signal (18102 evictions / 76k lookups) as one number
                "churn": round(self.evictions / lookups, 4)
                if lookups
                else 0.0,
            }


_global = ServingCache()

# Device-resident chunk cache (padded jnp arrays keyed by gather identity)
# — its own budget so HBM residency is bounded independently of the host
# cache (default 1 GiB: a deliberate slice of the chip's 16-32 GiB HBM,
# since resident chunks save both decode AND host->device transfer).
# max_entries=0: the serving-cache ENTRY cap (BYDB_SERVING_CACHE_CAP) is
# a host-cache knob and must not silently bound HBM residency too.
DEVICE_BUDGET = env_int("BYDB_DEVICE_CACHE_BYTES", 1 << 30)
_device = ServingCache(DEVICE_BUDGET, max_entries=0)

# Per-tenant serving-cache partitions (docs/robustness.md "Multi-tenant
# QoS"): queries running under a non-default tenant scope (qos/tenancy
# contextvar, bound by the serving roles) read/write their tenant's OWN
# LRU, so one tenant's churn cannot evict another's entries.  The
# default tenant keeps the original process-global instance — untenanted
# deployments are byte-identical to pre-QoS behavior.  Each partition
# gets the tenant's configured budget (qos limits `cache_bytes`) or the
# process default, and the same entry-cap knob.
_partitions: dict[str, ServingCache] = {}
_partitions_lock = threading.Lock()


def _tenant_partition(tenant: str) -> ServingCache:
    part = _partitions.get(tenant)
    if part is None:
        with _partitions_lock:
            part = _partitions.get(tenant)
            if part is None:
                from banyandb_tpu.qos.plane import global_qos

                budget = (
                    global_qos().limits(tenant).cache_bytes or DEFAULT_BUDGET
                )
                part = _partitions[tenant] = ServingCache(budget)
    return part


def global_cache() -> ServingCache:
    tenant = tenancy.current_tenant()
    if tenant == tenancy.DEFAULT_TENANT:
        return _global
    return _tenant_partition(tenant)


def partition_stats() -> dict[str, dict]:
    """Per-tenant partition stats for /metrics (`tenant`-labeled rows);
    the default tenant's cache keeps its original unlabeled series."""
    with _partitions_lock:
        parts = dict(_partitions)
    return {t: c.stats() for t, c in sorted(parts.items())}


def device_cache() -> ServingCache:
    return _device


def reset_global_cache(budget_bytes: int = DEFAULT_BUDGET) -> ServingCache:
    """Test hook / server reconfiguration."""
    global _global, _device
    _global = ServingCache(budget_bytes)
    _device = ServingCache(DEVICE_BUDGET, max_entries=0)
    with _partitions_lock:
        _partitions.clear()
    return _global
