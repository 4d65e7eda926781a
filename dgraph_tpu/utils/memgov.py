"""Process-wide memory governor: one budget over every byte-holding cache.

Every perf PR since 7 grew a cache — ELL plans, fused-program memos,
mesh shard residency, adapted tablets, plan memos — and none of them
shared a budget or understood bytes. This module is the single registry
they all join: each cache registers a *name* (from the static
`GOVERNED_CACHES` inventory below), a byte-accounting callback, and an
evict-one callback. Two budgets (`device`, `host`) with high/low
watermarks govern them; when resident bytes cross the high watermark the
governor evicts — cheapest-to-rebuild, coldest entry first, ordered by
predicted recompute value per byte (caches derive the value from the
compile/build µs the cost profile already records) — until bytes drop
under the low watermark.

On top of the budgets sits OOM-safe execution. Launch sites wrap their
device dispatch in `oom_retry(site, shape, fn)`: an XLA allocation
failure (`RESOURCE_EXHAUSTED` / `JaxRuntimeError` out-of-memory, or an
injected `AllocFault`) triggers a synchronous evict-to-low-watermark and
ONE retry; a second failure sticky-degrades that (site, shape) to the
caller's host/staged route — bit-identical results, the process never
dies. `set_alloc_fault` is the vault-style process hook the fault
schedule's `alloc` family uses to inject allocation failures at the real
launch sites.

Import discipline: this module must stay importable without jax (facts
extraction and the CLI read `GOVERNED_CACHES` without a device runtime);
jax and flightrec are only touched lazily.
"""

from __future__ import annotations

import weakref

from dgraph_tpu.utils import locks
from dgraph_tpu.utils.metrics import METRICS

__all__ = [
    "GOVERNED_CACHES", "Governor", "GOVERNOR", "AllocFault", "OomDegraded",
    "is_alloc_failure", "set_alloc_fault", "check_alloc_fault", "oom_retry",
    "HIGH_WATERMARK", "LOW_WATERMARK",
]

# ---------------------------------------------------------------------------
# static inventory: every governed cache in the process, by name.
# graftlint R14 pins this both ways — `analysis/facts.py` re-exports it
# verbatim and the runtime registry must register exactly these names —
# so a new byte-holding cache cannot ship ungoverned (the
# cost_record_fields pattern).

GOVERNED_CACHES: dict[str, str] = {
    "fused.program": "whole-query fused programs: compiled XLA callables "
                     "memoized per query shape (PR 15)",
    "batch.plan": "batch plan memo: parsed+grouped plans keyed by query "
                  "shape, shared across identical batches",
    "batch.ell": "host ELL adjacency builds per (snapshot, pred, dir) — "
                 "the padded matrices device kernels consume",
    "batch.ell_dev": "device-resident ELL adjacency (device_put of "
                     "batch.ell entries) — HBM bytes",
    "batch.kernel": "compiled recurse/step kernel callables per static "
                    "launch configuration",
    "store.device": "per-relation CSR (indptr, indices) device arrays "
                    "placed by Store.device_rel",
    "store.sharded": "mesh shard stacks placed by Store.sharded_rel — "
                     "the pod-scale residency (PR 10)",
    "api.tablet": "adapted tablet cache: per-(pred, snapshot) tablets "
                  "the serving path reuses across queries",
    "outofcore.resident": "LazyPreds resident tablets: out-of-core "
                          "postings faulted from disk under its own LRU",
    "timeseries.ring": "retained metrics history: the sampler daemon's "
                       "bounded ring of windowed points (PR 17) — under "
                       "pressure the oldest history is surrendered first",
    "store.vec": "float32vector embedding stacks placed by "
                 "Store.vec_device / vec_sharded — the k-NN seed "
                 "tablets (PR 18); evicted stacks re-place on next use",
}

# watermark fractions of the configured budget: eviction starts above
# HIGH and runs down to LOW (hysteresis so a single fill does not thrash)
HIGH_WATERMARK = 0.90
LOW_WATERMARK = 0.70

_BYTES_BUCKETS = (1 << 20, 16 << 20, 64 << 20, 256 << 20, 1 << 30,
                  4 << 30, 16 << 30)


class AllocFault(RuntimeError):
    """Synthetic allocation failure raised by the injection hook — the
    fault schedule's stand-in for XLA RESOURCE_EXHAUSTED."""


class OomDegraded(RuntimeError):
    """A (site, shape) exhausted its one OOM retry and is now sticky-
    degraded; the caller must serve via its host/staged route."""

    def __init__(self, site: str, shape: str):
        super().__init__(f"oom-degraded: {site} shape={shape}")
        self.site = site
        self.shape = shape


def is_alloc_failure(exc: BaseException) -> bool:
    """Classify an exception as a device allocation failure: the
    injected `AllocFault`, python `MemoryError`, or an XLA runtime
    error (`jax.errors.JaxRuntimeError` on the installed jax) whose text
    carries the canonical out-of-memory markers. Matched on type name +
    message so jax never has to be imported."""
    if isinstance(exc, (AllocFault, MemoryError)):
        return True
    if type(exc).__name__ != "JaxRuntimeError":
        return False
    text = str(exc).lower()
    return ("resource_exhausted" in text or "resource exhausted" in text
            or "out of memory" in text or "allocation failure" in text)


# ---------------------------------------------------------------------------
# allocation-fault injection hook (the vault `set_io_fault` pattern):
# a process-wide callback consulted at every launch site right before
# the device dispatch; returning truthy (or raising) injects the fault.

_alloc_fault_cb = None


def set_alloc_fault(cb) -> None:
    """Install (or clear, with None) the allocation-fault hook. The hook
    receives the launch-site name and injects by returning truthy or
    raising itself; fuzz harnesses arm one-shot closures."""
    global _alloc_fault_cb
    _alloc_fault_cb = cb


def check_alloc_fault(site: str) -> None:
    cb = _alloc_fault_cb
    if cb is not None and cb(site):
        raise AllocFault(f"injected allocation failure at {site}")


class _Entry:
    __slots__ = ("name", "kind", "bytes_cb", "evict_one_cb", "value_cb",
                 "detail_cb", "owner_ref")

    def __init__(self, name, kind, bytes_cb, evict_one_cb, value_cb,
                 owner, detail_cb=None):
        self.name = name
        self.kind = kind
        self.bytes_cb = bytes_cb
        self.evict_one_cb = evict_one_cb
        self.value_cb = value_cb
        self.detail_cb = detail_cb
        self.owner_ref = weakref.ref(owner) if owner is not None else None

    def alive(self) -> bool:
        return self.owner_ref is None or self.owner_ref() is not None

    def bytes(self) -> int:
        try:
            return int(self.bytes_cb())
        except Exception:
            return 0

    def value(self) -> float:
        """Predicted recompute µs per byte of the entry this cache would
        evict next — lower is cheaper to rebuild, so evicted first; a
        cache with no opinion (None) evicts before any priced one."""
        if self.value_cb is None:
            return 0.0
        try:
            v = self.value_cb()
        except Exception:
            return 0.0
        return 0.0 if v is None else float(v)

    def detail(self) -> list:
        """Per-resident rows for /debug/memory (e.g. a vec cache's
        placed stacks with their dims); [] when the cache has no
        detail callback or it fails."""
        if self.detail_cb is None:
            return []
        try:
            return list(self.detail_cb())
        except Exception:
            return []


class Governor:
    """The process-wide cache registry + budget enforcer. Callbacks are
    always invoked OUTSIDE the governor lock (entries are snapshotted
    under it first) so cache-internal locks never order against ours."""

    def __init__(self):
        self._lock = locks.make_lock("memgov.governor")
        locks.guarded(self, "memgov.governor")
        self._entries: dict[int, _Entry] = {}
        self._next_id = 0
        self._budgets = {"device": 0, "host": 0}
        self._armed = False          # any budget set (lock-free fast path)
        self._evictions: dict[str, int] = {}
        self._oom_events = 0
        self._oom_retries = 0
        self._degraded: dict[tuple[str, str], int] = {}
        self._deg_lock = locks.make_lock("memgov.degraded")  # leaf lock

    # -- registration -----------------------------------------------------

    def register(self, name: str, kind: str, bytes_cb, evict_one_cb,
                 value_cb=None, owner=None, detail_cb=None) -> int:
        """Join the registry. `name` must appear in GOVERNED_CACHES and
        `kind` is the budget it draws from ("device" | "host").
        `bytes_cb()` returns resident bytes; `evict_one_cb()` drops the
        cache's coldest entry and returns bytes freed (0 when empty);
        `value_cb()` prices that coldest entry in recompute-µs-per-byte.
        Per-instance caches pass `owner` so dead instances fall out of
        the registry via weakref."""
        if name not in GOVERNED_CACHES:
            raise ValueError(f"unknown governed cache {name!r} — add it "
                             f"to memgov.GOVERNED_CACHES")
        if kind not in ("device", "host"):
            raise ValueError(f"bad cache kind {kind!r}")
        e = _Entry(name, kind, bytes_cb, evict_one_cb, value_cb, owner,
                   detail_cb)
        with self._lock:
            self._next_id += 1
            rid = self._next_id
            self._entries[rid] = e
            self._prune_locked()
        return rid

    def unregister(self, rid: int) -> None:
        with self._lock:
            self._entries.pop(rid, None)

    def _prune_locked(self) -> None:
        dead = [k for k, e in self._entries.items() if not e.alive()]
        for k in dead:
            del self._entries[k]

    def registered_names(self) -> set:
        with self._lock:
            return {e.name for e in self._entries.values() if e.alive()}

    def _snapshot(self, kind=None) -> list:
        with self._lock:
            self._prune_locked()
            return [e for e in self._entries.values()
                    if e.alive() and (kind is None or e.kind == kind)]

    # -- budgets / accounting ---------------------------------------------

    def set_budgets(self, device_bytes: int = 0,
                    host_bytes: int = 0) -> None:
        """Configure the budgets (0 disarms a kind). Watermarks are
        fractions of the budget: evict above HIGH, down to LOW."""
        with self._lock:
            self._budgets["device"] = int(device_bytes)
            self._budgets["host"] = int(host_bytes)
        self._armed = bool(device_bytes or host_bytes)

    def budget(self, kind: str) -> int:
        return self._budgets[kind]

    def resident_bytes(self, kind: str) -> int:
        return sum(e.bytes() for e in self._snapshot(kind))

    # -- eviction ---------------------------------------------------------

    def maybe_evict(self, kind: str) -> int:
        """Cache fill hook: when the kind's budget is armed and resident
        bytes crossed the high watermark, evict down to the low one.
        Unarmed processes pay one attribute read (the hot-path bound the
        <5% overhead guard pins)."""
        if not self._armed:
            return 0
        budget = self._budgets[kind]
        if not budget:
            return 0
        if self.resident_bytes(kind) <= int(budget * HIGH_WATERMARK):
            return 0
        return self.evict_to_low(kind)

    def evict_to_low(self, kind: str) -> int:
        """Synchronous eviction pass: drop entries — lowest recompute-
        value-per-byte across caches first, each cache surrendering its
        own coldest entry — until resident bytes fall under the low
        watermark (or nothing evictable remains). Returns bytes freed."""
        budget = self._budgets[kind]
        low = int(budget * LOW_WATERMARK) if budget else 0
        freed = 0
        while self.resident_bytes(kind) > low:
            candidates = [e for e in self._snapshot(kind) if e.bytes() > 0]
            if not candidates:
                break
            candidates.sort(key=lambda e: e.value())
            got = 0
            for e in candidates:
                got = int(e.evict_one_cb() or 0)
                if got > 0:
                    METRICS.inc("cache_evictions_total", cache=e.name)
                    with self._lock:
                        self._evictions[e.name] = (
                            self._evictions.get(e.name, 0) + 1)
                    freed += got
                    break
            if got <= 0:      # every candidate refused: no progress
                break
        return freed

    # -- pressure (admission integration) ---------------------------------

    def admission_pressure(self):
        """Sustained-pressure probe for admission: a kind still above its
        high watermark AFTER an eviction pass (nothing left to shed but
        load). Returns the kind name, or None. Unarmed: one attribute
        read."""
        if not self._armed:
            return None
        for kind in ("device", "host"):
            budget = self._budgets[kind]
            if not budget:
                continue
            high = int(budget * HIGH_WATERMARK)
            if self.resident_bytes(kind) > high:
                self.evict_to_low(kind)
                if self.resident_bytes(kind) > high:
                    return kind
        return None

    # -- OOM lifecycle ----------------------------------------------------

    def note_oom(self, site: str, shape: str, kind: str = "device") -> int:
        """One allocation failure observed at a launch site: count it,
        flight-record it, and synchronously evict the kind to its low
        watermark so the retry has room. Returns bytes freed."""
        with self._deg_lock:
            self._oom_events += 1
            self._oom_retries += 1
        METRICS.inc("oom_events_total", site=site)
        freed = self.evict_to_low(kind)
        try:
            from dgraph_tpu.utils import flightrec
            flightrec.emit("memory.oom", site=site, shape=str(shape),
                           freed_bytes=freed)
        except Exception:
            pass
        return freed

    def degrade(self, site: str, shape: str) -> None:
        """Sticky-degrade a (site, shape): its one retry also failed, so
        every future request on the shape takes the host/staged route
        until reset. Bit-identical results, no process death."""
        with self._deg_lock:
            key = (site, str(shape))
            self._degraded[key] = self._degraded.get(key, 0) + 1
            n = len(self._degraded)
        METRICS.set_gauge("oom_degraded", float(n))
        try:
            from dgraph_tpu.utils import flightrec
            flightrec.emit("memory.degrade", site=site, shape=str(shape))
        except Exception:
            pass

    def is_degraded(self, site: str, shape) -> bool:
        with self._deg_lock:
            return (site, str(shape)) in self._degraded

    def oom_stats(self) -> dict:
        """Counters the watchdog's kind=oom scan convicts on."""
        with self._deg_lock:
            return {"events": self._oom_events,
                    "retries": self._oom_retries,
                    "degraded": len(self._degraded)}

    # -- introspection ----------------------------------------------------

    def status(self) -> dict:
        """The /debug/memory document: budgets + watermarks, per-cache
        resident bytes and evictions, OOM lifecycle state."""
        caches: dict[str, dict] = {}
        for e in self._snapshot():
            b = e.bytes()
            c = caches.setdefault(e.name, {"kind": e.kind, "bytes": 0,
                                           "registrants": 0})
            c["bytes"] += b
            c["registrants"] += 1
            d = e.detail()
            if d:
                c.setdefault("detail", []).extend(d)
        with self._lock:
            ev = dict(self._evictions)
            budgets = dict(self._budgets)
        for name, c in caches.items():
            c["evictions"] = ev.get(name, 0)
            METRICS.set_gauge("cache_resident_bytes", float(c["bytes"]),
                              cache=name)
        kinds = {}
        for kind in ("device", "host"):
            budget = budgets[kind]
            kinds[kind] = {
                "budget_bytes": budget,
                "high_bytes": int(budget * HIGH_WATERMARK),
                "low_bytes": int(budget * LOW_WATERMARK),
                "resident_bytes": sum(c["bytes"] for c in caches.values()
                                      if c["kind"] == kind),
            }
        with self._deg_lock:
            degraded = [{"site": s, "shape": sh, "count": n}
                        for (s, sh), n in sorted(self._degraded.items())]
            oom = {"events": self._oom_events,
                   "retries": self._oom_retries}
        # read-only pressure: above-high without triggering an eviction
        pressure = None
        for kind in ("device", "host"):
            k = kinds[kind]
            if k["budget_bytes"] and k["resident_bytes"] > k["high_bytes"]:
                pressure = kind
                break
        return {"budgets": kinds, "caches": caches,
                "oom": oom, "degraded": degraded,
                "pressure": pressure}

    def reset(self, full: bool = False) -> None:
        """Test hook: clear budgets, eviction/OOM counters and sticky
        degrades (registrations survive unless full=True — module-level
        memos register once at import)."""
        with self._lock:
            self._budgets = {"device": 0, "host": 0}
            self._evictions.clear()
            if full:
                self._entries.clear()
        self._armed = False
        with self._deg_lock:
            self._oom_events = 0
            self._oom_retries = 0
            self._degraded.clear()
        METRICS.set_gauge("oom_degraded", 0.0)


GOVERNOR = Governor()


def oom_retry(site: str, shape, fn, kind: str = "device"):
    """Run one device launch with the OOM lifecycle: an allocation
    failure triggers evict-to-low-watermark and ONE retry; a second
    failure sticky-degrades the (site, shape) and raises `OomDegraded`
    for the caller's host/staged fallback. A shape already degraded
    raises immediately (the sticky fast path). Any non-allocation
    exception passes through untouched."""
    if GOVERNOR.is_degraded(site, shape):
        raise OomDegraded(site, str(shape))
    try:
        check_alloc_fault(site)
        return fn()
    except Exception as e:
        if not is_alloc_failure(e):
            raise
        GOVERNOR.note_oom(site, str(shape), kind=kind)
        try:
            check_alloc_fault(site)
            return fn()
        except Exception as e2:
            if not is_alloc_failure(e2):
                raise
            GOVERNOR.degrade(site, str(shape))
            raise OomDegraded(site, str(shape)) from e2


def estimate_nbytes(value) -> int:
    """Best-effort byte size of a cached value: arrays report .nbytes,
    containers sum their members, everything else costs sys.getsizeof.
    An estimator, not an audit — budgets only need relative truth."""
    import sys
    seen_bytes = 0
    stack = [value]
    depth = 0
    while stack and depth < 4096:
        depth += 1
        v = stack.pop()
        nb = getattr(v, "nbytes", None)
        if nb is not None:
            try:
                seen_bytes += int(nb)
                continue
            except Exception:
                pass
        if isinstance(v, (tuple, list)):
            stack.extend(v)
        elif isinstance(v, dict):
            stack.extend(v.values())
        elif hasattr(v, "__dataclass_fields__"):
            stack.extend(vars(v).values())   # EllGraph/DeviceEll et al.
        else:
            seen_bytes += sys.getsizeof(v, 64)
    return seen_bytes
