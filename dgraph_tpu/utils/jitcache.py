"""Host-side jit compile-cache accounting.

XLA retraces/recompiles whenever a kernel launch's STATIC configuration
(bucketed shapes, caps, depth) changes, and a device that hangs and a
multi-second compile are indistinguishable without telemetry. Each
kernel call site wraps its launch in `jit_call(kernel, key)` where
`key` is exactly the static tuple that forces a distinct program —
first sight of a key counts as a compile (timed: the first invocation
traces + compiles synchronously before dispatch), repeats count as
cache hits.

The timing is an upper bound on compile cost (it includes the first
dispatch), which is the honest observable without reaching into XLA
internals; steady-state calls are classified exactly.
"""

from __future__ import annotations

import contextlib
import time

from dgraph_tpu.utils import locks, tracing
from dgraph_tpu.utils.metrics import METRICS

_seen: set = set()
_lock = locks.make_lock("jitcache.seen")

# compile times ladder: 10ms … 100s in µs
COMPILE_BUCKETS_US = (10_000, 100_000, 500_000, 1_000_000, 5_000_000,
                      10_000_000, 100_000_000)


def seen(kernel: str, key: tuple) -> bool:
    with _lock:
        return (kernel, key) in _seen


@contextlib.contextmanager
def jit_call(kernel: str, key: tuple):
    """Wrap one jitted-kernel launch; classifies it as compile (first
    time this static key is seen) or cache hit, and feeds the shared
    metrics/tracing registries. Yields True when a compile is expected.

    Every `jit_call` site is exactly one device dispatch, so this is
    ALSO where per-request launch accounting lives: the wrapped span
    feeds `costprofile.note_launch` — `kernel_launches` counts one per
    site reached, and the host-side gap since the previous launch in
    the same recorder frame lands in `launch_gap_us` (the dispatch-
    overhead baseline the whole-query fused path collapses to a single
    launch). A cache hit also feeds `jit_dispatch_us{kernel=}`."""
    from dgraph_tpu.utils import costprofile
    with _lock:
        new = (kernel, key) not in _seen
        if new:
            _seen.add((kernel, key))
    t0 = time.perf_counter()
    try:
        if not new:
            METRICS.inc("jit_cache_hits_total", kernel=kernel)
            costprofile.add("jit_cache_hits", 1)
            yield False
            return
        METRICS.inc("jit_compile_total", kernel=kernel)
        with tracing.span("jit.compile", kernel=kernel, key=str(key)):
            try:
                yield True
            finally:
                compile_us = (time.perf_counter() - t0) * 1e6
                METRICS.observe("jit_compile_us", compile_us,
                                buckets=COMPILE_BUCKETS_US, kernel=kernel)
                # per-kernel-family compile cost joins the request's
                # cost record (the compile-vs-execute split the cost
                # model needs)
                costprofile.add_kernel(kernel, compile_us=compile_us)
    finally:
        t1 = time.perf_counter()
        if not new:
            # a seen key compiles nothing: from here to the jitted
            # call's return is the dispatch, the host's part of a
            # launch before any wait for the device begins
            METRICS.observe("jit_dispatch_us", (t1 - t0) * 1e6,
                            kernel=kernel)
        costprofile.note_launch(t0, t1)


def reset() -> None:
    """Test hook: forget every key (a fresh process compiles anew)."""
    with _lock:
        _seen.clear()


class Memo:
    """Bounded LRU memo for host-side derived objects that amortize like
    compiled programs do (batch PLANS keyed by query shape, the bench's
    ELL build) — the host-side sibling of the jit compile cache above.
    Callers classify hits/misses into their own metrics; the memo only
    stores. Thread-safe via a named lock so the lock-order sanitizer
    covers every cache the batch path grew in PR 7.

    `governed=` names the memory-governor cache this memo registers as
    (graftlint R14 requires every Memo to pick one or waive): the memo
    then accounts bytes per entry (`put(..., nbytes=, rebuild_us=)`) and
    surrenders its LRU-coldest entry on demand, priced at rebuild-µs per
    byte for the governor's cross-cache eviction ordering."""

    def __init__(self, name: str, capacity: int = 128,
                 governed: str | None = None, kind: str = "host"):
        import collections
        self.name = name
        self.capacity = capacity
        self._d: "collections.OrderedDict" = collections.OrderedDict()
        self._sizes: dict = {}
        self._costs: dict = {}
        self._bytes = 0
        self._lock = locks.make_lock(f"jitcache.memo.{name}")
        locks.guarded(self, "jitcache.memo.*")
        if governed is not None:
            from dgraph_tpu.utils import memgov
            memgov.GOVERNOR.register(governed, kind, self.nbytes,
                                     self.evict_one,
                                     value_cb=self.coldest_value,
                                     owner=self)

    def get(self, key):
        with self._lock:
            if key not in self._d:
                return None
            self._d.move_to_end(key)
            return self._d[key]

    def put(self, key, value, nbytes: int | None = None,
            rebuild_us: float | None = None) -> None:
        """Insert (LRU-newest). `nbytes` is the entry's resident size
        (estimated when omitted) and `rebuild_us` what recomputing it
        costs — the governor evicts low rebuild-value-per-byte first."""
        if nbytes is None:
            from dgraph_tpu.utils import memgov
            nbytes = memgov.estimate_nbytes(value)
        with self._lock:
            self._drop_locked(key)
            self._d[key] = value
            self._sizes[key] = int(nbytes)
            if rebuild_us is not None:
                self._costs[key] = float(rebuild_us)
            self._bytes += int(nbytes)
            while len(self._d) > self.capacity:
                k, _ = self._d.popitem(last=False)
                self._bytes -= self._sizes.pop(k, 0)
                self._costs.pop(k, None)

    def _drop_locked(self, key) -> None:
        if key in self._d:
            del self._d[key]
            self._bytes -= self._sizes.pop(key, 0)
            self._costs.pop(key, None)

    def reprice(self, key, rebuild_us: float) -> None:
        """Update an entry's rebuild cost after the fact (fused programs
        only learn their true compile µs at first dispatch)."""
        with self._lock:
            if key in self._d:
                self._costs[key] = float(rebuild_us)

    def nbytes(self) -> int:
        with self._lock:
            return self._bytes

    def evict_one(self) -> int:
        """Drop the LRU-coldest entry; returns bytes freed (0 = empty)."""
        with self._lock:
            if not self._d:
                return 0
            k, _ = self._d.popitem(last=False)
            freed = self._sizes.pop(k, 0)
            self._costs.pop(k, None)
            self._bytes -= freed
            return freed

    def coldest_value(self) -> float | None:
        """Recompute-µs-per-byte of the entry evict_one would drop."""
        with self._lock:
            if not self._d:
                return None
            k = next(iter(self._d))
            cost = self._costs.get(k)
            if cost is None:
                return None
            return cost / max(self._sizes.get(k, 1), 1)

    def clear(self) -> None:
        with self._lock:
            self._d.clear()
            self._sizes.clear()
            self._costs.clear()
            self._bytes = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)
