"""Memory governor (ISSUE 16): eviction value ordering, the OOM
evict-retry → sticky-degrade lifecycle, bit-identity of the degraded
route, the <5% uncontended-overhead guard (mirroring the tracing /
costprofile guards), and the /debug/memory + flight-bundle surfaces.

The contract under test: budgeted serving completes every request with
byte-identical results to unbudgeted serving — pressure shows up as
evictions, retries, and latency, never as wrong answers or a dead
process.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

from dgraph_tpu.engine import Engine
from dgraph_tpu.server.api import Alpha
from dgraph_tpu.server.http import make_http_server, serve_background
from dgraph_tpu.store import StoreBuilder, parse_schema
from dgraph_tpu.utils import flightrec, memgov
from dgraph_tpu.utils.memgov import (GOVERNOR, Governor, AllocFault,
                                     OomDegraded, HIGH_WATERMARK,
                                     LOW_WATERMARK)
from dgraph_tpu.utils.metrics import METRICS


@pytest.fixture(autouse=True)
def _clean():
    memgov.set_alloc_fault(None)
    GOVERNOR.reset()
    yield
    memgov.set_alloc_fault(None)
    GOVERNOR.reset()


class _FakeCache:
    """A governed cache stub: N entries of `entry_bytes` each, priced at
    a fixed recompute value — the eviction order probe."""

    def __init__(self, n, entry_bytes, value):
        self.entries = n
        self.entry_bytes = entry_bytes
        self.value = value
        self.evicted = 0

    def bytes(self):
        return self.entries * self.entry_bytes

    def evict_one(self):
        if self.entries <= 0:
            return 0
        self.entries -= 1
        self.evicted += 1
        return self.entry_bytes


def _register(gov, name, cache):
    return gov.register(name, "host", cache.bytes, cache.evict_one,
                        value_cb=lambda: cache.value, owner=cache)


def test_eviction_orders_by_recompute_value_per_byte():
    """Above the high watermark the governor sheds the CHEAPEST-to-
    rebuild entries first and stops at the low watermark — the expensive
    cache is only touched once the cheap one runs dry."""
    gov = Governor()
    cheap = _FakeCache(n=8, entry_bytes=100, value=1.0)
    dear = _FakeCache(n=8, entry_bytes=100, value=500.0)
    _register(gov, "batch.ell", cheap)
    _register(gov, "api.tablet", dear)
    # resident 1600 over a 1000 budget: low watermark 700 → free 900 =
    # ALL 8 cheap entries before exactly ONE expensive entry is touched
    gov.set_budgets(host_bytes=1000)
    freed = gov.evict_to_low("host")
    assert freed == 900
    assert gov.resident_bytes("host") <= int(1000 * LOW_WATERMARK)
    assert cheap.evicted == 8
    assert dear.evicted == 1


def test_unknown_cache_name_refused():
    gov = Governor()
    with pytest.raises(ValueError):
        gov.register("rogue.cache", "host", lambda: 0, lambda: 0)
    with pytest.raises(ValueError):
        gov.register("batch.ell", "hbm", lambda: 0, lambda: 0)


def test_oom_retry_absorbs_single_failure_with_one_evict_pass():
    """One allocation failure: evict-to-low + ONE retry succeeds — the
    caller sees the result, nothing degrades, the counters record it."""
    cache = _FakeCache(n=4, entry_bytes=100, value=1.0)
    GOVERNOR.register("batch.ell", "host", cache.bytes, cache.evict_one,
                      owner=cache)
    GOVERNOR.set_budgets(host_bytes=300)  # resident 400 > high 270
    armed = [True]

    def hook(site):
        if armed[0]:
            armed[0] = False
            return True
        return False

    memgov.set_alloc_fault(hook)
    got = memgov.oom_retry("t.site", "shape-a", lambda: 42, kind="host")
    assert got == 42
    st = GOVERNOR.oom_stats()
    assert st == {"events": 1, "retries": 1, "degraded": 0}
    assert cache.evicted > 0, "the failure must trigger the evict pass"
    assert not GOVERNOR.is_degraded("t.site", "shape-a")


def test_oom_retry_sticky_degrades_on_repeat():
    """The retry fails too → OomDegraded, and the (site, shape) is
    STICKY: later calls raise immediately without running the launch
    (or consulting the fault hook)."""
    memgov.set_alloc_fault(lambda site: site == "t.site")
    calls = []
    with pytest.raises(OomDegraded):
        memgov.oom_retry("t.site", "shape-b", lambda: calls.append(1))
    assert not calls, "the hook faults BEFORE the launch runs"
    st = GOVERNOR.oom_stats()
    assert st["events"] == 1 and st["degraded"] == 1
    # sticky fast path: hook disarmed, the shape still refuses the
    # device route — and the launch fn is never invoked
    memgov.set_alloc_fault(None)
    with pytest.raises(OomDegraded):
        memgov.oom_retry("t.site", "shape-b", lambda: calls.append(1))
    assert not calls
    # an unrelated shape at the same site is unaffected
    assert memgov.oom_retry("t.site", "shape-c", lambda: 7) == 7
    # the gauge tracks the sticky set; reset clears it
    assert METRICS.snapshot()["gauges"]["oom_degraded"] == 1.0
    GOVERNOR.reset()
    assert memgov.GOVERNOR.oom_stats()["degraded"] == 0


def test_non_alloc_errors_pass_through_untouched():
    with pytest.raises(KeyError):
        memgov.oom_retry("t.site", "s", lambda: {}["missing"])
    assert GOVERNOR.oom_stats() == {"events": 0, "retries": 0,
                                    "degraded": 0}


def test_is_alloc_failure_classification():
    assert memgov.is_alloc_failure(AllocFault("x"))
    assert memgov.is_alloc_failure(MemoryError())

    # the REAL class the installed jax raises on HBM exhaustion — a
    # stand-in with a matching name let the classifier rot once already
    import jax
    assert memgov.is_alloc_failure(jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "17179869184 bytes."))
    assert not memgov.is_alloc_failure(
        jax.errors.JaxRuntimeError("INVALID_ARGUMENT: invalid shape"))
    assert not memgov.is_alloc_failure(ValueError("out of memory"))


def _friend_store(n=256):
    rng = np.random.default_rng(7)
    b = StoreBuilder(parse_schema(
        "name: string @index(exact) .\nfriend: [uid] @reverse .\n"
        "emb: float32vector @dim(4) ."))
    for i in range(1, n + 1):
        b.add_value(i, "name", f"p{i}")
        b.add_value(i, "emb",
                    [int(x) for x in rng.integers(0, 5, 4)])
        for j in rng.integers(1, n + 1, 4):
            b.add_edge(i, "friend", int(j))
    return b.finalize()


def test_degraded_route_is_bit_identical_to_device_route():
    """The acceptance bar: the same query served by the device route and
    by the OOM-degraded host route returns byte-identical responses —
    degradation is a latency event, never a correctness event."""
    store = _friend_store()
    q = '{ q(func: uid(1)) { friend { friend { friend { uid } } } } }'
    # the GraphRAG seed path rides the same contract: the k-NN top-k
    # launch (site vec.topk) degrades to the host scan, identically
    qv = ('{ q(func: similar_to(emb, 5, "[1, 0, 2, 1]")) '
          '{ uid friend { uid } } }')
    dev = Engine(store, device_threshold=1)   # frontier ≥ 1 → device
    want = dev.query(q)
    want_v = dev.query(qv)
    assert any(p in ("device", "fused") for p in _routes()), \
        "baseline must actually take a device-backed route"

    # every device-backed launch (fused program, device hop, mesh hop,
    # k-NN top-k) allocation-fails → evict-retry → sticky degrade →
    # the staged / host walk serves
    memgov.set_alloc_fault(lambda site: site.startswith(("fused.",
                                                         "hop.",
                                                         "mesh.",
                                                         "vec.")))
    degraded = Engine(store, device_threshold=1)
    got = degraded.query(q)
    assert json.dumps(got, sort_keys=True) == \
        json.dumps(want, sort_keys=True)
    assert json.dumps(degraded.query(qv), sort_keys=True) == \
        json.dumps(want_v, sort_keys=True)
    assert GOVERNOR.oom_stats()["degraded"] >= 1
    # sticky: the SECOND query never re-attempts the device launch, so
    # it serves even with the hook gone
    memgov.set_alloc_fault(None)
    assert json.dumps(degraded.query(q), sort_keys=True) == \
        json.dumps(want, sort_keys=True)
    assert json.dumps(degraded.query(qv), sort_keys=True) == \
        json.dumps(want_v, sort_keys=True)


def _routes():
    snap = METRICS.snapshot()["counters"]
    return [k.split("path=")[1].rstrip("}").strip('"') for k in snap
            if k.startswith("edges_traversed_total{") and "path=" in k]


def _hot_loop_secs(engine, queries, reps):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for q in queries:
            engine.query(q)
        best = min(best, time.perf_counter() - t0)
    return best


def test_governor_overhead_under_5_percent():
    """The armed-but-uncontended governor (budgets set far above the
    working set: every maybe_evict returns at the watermark check) must
    stay within 5% of the unarmed fast path on test_tracing's kind of
    hot loop. Interleaved best-of-3 damps scheduler noise the same way
    the tracing/costprofile guards do."""
    store = _friend_store(n=512)
    engine = Engine(store, device_threshold=10**9)
    queries = [
        '{ q(func: eq(name, "p9")) { name friend { name } } }',
        '{ q(func: has(friend), first: 20) { name friend { friend '
        '{ name } } } }',
    ]
    for q in queries:  # warm parse/caches once
        engine.query(q)

    best_ratio = float("inf")
    for _attempt in range(3):
        GOVERNOR.set_budgets(0, 0)                 # unarmed fast path
        off = _hot_loop_secs(engine, queries, reps=5)
        GOVERNOR.set_budgets(device_bytes=1 << 40,
                             host_bytes=1 << 40)   # armed, uncontended
        on = _hot_loop_secs(engine, queries, reps=5)
        best_ratio = min(best_ratio, on / off)
        if best_ratio <= 1.05:
            break
    GOVERNOR.set_budgets(0, 0)
    assert best_ratio <= 1.05, (
        f"governor overhead {best_ratio:.3f}x exceeds the 5% budget "
        f"on the uncontended query path")


def test_debug_memory_endpoint_reports_the_lifecycle():
    """/debug/memory serves the governor snapshot: per-cache resident
    bytes + registrants + evictions against the budgets/watermarks, the
    OOM counters, and the sticky-degraded shapes the ISSUE's acceptance
    asserts are visible after an injected alloc fault."""
    a = Alpha(device_threshold=10**9)
    a.alter('name: string @index(exact) .')
    a.mutate(set_nquads='_:x <name> "alice" .')
    a.query('{ q(func: eq(name, "alice")) { name } }')
    GOVERNOR.set_budgets(host_bytes=64 << 20)
    # one injected repeat-OOM: exactly one evict-retry, then sticky
    memgov.set_alloc_fault(lambda site: site == "dbg.site")
    with pytest.raises(OomDegraded):
        memgov.oom_retry("dbg.site", "lanes=32", lambda: None)
    memgov.set_alloc_fault(None)

    srv = make_http_server(a)
    serve_background(srv)
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        with urllib.request.urlopen(base + "/debug/memory") as r:
            doc = json.loads(r.read())
        assert doc["budgets"]["host"]["budget_bytes"] == 64 << 20
        assert doc["budgets"]["host"]["high_bytes"] == \
            int((64 << 20) * HIGH_WATERMARK)
        # the serving path's caches are registered and byte-accounted
        assert "api.tablet" in doc["caches"]
        assert all(set(c) >= {"kind", "bytes", "registrants",
                              "evictions"} for c in doc["caches"].values())
        assert doc["oom"] == {"events": 1, "retries": 1}
        assert doc["degraded"] == [{"site": "dbg.site",
                                    "shape": "lanes=32", "count": 1}]
        # the inventory names the endpoint
        with urllib.request.urlopen(base + "/debug") as r:
            assert any(e["path"] == "/debug/memory"
                       for e in json.loads(r.read())["endpoints"])
    finally:
        srv.shutdown()


def test_flight_bundle_carries_the_memory_surface():
    """An OOM conviction's evidence: the flight bundle's `memory`
    surface is the same governor snapshot — budgets, caches, and the
    sticky-degraded shape that explains the dump."""
    GOVERNOR.set_budgets(device_bytes=8 << 20)
    memgov.set_alloc_fault(lambda site: site == "fb.site")
    with pytest.raises(OomDegraded):
        memgov.oom_retry("fb.site", "d4", lambda: None)
    memgov.set_alloc_fault(None)
    out = flightrec.dump(trigger="manual", reason={"why": "memtest"})
    mem = out["bundle"]["surfaces"]["memory"]
    assert mem["budgets"]["device"]["budget_bytes"] == 8 << 20
    assert {"site": "fb.site", "shape": "d4", "count": 1} \
        in mem["degraded"]
    assert mem["oom"]["events"] == 1
