"""Retained metrics history + SLO engine (ISSUE 17): the sampler ring,
windowed burn-rate evaluation, the Holt load forecast, and the wired
surfaces (/debug/timeseries, /debug/slo, ?explain=true, flight-bundle
"timeseries", fleet merge).

Determinism discipline: every ring/engine test drives `sample(now=...)`
/ `evaluate(ring, now=...)` with explicit monotonic stamps against an
ISOLATED Registry — no sleeps, no daemon-thread timing in the math
assertions. The daemon itself is only exercised by the overhead guard
and the live-HTTP acceptance at the bottom.
"""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from dgraph_tpu.utils import flightrec, memgov, slo, timeseries
from dgraph_tpu.utils.metrics import METRICS, Registry
from dgraph_tpu.utils.timeseries import Forecast, Ring, _percentile


@pytest.fixture(autouse=True)
def _disarmed():
    """Every test starts and ends with the sampler + engine disarmed —
    an armed global sampler would leak into unrelated suites."""
    timeseries.disarm()
    yield
    timeseries.disarm()
    slo.uninstall()


# ---------------------------------------------------------------------------
# percentile + window math (deterministic, isolated registry)

def test_percentile_interpolation_deterministic():
    # ladder (100, 1000), counts [10, 10, 0]: ranks 1..10 interpolate
    # inside [0,100], 11..20 inside [100,1000]
    buckets = (100, 1000)
    assert _percentile(buckets, [10, 10, 0], 20, 0.50) == 100.0
    assert _percentile(buckets, [10, 10, 0], 20, 0.25) == 50.0
    assert _percentile(buckets, [10, 10, 0], 20, 1.00) == 1000.0
    # the +Inf slot clamps to the top finite bound — no invented tail
    assert _percentile(buckets, [0, 0, 5], 5, 0.99) == 1000.0
    assert _percentile(buckets, [0, 0, 0], 0, 0.99) == 0.0


def test_ring_sample_deltas_rates_and_hist_percentiles():
    reg = Registry()
    ring = Ring(points=64, registry=reg)
    assert ring.sample(now=0.0) is None       # first call baselines

    reg.inc("shed_total", value=4.0, lane="read", reason="queue_full")
    for _ in range(90):
        reg.observe("query_latency_us", 500, endpoint="query")
    for _ in range(10):
        reg.observe("query_latency_us", 50_000, endpoint="query")
    p = ring.sample(now=2.0)

    key = 'shed_total{lane="read",reason="queue_full"}'
    assert p["deltas"][key] == 4.0
    assert p["rates"][key] == pytest.approx(2.0)   # 4 over dt=2s
    h = p["hists"]['query_latency_us{endpoint="query"}']
    assert h["n"] == 100
    # 90 obs in (100,1000], 10 in (10k,100k]: rank 50 sits 50/90 into
    # the second bucket → 100 + 900·(5/9) = 600 — pure bucket math
    assert h["p50"] == pytest.approx(600.0)
    assert 10_000 < h["p99"] <= 100_000
    # a second tick with no traffic produces a point with no deltas
    p2 = ring.sample(now=3.0)
    assert p2["deltas"] == {} and p2["hists"] == {}

    w = ring.window(10.0, now=3.0)
    assert w.delta("shed_total") == 4.0
    bad, total = w.frac_above("query_latency_us", 1000.0)
    assert (bad, total) == (10.0, 100.0)
    assert w.percentile("query_latency_us", 0.5) == pytest.approx(600.0)


def test_ring_capacity_bound_and_drop_accounting():
    reg = Registry()
    ring = Ring(points=4, registry=reg)
    ring.sample(now=0.0)
    for i in range(1, 11):
        reg.inc("ticks_total")
        ring.sample(now=float(i))
    assert len(ring) == 4
    assert ring.points_total == 10
    assert ring.dropped_total == 6
    # retained points are the NEWEST ones
    ages = [p["t"] for p in ring.window(100.0, now=10.0).points]
    assert ages == [7.0, 8.0, 9.0, 10.0]


def test_ring_memgov_eviction_frees_oldest():
    assert "timeseries.ring" in memgov.GOVERNED_CACHES
    reg = Registry()
    ring = Ring(points=64, registry=reg)
    ring.sample(now=0.0)
    for i in range(1, 9):
        reg.inc("ticks_total")
        ring.sample(now=float(i))
    before_pts, before_bytes = len(ring), ring._resident_bytes()
    dropped0 = METRICS.get("ts_ring_dropped_total", reason="memgov")
    freed = ring._evict_one()
    assert freed > 0
    assert ring._resident_bytes() == before_bytes - freed
    k = before_pts - len(ring)
    assert k >= 1
    assert ring.dropped_total == k
    assert METRICS.get("ts_ring_dropped_total",
                       reason="memgov") == dropped0 + k
    # survivors are the newest — history is surrendered oldest-first
    assert ring.window(100.0, now=8.0).points[-1]["t"] == 8.0


# ---------------------------------------------------------------------------
# SLO engine: burn-rate windows, edge-triggered breaches, conviction feed

def _feed(reg, value, n):
    for _ in range(n):
        reg.observe("query_latency_us", value, endpoint="query")


def test_burn_rate_fast_window_breaches_slow_does_not(tmp_path):
    """A fresh latency regression burns the FAST window far past its
    threshold while the slow window (diluted by the healthy history)
    stays under — the page-vs-ticket split the two windows encode."""
    reg = Registry()
    ring = Ring(points=128, registry=reg)
    eng = slo.SloEngine({"read_latency_p99_us": 100_000.0},
                        fast_window_s=15.0, slow_window_s=1000.0,
                        fast_burn=14.0, slow_burn=2.0,
                        sustain_evals=2)
    ring.sample(now=0.0)
    for t in (10.0, 20.0, 30.0):          # healthy: 4000 fast obs/tick
        _feed(reg, 500, 4000)
        ring.sample(now=t)
    for t in (40.0, 50.0):                # regression: all obs over target
        _feed(reg, 5_000_000, 30)
        ring.sample(now=t)

    flightrec.arm(diag_dir=str(tmp_path), watchdog=False)
    try:
        states = eng.evaluate(ring, now=50.0)
        st = states["read_latency_p99_us"]
        fast, slow = st["windows"]["fast"], st["windows"]["slow"]
        # fast window holds only the two bad ticks: 100% bad on a 1%
        # budget = burn 100; slow dilutes 60 bad into 12060 total
        assert fast["bad_frac"] == pytest.approx(1.0)
        assert fast["burn"] >= 14.0 and fast["breached"]
        assert slow["burn"] < 2.0 and not slow["breached"]
        assert st["consec_fast"] == 1
        assert eng.breaches_total == 1
        assert eng.convictable() == []    # one breach is a page, not a verdict

        # steady state: still breached, but the edge already fired
        eng.evaluate(ring, now=50.0)
        assert eng.breaches_total == 1
        conv = eng.convictable()
        assert conv and conv[0]["slo"] == "read_latency_p99_us"
        assert conv[0]["consec_fast"] == 2

        # the breach landed in the flight ring with its burn evidence
        evs = [e for e in flightrec._STATE.ring.recent()
               if e["kind"] == "slo.breach"]
        assert evs and evs[-1]["slo"] == "read_latency_p99_us"
        assert evs[-1]["window"] == "fast"
        assert evs[-1]["burn"] >= 14.0

        # recovery resets the consecutive-breach counter
        _feed(reg, 500, 4000)
        ring.sample(now=60.0)
        st2 = eng.evaluate(ring, now=60.0)["read_latency_p99_us"]
        assert not st2["windows"]["fast"]["breached"]
        assert st2["consec_fast"] == 0 and eng.convictable() == []
    finally:
        flightrec.disarm()


def test_error_and_shed_rate_objectives():
    reg = Registry()
    ring = Ring(points=64, registry=reg)
    eng = slo.SloEngine({"error_rate": 0.01, "shed_rate": 0.05},
                        fast_window_s=10.0, slow_window_s=10.0,
                        fast_burn=14.0, slow_burn=14.0)
    ring.sample(now=0.0)
    _feed(reg, 500, 80)
    reg.inc("query_errors_total", value=20.0)
    reg.inc("admission_requests_total", value=100.0, lane="read")
    reg.inc("shed_total", value=50.0, lane="read", reason="queue_full")
    ring.sample(now=5.0)
    states = eng.evaluate(ring, now=5.0)
    err = states["error_rate"]["windows"]["fast"]
    assert err["bad_frac"] == pytest.approx(0.2)       # 20 / (80+20)
    assert err["burn"] == pytest.approx(20.0) and err["breached"]
    shed = states["shed_rate"]["windows"]["fast"]
    assert shed["bad_frac"] == pytest.approx(0.5)      # 50 / 100
    assert shed["burn"] == pytest.approx(10.0)         # budget 0.05
    assert not shed["breached"]                        # 10 < 14
    # empty history burns nothing (no division blowups on total=0)
    empty = Ring(points=8, registry=Registry())
    st = slo.SloEngine().evaluate(empty, now=0.0)
    assert all(not w["breached"] and w["burn"] == 0.0
               for s in st.values() for w in s["windows"].values())


# ---------------------------------------------------------------------------
# Holt forecast + the admission off-path contract

def test_forecast_holt_trend_deterministic():
    fc = Forecast(alpha=0.5, beta=0.3, horizon_s=30.0, margin=2.0)
    fc.update("read", 10.0)               # baseline: level 10, trend 0
    fc.update("read", 20.0, dt=1.0)
    # level = .5*20 + .5*(10+0) = 15; trend = .3*(15-10) = 1.5
    assert fc.predicted_rate("read") == pytest.approx(15.0 + 1.5 * 30.0)
    assert fc.predicted_demand("read", 100_000.0) == pytest.approx(6.0)
    assert fc.should_shed("read", 100_000.0, max_inflight=1)   # 6 > 2
    assert not fc.should_shed("read", 100_000.0, max_inflight=10)
    # a lane with no samples has no signal — it never sheds
    assert not fc.should_shed("mutate", 10**9, max_inflight=1)
    assert fc.status()["sheds"] == 1


def test_forecast_probe_off_path_and_admission_shed():
    from dgraph_tpu.server.admission import (AdmissionController,
                                             ServerOverloaded)
    # disarmed: the probe is one global load + None check → never sheds
    assert timeseries.state() is None
    assert not timeseries.forecast_probe("read", 10**9, 1)
    # armed with forecast=False keeps the SAME off-path (no Forecast
    # object exists at all — the --no-forecast_shedding contract)
    timeseries.arm(interval_s=60.0, ring_points=16, forecast=False,
                   start_thread=False)
    assert timeseries._FORECAST is None
    assert not timeseries.forecast_probe("read", 10**9, 1)

    # a saturated lane with forecast off sheds for queue_full, never
    # for "forecast" — admission behavior is identical to disarmed
    ac = AdmissionController(max_inflight=1, queue_depth=0)
    lane = ac.lanes["read"]
    lane.acquire(cost_us=1000.0)
    with pytest.raises(ServerOverloaded):
        lane.acquire(cost_us=1000.0)
    assert lane.shed_total == 1
    fsheds0 = METRICS.get("forecast_sheds_total", lane="read")

    # armed WITH forecast + a hot predicted rate: the probe sheds the
    # queued arrival before the queue even fills
    timeseries.arm(interval_s=60.0, ring_points=16, forecast=True,
                   start_thread=False)
    timeseries._FORECAST.update("read", 100.0)
    timeseries._FORECAST.update("read", 200.0, dt=1.0)
    assert timeseries.forecast_probe("read", 1_000_000.0, 1)
    ac2 = AdmissionController(max_inflight=1, queue_depth=8)
    lane2 = ac2.lanes["read"]
    lane2.acquire(cost_us=1000.0)
    with pytest.raises(ServerOverloaded) as ei:
        lane2.acquire(cost_us=1_000_000.0)
    assert ei.value.retry_after_s > 0
    assert METRICS.get("forecast_sheds_total", lane="read") == fsheds0 + 1
    assert METRICS.get("shed_total", lane="read", reason="forecast") >= 1


def test_arm_disarm_lifecycle_and_status():
    eng = slo.SloEngine(fast_window_s=5.0, slow_window_s=20.0)
    s = timeseries.arm(interval_s=60.0, ring_points=32, slo_engine=eng,
                       forecast=True, start_thread=False)
    assert timeseries.state() is s and slo.ENGINE is eng
    # re-arm replaces (idempotent — cli restart / bench stages re-arm)
    s2 = timeseries.arm(interval_s=60.0, ring_points=32,
                        start_thread=False)
    assert timeseries.state() is s2 and s2 is not s
    assert slo.ENGINE is None            # the replaced engine uninstalled
    doc = timeseries.status()
    assert doc["armed"] and "names" in doc and "ring" in doc
    timeseries.disarm()
    assert timeseries.status() == {"armed": False}
    assert timeseries.recent_window() is None


# ---------------------------------------------------------------------------
# tier-1 guard: retained history must never become the regression

def _hot_loop_secs(engine, queries, reps):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for q in queries:
            engine.query(q)
        best = min(best, time.perf_counter() - t0)
    return best


def test_armed_sampler_overhead_under_5_percent():
    """The serving default (sampler daemon + SLO engine + forecast all
    armed) must stay within 5% of the disarmed path over the same hot
    loop test_tracing's guard uses — the ring reads the registry from
    its OWN thread; the query path pays nothing."""
    from dgraph_tpu.engine import Engine
    from dgraph_tpu.store import StoreBuilder, parse_schema

    rng = np.random.default_rng(11)
    n = 512
    b = StoreBuilder(parse_schema(
        "name: string @index(exact) .\n"
        "score: int @index(int) .\nfriend: [uid] @reverse ."))
    for i in range(1, n + 1):
        b.add_value(i, "name", f"p{i}")
        b.add_value(i, "score", i % 17)
        for j in rng.integers(1, n + 1, 4):
            b.add_edge(i, "friend", int(j))
    store = b.finalize()
    engine = Engine(store, device_threshold=10**9)
    queries = [
        '{ q(func: ge(score, 8)) { name friend { name score } } }',
        '{ q(func: has(friend), first: 20) { name friend { friend '
        '{ name } } } }',
    ]
    for q in queries:  # warm parse/caches once
        engine.query(q)

    best_ratio = float("inf")
    for _attempt in range(3):
        timeseries.disarm()
        off = _hot_loop_secs(engine, queries, reps=5)
        timeseries.arm(interval_s=0.05, ring_points=512,
                       slo_engine=slo.SloEngine(fast_window_s=5.0,
                                                slow_window_s=30.0),
                       forecast=True)
        on = _hot_loop_secs(engine, queries, reps=5)
        timeseries.disarm()
        best_ratio = min(best_ratio, on / off)
        if best_ratio <= 1.05:
            break
    assert best_ratio <= 1.05, (
        f"armed sampler overhead {best_ratio:.3f}x exceeds the 5% "
        f"budget on the hot query path")


# ---------------------------------------------------------------------------
# live-HTTP acceptance: breach → exemplar → debug surfaces → bundle → fleet

@pytest.fixture()
def alpha():
    from dgraph_tpu.server.api import Alpha
    a = Alpha(device_threshold=10**9)
    a.alter("name: string @index(exact) .\nfriend: [uid] @reverse .")
    a.mutate(set_nquads="""
        _:a <name> "alice" .
        _:b <name> "bob" .
        _:a <friend> _:b .
    """)
    return a


def _serve(alpha):
    from dgraph_tpu.server.http import make_http_server, serve_background
    srv = make_http_server(alpha)
    serve_background(srv)
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


def _get(url):
    with urllib.request.urlopen(url) as r:
        return json.loads(r.read())


def _post_query(base, path="/query", headers=None):
    req = urllib.request.Request(
        base + path,
        data=b'{ q(func: eq(name, "alice")) { name friend { name } } }',
        headers={"Content-Type": "application/dql", **(headers or {})})
    with urllib.request.urlopen(req) as r:
        return json.loads(r.read()), dict(r.headers)


def test_explain_echoes_cost_breakdown(alpha):
    srv, base = _serve(alpha)
    try:
        # off-path: no explain requested → the envelope carries none
        out, headers = _post_query(base)
        assert "explain" not in out["extensions"]
        assert "X-Explain" not in headers

        out, headers = _post_query(base, path="/query?explain=true")
        assert headers.get("X-Explain") == "true"
        doc = out["extensions"]["explain"]
        # the EXISTING cost record (utils/costprofile), joined by the
        # response's own trace id — no new accounting
        assert doc["trace_id"] == out["extensions"]["trace_id"]
        assert "note" in doc or "total_us" in doc or "route" in doc

        # header spelling reaches the same breakdown
        out, headers = _post_query(base, headers={"X-Explain": "true"})
        assert headers.get("X-Explain") == "true"
        assert out["extensions"]["explain"]["trace_id"] == \
            out["extensions"]["trace_id"]
    finally:
        srv.shutdown()


def test_query_errors_counted_per_lane_any_transport(alpha):
    """error_rate's bad events are counted in the api._request
    lifecycle, so a failed serve burns the budget whether it arrived
    over HTTP, gRPC, or an embedded call."""
    before = METRICS.get("query_errors_total", lane="read")
    with pytest.raises(Exception):
        alpha.query("{ this is not dql")          # embedded caller
    assert METRICS.get("query_errors_total", lane="read") == before + 1
    srv, base = _serve(alpha)
    try:
        req = urllib.request.Request(
            base + "/query", data=b"{ this is not dql",
            headers={"Content-Type": "application/dql"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 400               # HTTP surface intact
        assert METRICS.get("query_errors_total",
                           lane="read") == before + 2
    finally:
        srv.shutdown()


def test_breach_exemplar_and_debug_surfaces_live(alpha, tmp_path):
    """The acceptance chain: induced latency regression → fast-window
    burn breach → flight event whose exemplar trace id resolves at
    /debug/traces → /debug/timeseries + /debug/slo + the flight
    bundle's "timeseries" surface + the fleet merge all agree."""
    from dgraph_tpu.server import fleet

    alpha.slow_query_ms = 0.001      # everything slow-logs with its tid
    eng = slo.SloEngine({"read_latency_p99_us": 1.0},
                        fast_window_s=30.0, slow_window_s=60.0,
                        fast_burn=1.0, slow_burn=10**9,
                        sustain_evals=2)
    sampler = timeseries.arm(interval_s=3600.0, ring_points=64,
                             slo_engine=eng, forecast=False,
                             start_thread=False)
    flightrec.arm(diag_dir=str(tmp_path), watchdog=False)
    srv, base = _serve(alpha)
    try:
        sampler.tick()               # baseline
        tids = []
        for _ in range(3):
            out, _ = _post_query(base)
            tids.append(out["extensions"]["trace_id"])
        sampler.tick()               # point + evaluate → breach

        # the breach event carries an exemplar trace id from the
        # slow-query ring — one of OUR requests, newest first
        evs = [e for e in flightrec._STATE.ring.recent()
               if e["kind"] == "slo.breach"
               and e["slo"] == "read_latency_p99_us"]
        assert evs and evs[-1]["window"] == "fast"
        exemplar = evs[-1]["trace_id"]
        assert exemplar in tids
        # a request's root closes once its response is on the wire
        for _ in range(400):
            spans = _get(base + f"/debug/traces?trace_id={exemplar}"
                         )["spans"]
            if any(s["name"] == "http.query" for s in spans):
                break
            time.sleep(0.005)
        assert spans and {s["name"] for s in spans} >= {"http.query"}
        assert all(s["trace_id"] == exemplar for s in spans)

        # /debug/slo: armed, fast breached, slow (threshold 1e9) not
        doc = _get(base + "/debug/slo")
        st = doc["states"]["read_latency_p99_us"]
        assert doc["armed"] and st["windows"]["fast"]["breached"]
        assert not st["windows"]["slow"]["breached"]
        assert doc["breaches_total"] >= 1

        # /debug/timeseries: the retained latency series, with rates
        doc = _get(base + "/debug/timeseries?name=query_latency_us")
        key = 'query_latency_us{endpoint="query"}'
        assert doc["armed"] and key in doc["series"]
        assert doc["series"][key][-1]["n"] == 3
        names = _get(base + "/debug/timeseries")["names"]
        assert key in names["hists"]
        # counters serve raw deltas under ?rate=false (ts_points_total
        # increments AFTER each sample, so its first delta needs tick 3)
        sampler.tick()
        doc = _get(base + "/debug/timeseries?name=ts_points_total"
                          "&rate=false&window=600")
        assert any(pt["value"] >= 1.0
                   for pts in doc["series"].values() for pt in pts)

        # both endpoints are advertised in the /debug index
        paths = {e["path"] for e in _get(base + "/debug")["endpoints"]}
        assert {"/debug/timeseries", "/debug/slo"} <= paths

        # flight bundle: the "timeseries" surface retains the approach
        bundle = flightrec.dump(trigger="manual", write=False)["bundle"]
        ts = bundle["surfaces"]["timeseries"]
        assert ts["points"] and ts["summary"]["query_latency"]["n"] == 3
        assert ts["slo"]["read_latency_p99_us"]["windows"]["fast"][
            "breached"]

        # fleet merge: the node fragment + the cluster worst-burn view
        frag = fleet.node_snapshot(alpha)
        assert frag["timeseries"]["points"] >= 1
        assert frag["slo"]["states"]["read_latency_p99_us"]
        merged = fleet.fleet_snapshot(alpha)["slo"]
        worst = merged["worst_burn"]["read_latency_p99_us"]["fast"]
        assert worst["breached"] and worst["burn"] >= 1.0
        assert merged["breaches_total"] >= 1
    finally:
        srv.shutdown()
        flightrec.disarm()
        alpha.slow_query_ms = 0.0
