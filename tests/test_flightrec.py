"""Flight recorder (ISSUE 13): bounded ring, predicted-cost watchdog,
one-shot diagnostic bundles, the live-HTTP acceptance path, the
`dgraph_tpu diagnose` verb, and the <5% armed-overhead tier-1 guard.

The load-bearing contracts:

  * a synthetic stalled request (costprior prediction tiny, handler
    sleeping) triggers EXACTLY ONE dump containing that request's
    Python stack, its trace spans, its prediction, and the admission
    snapshot — with no operator action;
  * deadline-carrying requests are judged only against their budget
    (cooperative cancellation fires first) — slow-but-inside-budget
    work never convicts, a wedge past budget+grace does;
  * disarmed, the module starts zero threads and every hook is inert;
  * the bundle JSON round-trips through disk and names every debug
    surface the HTTP layer serves.
"""

import json
import os
import signal
import threading
import time

import numpy as np
import pytest

from dgraph_tpu.server.api import Alpha
from dgraph_tpu.store import StoreBuilder, parse_schema
from dgraph_tpu.utils import costprior, costprofile
from dgraph_tpu.utils import deadline as dl
from dgraph_tpu.utils import flightrec, tracing
from dgraph_tpu.utils.metrics import METRICS

SURFACES = {"traces", "events", "costs", "scheduler", "admission",
            "locks", "races", "peers", "slow_queries", "memory",
            "timeseries"}


@pytest.fixture(autouse=True)
def _clean():
    flightrec.disarm()
    with flightrec._DUMPS_LOCK:
        del flightrec._DUMPS[:]
    costprior.reset()
    costprior.set_enabled(True)
    costprofile.reset()
    costprofile.set_enabled(True)
    yield
    flightrec.disarm()
    with flightrec._DUMPS_LOCK:
        del flightrec._DUMPS[:]
    costprior.reset()
    costprofile.reset()


def _wait_for(pred, timeout=10.0, step=0.01):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if pred():
            return True
        time.sleep(step)
    return False


def _stall_total(kind: str) -> float:
    return METRICS.get("watchdog_stalls_total", kind=kind)


# ---------------------------------------------------------------------------
# the ring

def test_ring_bounded_and_drops_counted():
    ring = flightrec.FlightRing(cap=8)
    d0 = METRICS.get("flight_ring_dropped_total", kind="filler")
    for i in range(20):
        ring.add("filler", {"i": i})
    events = ring.recent()
    assert len(events) == 8
    # oldest dropped: the survivors are the 8 newest
    assert [e["i"] for e in events] == list(range(12, 20))
    assert METRICS.get("flight_ring_dropped_total",
                       kind="filler") - d0 == 12
    assert ring.stats() == {"size": 8, "cap": 8, "added": 20}


def test_armed_ring_taps_spans_costs_and_emit(tmp_path):
    flightrec.arm(diag_dir=str(tmp_path), watchdog=False)
    # emit hook (the admission/breaker/maintenance/corruption sites)
    flightrec.emit("breaker.transition", peer="x:1", frm="closed",
                   to="open")
    # span sink: request-root spans always ring; fast child spans don't
    with tracing.span("request_root"):
        with tracing.span("micro_child"):
            pass
    # cost sink
    with costprofile.profile("read"):
        costprofile.add_shape("t")
    kinds = [e["kind"] for e in flightrec.state()["ring"]]
    assert "breaker.transition" in kinds
    assert "cost" in kinds
    names = [e.get("name") for e in flightrec.state()["ring"]
             if e["kind"] == "span"]
    assert "request_root" in names
    assert "micro_child" not in names  # sub-ms child: filtered


def test_disarmed_is_inert_and_starts_zero_threads():
    before = set(threading.enumerate())
    flightrec.emit("ghost", x=1)
    with flightrec.track("ghost-op") as op:
        assert op is None
    st = flightrec.state()
    assert st["armed"] is False and st["inflight"] == 0
    # a dump still builds (the pull path on an unarmed server) but
    # writes nothing and spawns nothing
    out = flightrec.dump(trigger="manual")
    assert out["path"] is None
    assert set(out["bundle"]["surfaces"]) == SURFACES
    assert set(threading.enumerate()) == before


def test_arm_starts_exactly_the_watchdog_and_disarm_stops_it(tmp_path):
    before = set(threading.enumerate())
    flightrec.arm(diag_dir=str(tmp_path))
    started = set(threading.enumerate()) - before
    assert [t.name for t in started] == ["dgraph-flight-watchdog"]
    flightrec.disarm()
    assert _wait_for(lambda: not any(t.is_alive() for t in started),
                     timeout=5.0)


# ---------------------------------------------------------------------------
# the watchdog

def _seed_tiny_prior(text: str, shape: str = "synthetic",
                     us: float = 400.0):
    """Teach the priors a TINY cost for `text` (the public learn path:
    text→shape memo + per-shape prior past the sample floor)."""
    for _ in range(costprior.SAMPLE_FLOOR):
        costprior.learn("read", text, shape, actual_us=us)


def test_stalled_request_triggers_exactly_one_dump(tmp_path):
    """The headline: a request whose costprior prediction is tiny but
    whose handler sleeps is convicted by the watchdog and dumped ONCE
    (rate limit), with the sleeping thread's stack in the bundle."""
    alpha = Alpha(device_threshold=10**9)
    q = "{ q(func: uid(1)) { name } }"
    _seed_tiny_prior(q)
    flightrec.arm(diag_dir=str(tmp_path), poll_s=0.02,
                  stall_factor=2.0, stall_floor_ms=1.0,
                  min_dump_interval_s=60.0, alpha=alpha)
    r0 = _stall_total("request")

    def worker():
        with alpha._request("read", None, query_text=q):
            time.sleep(0.8)

    t = threading.Thread(target=worker, name="stalled-request")
    t.start()
    assert _wait_for(lambda: flightrec.dumps(), timeout=5.0)
    t.join()
    dumps = flightrec.dumps()
    assert len(dumps) == 1
    assert dumps[0]["trigger"] == "watchdog"
    assert dumps[0]["reason"]["kind"] == "request"
    assert _stall_total("request") - r0 == 1
    files = [f for f in os.listdir(tmp_path) if f.startswith("flight-")]
    assert len(files) == 1
    bundle = json.loads((tmp_path / files[0]).read_text())
    ops = [o for o in bundle["inflight"] if o["name"] == "request.read"]
    assert ops and ops[0]["convicted"]
    assert ops[0]["predicted_us"] == pytest.approx(400.0, rel=0.5)
    assert "time.sleep" in ops[0]["stack"]
    assert set(bundle["surfaces"]) == SURFACES


def test_second_conviction_inside_interval_is_suppressed(tmp_path):
    alpha = Alpha(device_threshold=10**9)
    q = "{ q(func: uid(2)) { name } }"
    _seed_tiny_prior(q)
    flightrec.arm(diag_dir=str(tmp_path), poll_s=0.02,
                  stall_factor=2.0, stall_floor_ms=1.0,
                  min_dump_interval_s=60.0, alpha=alpha)

    def worker():
        with alpha._request("read", None, query_text=q):
            time.sleep(0.6)

    ts = [threading.Thread(target=worker) for _ in range(2)]
    for t in ts:
        t.start()
    wd = flightrec._STATE.watchdog
    assert _wait_for(lambda: wd.state()["convictions"] >= 2, timeout=5.0)
    for t in ts:
        t.join()
    st = wd.state()
    assert st["convictions"] == 2
    assert st["suppressed"] >= 1
    assert len(flightrec.dumps()) == 1  # rate limit: one bundle


def test_deadline_requests_judged_only_against_their_budget(tmp_path):
    """Fault-extended-deadline contract: a request grossly past its
    PREDICTION but inside its budget never convicts (cancellation owns
    that regime); one wedged past budget + grace does."""
    flightrec.arm(diag_dir=str(tmp_path), poll_s=0.02,
                  stall_factor=1.0, stall_floor_ms=1.0, grace_s=0.05,
                  min_dump_interval_s=60.0)
    w0 = _stall_total("wedged")
    ctx = dl.RequestContext(10_000.0)  # 10 s budget
    with flightrec.track("request.read", ctx=ctx, lane="read",
                         predicted_us=10.0):
        time.sleep(0.3)  # 30000× the prediction, inside the budget
    assert flightrec.dumps() == []
    ctx = dl.RequestContext(20.0)      # 20 ms budget, never checks it
    with flightrec.track("request.read", ctx=ctx, lane="read"):
        time.sleep(0.5)                # wedged: past budget + grace
    assert _stall_total("wedged") - w0 == 1
    dumps = flightrec.dumps()
    assert len(dumps) == 1 and dumps[0]["reason"]["kind"] == "wedged"


def test_explicit_budget_track_convicts_like_bench_stage(tmp_path):
    """track(name, budget_s=...) — a stage wedged past its deadline is
    convicted as `wedged` and the on_dump hook observes the bundle
    record."""
    seen = []
    flightrec.arm(diag_dir=str(tmp_path), poll_s=0.02, grace_s=0.02,
                  min_dump_interval_s=60.0,
                  on_dump=lambda rec, bundle: seen.append(rec))
    with flightrec.track("bench.stage2", budget_s=0.05):
        _wait_for(lambda: seen, timeout=5.0)
    assert seen and seen[0]["reason"]["op"]["name"] == "bench.stage2"
    assert seen[0]["reason"]["kind"] == "wedged"
    assert seen[0]["path"] and os.path.exists(seen[0]["path"])


def test_queue_head_stall_convicts(tmp_path):
    from types import SimpleNamespace

    from dgraph_tpu.server.admission import AdmissionController
    adm = AdmissionController(max_inflight=1, queue_depth=4)
    stub = SimpleNamespace(admission=adm, maintenance=None)
    flightrec.arm(diag_dir=str(tmp_path), poll_s=0.02,
                  stall_factor=1.0, stall_floor_ms=1.0,
                  min_dump_interval_s=60.0, alpha=stub)
    q0 = _stall_total("queue_head")
    release = threading.Event()
    entered = threading.Event()

    def holder():
        with adm.admit("read"):
            entered.set()
            release.wait(5.0)

    def waiter():
        entered.wait(5.0)
        with adm.admit("read"):
            pass

    th = threading.Thread(target=holder)
    tw = threading.Thread(target=waiter)
    th.start()
    tw.start()
    try:
        # head waits past factor × service EMA (seed 50 ms) → convict
        assert _wait_for(lambda: _stall_total("queue_head") - q0 >= 1,
                         timeout=5.0)
        assert _wait_for(lambda: flightrec.dumps(), timeout=5.0)
        assert flightrec.dumps()[0]["reason"]["kind"] == "queue_head"
    finally:
        release.set()
        th.join()
        tw.join()


def test_wedged_pusher_convicts(tmp_path):
    from types import SimpleNamespace

    from dgraph_tpu.utils.push import TelemetryPusher
    p = TelemetryPusher("http://127.0.0.1:1", interval_s=0.1)
    # never started: thread dead, but the sink buffer holds work
    p.offer_cost({"shape": "x"})
    flightrec.arm(diag_dir=str(tmp_path), poll_s=0.02,
                  min_dump_interval_s=60.0,
                  alpha=SimpleNamespace(admission=None,
                                        maintenance=None),
                  pusher=p)
    assert _wait_for(lambda: _stall_total("pusher") >= 1, timeout=5.0)
    assert _wait_for(lambda: flightrec.dumps(), timeout=5.0)
    assert flightrec.dumps()[0]["reason"]["kind"] == "pusher"


def test_sigusr2_dumps_a_bundle(tmp_path):
    flightrec.arm(diag_dir=str(tmp_path), poll_s=0.02, signals=True)
    os.kill(os.getpid(), signal.SIGUSR2)
    assert _wait_for(lambda: flightrec.dumps(), timeout=5.0)
    d = flightrec.dumps()[0]
    assert d["trigger"] == "sigusr2"
    assert d["path"] and os.path.exists(d["path"])
    flightrec.disarm()
    # handler restored: a second SIGUSR2 must not dump (nor kill us —
    # the previous handler here is pytest's default/ignore state)
    prev = signal.getsignal(signal.SIGUSR2)
    assert prev is not None


# ---------------------------------------------------------------------------
# the bundle

def test_bundle_roundtrips_and_names_every_surface(tmp_path):
    alpha = Alpha(device_threshold=10**9)
    alpha.attach_admission(2, 2)
    flightrec.arm(diag_dir=str(tmp_path), watchdog=False, alpha=alpha,
                  config={"p_dir": "p", "stall_factor": 10.0})
    flightrec.emit("storage.corruption", file="x.npz",
                   file_kind="segment")
    out = flightrec.dump(trigger="manual", reason={"why": "test"})
    path = out["path"]
    assert path and os.path.exists(path)
    loaded = json.loads(open(path).read())
    # disk round-trip is exactly the built bundle
    assert loaded == json.loads(json.dumps(out["bundle"], default=str))
    assert set(loaded["surfaces"]) == SURFACES
    assert loaded["surfaces"]["admission"]["enabled"] is True
    assert loaded["surfaces"]["peers"] == {"enabled": False}
    assert "dgraph_tpu_" in loaded["metrics"]
    assert loaded["config"]["stall_factor"] == 10.0
    assert any(e["kind"] == "storage.corruption"
               for e in loaded["ring"])
    assert loaded["trigger"] == "manual"
    assert loaded["reason"] == {"why": "test"}
    # all-thread stacks name this very test frame
    assert any("test_bundle_roundtrips" in s
               for s in loaded["stacks"].values())


# ---------------------------------------------------------------------------
# acceptance: live HTTP server, stalled query, zero operator actions

def _chain_alpha(chain_n=1200):
    b = StoreBuilder(parse_schema(
        "link: [uid] @reverse .\nname: string @index(exact) ."))
    uids = np.arange(1, chain_n, dtype=np.int64)
    b.add_edges("link", uids, uids + 1)
    b.add_value(chain_n + 5, "name", "island")  # unreachable
    a = Alpha(base=b.finalize(), device_threshold=10**9)
    q = ("{ path as shortest(from: 0x1, to: 0x%x, depth: %d) "
         "{ link } }" % (chain_n + 5, chain_n))
    return a, q


def test_http_acceptance_stalled_query_dumps_and_diagnose_pulls(
        tmp_path, capsys):
    """ISSUE-13 acceptance: a live HTTP server with a deliberately
    stalled query (sleep ≫ prediction — here a shortest grind whose
    prior was taught to be tiny) produces, with NO operator action, a
    bundle on disk containing the stalled request's Python stack, its
    trace spans, its shape's costprior prediction, and the admission
    snapshot — and `dgraph_tpu diagnose` fetches an equivalent bundle
    from the same server."""
    import urllib.request

    from dgraph_tpu import cli
    from dgraph_tpu.server.http import make_http_server, serve_background

    alpha, q = _chain_alpha()
    alpha.attach_admission(4, 8)
    _seed_tiny_prior(q, shape="shortest:link")
    diag = tmp_path / "diag"
    flightrec.arm(diag_dir=str(diag), poll_s=0.02, stall_factor=2.0,
                  stall_floor_ms=1.0, min_dump_interval_s=60.0,
                  alpha=alpha)
    srv = make_http_server(alpha)
    serve_background(srv)
    port = srv.server_address[1]
    base = f"http://127.0.0.1:{port}"
    try:
        done = threading.Event()

        def run_query():
            req = urllib.request.Request(
                base + "/query", data=q.encode(),
                headers={"Content-Type": "application/dql"})
            with urllib.request.urlopen(req) as r:
                r.read()
            done.set()

        threading.Thread(target=run_query, daemon=True).start()
        # no operator action: the watchdog writes the bundle itself
        assert _wait_for(
            lambda: diag.exists() and any(
                f.startswith("flight-watchdog")
                for f in os.listdir(diag)), timeout=20.0)
        assert done.wait(30.0)

        fname = next(f for f in os.listdir(diag)
                     if f.startswith("flight-watchdog"))
        bundle = json.loads((diag / fname).read_text())
        assert bundle["reason"]["kind"] == "request"
        # the convicted op's evidence is pinned at CONVICTION time, so
        # it survives even a stall that finishes before the bundle
        op = bundle["reason"]["op"]
        assert op["name"] == "request.read"
        # the stalled request's shape prediction (taught tiny)
        assert 0 < op["predicted_us"] < 10_000
        # its Python stack: the handler thread inside the grind
        assert "shortest" in op["stack"]
        # its trace spans: completed children of the live request
        assert op["trace_id"] and op["spans"]
        # the admission snapshot rode along
        adm = bundle["surfaces"]["admission"]
        assert adm["enabled"] is True and "lanes" in adm
        assert set(bundle["surfaces"]) == SURFACES

        # GET /debug/flightrecorder surfaces the same state
        with urllib.request.urlopen(
                base + "/debug/flightrecorder") as r:
            st = json.loads(r.read())
        assert st["armed"] is True
        assert any(d["trigger"] == "watchdog" for d in st["dumps"])

        # GET /debug lists the inventory (incl. this endpoint)
        with urllib.request.urlopen(base + "/debug") as r:
            idx = json.loads(r.read())["endpoints"]
        assert {"path": "/debug/flightrecorder",
                "doc": [e["doc"] for e in idx
                        if e["path"] == "/debug/flightrecorder"][0]} \
            in idx

        # `dgraph_tpu diagnose` pulls an equivalent bundle
        out_path = tmp_path / "pulled.json"
        rc = cli.main(["diagnose", f"127.0.0.1:{port}",
                       "--out", str(out_path)])
        assert rc == 0
        printed = json.loads(capsys.readouterr().out.strip()
                             .splitlines()[-1])
        assert printed["path"] == str(out_path)
        pulled = json.loads(out_path.read_text())
        assert pulled["trigger"] == "http"
        assert set(pulled["surfaces"]) == set(bundle["surfaces"])
        assert pulled["watchdog"]["convictions"] >= 1
        # the server also persisted the diagnose-triggered bundle
        assert printed["server_path"] and \
            os.path.exists(printed["server_path"])
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# tier-1 guard: the armed recorder must never become the regression

def _hot_loop_secs(alpha, queries, reps):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for q in queries:
            alpha.query(q)
        best = min(best, time.perf_counter() - t0)
    return best


def test_armed_overhead_under_5_percent(tmp_path):
    """Armed ring + watchdog (production posture) vs disarmed, on the
    served query path — mirroring test_tracing.py's guard. min-of-N
    interleaved best-of damps scheduler noise."""
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
    alpha = Alpha(base=b.finalize(), device_threshold=10**9)
    queries = [
        '{ q(func: ge(score, 8)) { name friend { name score } } }',
        '{ q(func: has(friend), first: 20) { name friend { friend '
        '{ name } } } }',
    ]
    for q in queries:  # warm parse/caches once
        alpha.query(q)

    best_ratio = float("inf")
    for _attempt in range(3):
        flightrec.disarm()
        off = _hot_loop_secs(alpha, queries, reps=5)
        flightrec.arm(diag_dir=str(tmp_path), poll_s=0.05,
                      alpha=alpha)
        on = _hot_loop_secs(alpha, queries, reps=5)
        best_ratio = min(best_ratio, on / off)
        if best_ratio <= 1.05:
            break
    assert best_ratio <= 1.05, (
        f"armed flight recorder overhead {best_ratio:.3f}x exceeds "
        f"the 5% budget on the hot query path")
