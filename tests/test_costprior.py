"""Cost-prior scheduling (ISSUE 9): prior lifecycle (fit determinism,
persistence through checkpoint/reopen, unseen-shape fallback), the
admission layer's cost-aware decisions (SJF handoff, displacement,
idle-EMA cold start), the A/B acceptance (priors-on beats priors-off on
cheap-query p99 and shed precision under a fixed seed), the
/debug/scheduler surface, and the <5% uncontended hot-path overhead
guard mirroring test_admission.py's.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from dgraph_tpu.server.admission import AdmissionController, ServerOverloaded
from dgraph_tpu.server.api import Alpha
from dgraph_tpu.store import StoreBuilder, parse_schema
from dgraph_tpu.utils import costprior, costprofile
from dgraph_tpu.utils.costprofile import Aggregator
from dgraph_tpu.utils.costprior import BLEND, CostPriorModel
from dgraph_tpu.utils.metrics import METRICS


@pytest.fixture(autouse=True)
def _clean():
    costprior.reset()
    costprofile.reset()
    costprior.set_enabled(True)
    yield
    costprior.set_enabled(True)
    costprior.reset()
    costprofile.reset()


# ---------------------------------------------------------------------------
# prior lifecycle

def _fixed_agg():
    agg = Aggregator()
    rng = np.random.default_rng(42)
    for shape, base in (("q:eq~d1", 500), ("recurse:friend~d3", 80_000)):
        for _ in range(32):
            agg.record({"shape": shape,
                        "total_us": int(base + rng.integers(0, base)),
                        "lanes": 32, "depth": 3, "queries": 1})
    return agg


def test_refit_is_deterministic_for_a_fixed_digest_set():
    """Two models refit from the same digests agree bit-for-bit, and
    the prediction is the documented percentile blend."""
    agg = _fixed_agg()
    m1, m2 = CostPriorModel(), CostPriorModel()
    s1 = m1.refit(agg)
    s2 = m2.refit(agg)
    assert s1 == s2
    assert m1.to_state() == m2.to_state()
    assert s1["shapes_fitted"] == 2
    with agg._lock:
        d = agg._shapes["q:eq~d1"].digests["total_us"]
        p50, p90 = d.percentile(0.50), d.percentile(0.90)
    assert m1.predict_shape("q:eq~d1") == pytest.approx(
        p50 + BLEND * (p90 - p50))
    # the cheap shape predicts cheap, the expensive one expensive
    assert m1.predict_shape("q:eq~d1") * 10 \
        < m1.predict_shape("recurse:friend~d3")


def test_unseen_shape_falls_back_to_lane_ema():
    m = CostPriorModel()
    m.refit(_fixed_agg())
    # unseen text AND unseen shape → fallback; the lane EMA is learned
    # from completed requests of that lane, whatever their shape
    before = METRICS.get("cost_prior_fallbacks_total", lane="read")
    us, src = m.predict("read", text="{ never seen }")
    assert src == "fallback" and us > 0
    assert METRICS.get("cost_prior_fallbacks_total",
                       lane="read") == before + 1
    m.learn("read", "{ never seen }", "q:weird~d9", 4_000.0)
    us2, src2 = m.predict("read", text="{ another novel }")
    assert src2 == "fallback"
    assert us2 == pytest.approx(4_000.0)  # first observation seeds EMA
    # the learned text now maps to its shape, but the shape is below
    # the sample floor → still the graceful fallback, never a raise
    us3, src3 = m.predict("read", text="{ never seen }")
    assert src3 == "fallback"
    # once the shape crosses the floor, the prior takes over
    for _ in range(m.sample_floor):
        m.learn("read", "{ never seen }", "q:weird~d9", 4_000.0)
    us4, src4 = m.predict("read", text="{ never seen }")
    assert src4 == "prior" and us4 == pytest.approx(4_000.0, rel=0.2)
    assert METRICS.get("cost_prior_hits_total", lane="read") >= 1


def test_persistence_round_trip_through_checkpoint_and_open(tmp_path):
    """Alpha.checkpoint_to writes costpriors.json beside
    costprofiles.json; Alpha.open merges it back AND fills unseen
    shapes from the digests (merge-on-boot, like the digests)."""
    a = Alpha(device_threshold=10**9)
    a.alter("name: string @index(exact) .")
    a.mutate(set_nquads='_:a <name> "x" .')
    q = '{ q(func: eq(name, "x")) { name } }'
    for _ in range(costprior.PRIORS.sample_floor + 2):
        a.query(q)
    us_before, src_before = costprior.predict("read", text=q)
    assert src_before == "prior"
    p_dir = str(tmp_path / "p")
    a.checkpoint_to(p_dir)
    state = json.loads((tmp_path / "p" / "costpriors.json").read_text())
    assert "q:eq~d1" in state["shapes"]
    n_persisted = state["shapes"]["q:eq~d1"]["n"]
    assert n_persisted >= costprior.PRIORS.sample_floor

    costprior.reset()
    costprofile.reset()
    a2 = Alpha.open(p_dir)
    # the merged model predicts without a single new observation (the
    # text→shape memo is process-local, so look up by shape)
    assert costprior.PRIORS.predict_shape("q:eq~d1") == pytest.approx(
        us_before, rel=0.5)
    st = costprior.PRIORS.to_state()
    assert st["shapes"]["q:eq~d1"]["n"] >= n_persisted
    assert a2.mvcc.base.n_nodes >= 1
    # merging the same file twice n-weights rather than duplicating
    n1 = costprior.PRIORS.to_state()["shapes"]["q:eq~d1"]["n"]
    assert costprior.load(str(tmp_path / "p" / "costpriors.json"))
    assert costprior.PRIORS.to_state()["shapes"]["q:eq~d1"]["n"] \
        == n1 + n_persisted
    # corrupt/missing files are a no-op, never a boot failure
    assert not costprior.load(str(tmp_path / "absent.json"))
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert not costprior.load(str(bad))


# ---------------------------------------------------------------------------
# admission: cost-aware handoff + displacement + idle-EMA cold start

def _hold_token(adm, lane, started, release, cost_us=None):
    def run():
        with adm.admit(lane, cost_us=cost_us):
            started.set()
            release.wait(10)
    t = threading.Thread(target=run, daemon=True)
    t.start()
    started.wait(5)
    return t


def _wait_queued(adm, lane, n, timeout=5.0):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if len(adm.lanes[lane].waiters) >= n:
            return True
        time.sleep(0.001)
    return False


def test_release_hands_token_to_cheapest_predicted_waiter():
    """SJF: with predictions present, release picks the cheapest
    waiter, not the oldest — FIFO only breaks ties."""
    adm = AdmissionController(1, 8)
    started, release = threading.Event(), threading.Event()
    holder = _hold_token(adm, "read", started, release, cost_us=1e6)
    order = []
    costs = [500_000.0, 1_000.0, 250_000.0, 1_000.0]
    workers = []
    for i, c in enumerate(costs):
        def run(i=i, c=c):
            with adm.admit("read", cost_us=c):
                order.append(i)
        t = threading.Thread(target=run)
        t.start()
        workers.append(t)
        assert _wait_queued(adm, "read", i + 1)
    release.set()
    for t in workers:
        t.join(5)
    holder.join(5)
    # cheapest first; equal costs in arrival order
    assert order == [1, 3, 2, 0], order


def test_cheap_arrival_displaces_most_expensive_queued():
    """Full queue + a cheap arrival: the costliest queued waiter is
    shed (reason="displaced"), the cheap request takes its slot."""
    adm = AdmissionController(1, 1)
    before = METRICS.get("shed_total", lane="read", reason="displaced")
    started, release = threading.Event(), threading.Event()
    holder = _hold_token(adm, "read", started, release, cost_us=1e6)
    shed = []

    def expensive():
        try:
            with adm.admit("read", cost_us=900_000.0):
                pass
        except ServerOverloaded as e:
            shed.append(e)

    exp = threading.Thread(target=expensive)
    exp.start()
    assert _wait_queued(adm, "read", 1)
    admitted = []

    def cheap():
        with adm.admit("read", cost_us=1_000.0):
            admitted.append(True)

    ch = threading.Thread(target=cheap)
    ch.start()
    exp.join(5)
    assert shed and shed[0].retry_after_s > 0
    assert METRICS.get("shed_total", lane="read",
                       reason="displaced") == before + 1
    release.set()
    ch.join(5)
    holder.join(5)
    assert admitted == [True]
    st = adm.status()["lanes"]["read"]
    assert st["inflight"] == 0 and st["queued"] == 0
    # an EQUALLY expensive arrival does NOT displace (strictly-greater
    # rule): it is shed itself with reason="queue_full"
    started2, release2 = threading.Event(), threading.Event()
    holder2 = _hold_token(adm, "read", started2, release2, cost_us=1e6)
    blocked = []

    def waiter():
        with adm.admit("read", cost_us=500.0):
            pass
    w = threading.Thread(target=waiter)
    w.start()
    assert _wait_queued(adm, "read", 1)
    with pytest.raises(ServerOverloaded):
        with adm.admit("read", cost_us=500.0):
            blocked.append(True)
    assert not blocked
    release2.set()
    w.join(5)
    holder2.join(5)


def test_idle_lane_ema_decays_to_seed():
    """Satellite: an idle lane's stale service-time EMA resets after
    the idle window, so post-quiet Retry-After hints aren't shaped by
    the last burst — and with no shape prior the (decayed) EMA is the
    graceful fallback."""
    from dgraph_tpu.server.admission import _EMA_SEED_S
    adm = AdmissionController(1, 0)
    lane = adm.lanes["read"]
    # a burst of slow requests drives the EMA up
    for _ in range(12):
        with adm.admit("read"):
            pass
        lane.service_ema_s = lane.service_ema_s + 0.2 * (5.0 -
                                                         lane.service_ema_s)
    assert lane.service_ema_s > 1.0
    with lane.lock:  # _retry_after_s is a caller-holds-the-lock helper
        stale_hint = lane._retry_after_s(1)  # one slot ahead × stale EMA
    # simulate the idle window having elapsed
    lane._last_activity = time.monotonic() - lane.idle_reset_s - 1.0
    started, release = threading.Event(), threading.Event()
    holder = _hold_token(adm, "read", started, release)  # triggers decay
    assert lane.service_ema_s == pytest.approx(_EMA_SEED_S)
    with lane.lock:
        fresh_hint = lane._retry_after_s(1)
    assert fresh_hint < stale_hint / 10
    # queue_depth=0: the next arrival sheds with the DECAYED hint
    with pytest.raises(ServerOverloaded) as ei:
        with adm.admit("read"):
            pass
    assert ei.value.retry_after_s <= fresh_hint * 2 + 0.011
    release.set()
    holder.join(5)
    # within the idle window nothing decays
    lane.service_ema_s = 3.0
    lane._last_activity = time.monotonic()
    with lane.lock:  # caller-holds-the-lock helper
        lane._maybe_decay_ema(time.monotonic())
    assert lane.service_ema_s == 3.0


# ---------------------------------------------------------------------------
# acceptance: priors-on beats priors-off (fixed seed), /debug/scheduler

def run_sched_workload(priors_on: bool, chain_n: int = 2000,
                       n_expensive: int = 3, n_cheap: int = 6,
                       queue_depth: int = 4, seed: int = 23) -> dict:
    """Mixed cheap/expensive serving under admission pressure — the
    cost-prior A/B harness of the acceptance test below.

    One token, a bounded queue: an EXPENSIVE query (shortest-path grind
    over a `chain_n` uid chain hunting an unreachable island) holds the
    token while more expensive queries queue; CHEAP name lookups then
    arrive. With priors OFF the cheap arrivals queue FIFO behind the
    expensive ones or get shed at the full queue (sheds land on cheap
    work). With priors ON the scheduler predicts each arrival's cost
    from its warmed shape prior: cheap queries displace queued
    expensive ones (sheds land on the expensive work) and drain first
    (SJF handoff). Reports cheap p50/p99 µs over COMPLETED cheap
    queries, shed counts by kind, and shed precision = expensive sheds
    / total sheds."""
    import threading as _threading

    from dgraph_tpu.server.admission import ServerOverloaded
    from dgraph_tpu.server.api import Alpha
    from dgraph_tpu.store import StoreBuilder, parse_schema
    from dgraph_tpu.utils import costprior, costprofile

    costprior.reset()
    costprofile.reset()
    floor0 = costprior.PRIORS.sample_floor
    costprior.PRIORS.sample_floor = 2  # 2 warm runs arm a prior
    try:
        b = StoreBuilder(parse_schema(
            "link: [uid] @reverse .\nname: string @index(exact) ."))
        uids = np.arange(1, chain_n, dtype=np.int64)
        b.add_edges("link", uids, uids + 1)
        for i in range(1, 65):
            b.add_value(i, "name", f"p{i}")
        b.add_value(chain_n + 5, "name", "island")  # unreachable
        alpha = Alpha(base=b.finalize(), device_threshold=10**9)
        alpha.cost_priors = priors_on

        exp_q = ("{ path as shortest(from: 0x1, to: 0x%x, depth: %d) "
                 "{ link } }" % (chain_n + 5, chain_n))
        rng = np.random.default_rng(seed)
        cheap_qs = ['{ q(func: eq(name, "p%d")) { name } }' % i
                    for i in rng.integers(1, 65, n_cheap)]

        # warm uncontended: parse caches + (priors on) text→shape memo
        # and per-shape priors past the (lowered) sample floor
        for _ in range(2):
            alpha.query(exp_q)
            for q in cheap_qs:
                alpha.query(q)

        adm = alpha.attach_admission(max_inflight=1,
                                     queue_depth=queue_depth)
        results = {"cheap_us": [], "shed": {"cheap": 0, "expensive": 0},
                   "ok": {"cheap": 0, "expensive": 0}}
        lock = _threading.Lock()

        def run(q: str, kind: str):
            t0 = time.perf_counter()
            try:
                alpha.query(q)
                us = (time.perf_counter() - t0) * 1e6
                with lock:
                    results["ok"][kind] += 1
                    if kind == "cheap":
                        results["cheap_us"].append(us)
            except ServerOverloaded:
                with lock:
                    results["shed"][kind] += 1

        threads = []

        def submit(q, kind):
            t = _threading.Thread(target=run, args=(q, kind))
            t.start()
            threads.append(t)

        def wait_for(pred, timeout=10.0):
            end = time.monotonic() + timeout
            while time.monotonic() < end:
                if pred():
                    return True
                time.sleep(0.002)
            return False

        lane = adm.lanes["read"]

        def lane_state():
            # under the lane lock: request threads mutate these and the
            # race sanitizer (rightly) convicts an unlocked poll
            with lane.lock:
                return lane.inflight, len(lane.waiters)

        submit(exp_q, "expensive")
        wait_for(lambda: lane_state()[0] >= 1)
        for _ in range(n_expensive - 1):
            submit(exp_q, "expensive")
        wait_for(lambda: lane_state()[1] >= n_expensive - 1)
        for q in cheap_qs:
            submit(q, "cheap")
            time.sleep(0.01)
        for t in threads:
            t.join(60)

        lats = sorted(results["cheap_us"])
        sheds = results["shed"]["cheap"] + results["shed"]["expensive"]
        out = {
            "priors": priors_on,
            "cheap_completed": len(lats),
            "cheap_p50_us": round(lats[len(lats) // 2]) if lats else 0,
            "cheap_p99_us": round(lats[min(len(lats) - 1,
                                           int(len(lats) * 0.99))])
            if lats else 0,
            "shed_cheap": results["shed"]["cheap"],
            "shed_expensive": results["shed"]["expensive"],
            "shed_precision": (results["shed"]["expensive"] / sheds
                               if sheds else None),
            "expensive_ok": results["ok"]["expensive"],
        }
        if priors_on:
            st = costprior.status()
            out["prior"] = {"hits": st["hits"],
                            "fallbacks": st["fallbacks"],
                            "error": st["error"]}
        return out
    finally:
        costprior.PRIORS.sample_floor = floor0


def test_sched_acceptance_priors_on_beats_off():
    """ISSUE 9 acceptance: on the mixed cheap/expensive workload
    (run_sched_workload, fixed seed), priors-on beats priors-off
    on BOTH cheap-query p99 and shed precision."""
    off = run_sched_workload(priors_on=False, chain_n=1500, seed=23)
    on = run_sched_workload(priors_on=True, chain_n=1500, seed=23)
    assert on["cheap_completed"] >= off["cheap_completed"]
    assert on["cheap_p99_us"] < off["cheap_p99_us"], (on, off)
    off_prec = off["shed_precision"] or 0.0
    assert on["shed_precision"] is not None
    assert on["shed_precision"] > off_prec, (on, off)
    # predicted-vs-actual error was recorded during the on-run
    assert on["prior"]["error"]["n"] >= 1


def test_debug_scheduler_surfaces_priors_and_error():
    from dgraph_tpu.server.http import make_http_server, serve_background

    a = Alpha(device_threshold=10**9)
    a.alter("name: string @index(exact) .")
    a.mutate(set_nquads='_:a <name> "x" .')
    a.attach_admission(max_inflight=4, queue_depth=4)
    q = '{ q(func: eq(name, "x")) { name } }'
    for _ in range(costprior.PRIORS.sample_floor + 3):
        a.query(q)
    srv = make_http_server(a, port=0)
    serve_background(srv)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.server_address[1]}"
                f"/debug/scheduler") as r:
            doc = json.loads(r.read())
        assert doc["enabled"] is True
        assert doc["shapes"] >= 1
        assert doc["hits"] >= 1 and doc["fallbacks"] >= 1
        assert doc["error"]["n"] >= 1          # predicted-vs-actual
        assert doc["top"][0]["shape"] == "q:eq~d1"
        assert doc["lane_ema_us"]["read"] > 0
        assert doc["admission"]["lanes"]["read"]["inflight"] == 0
        # the shed's prediction joins the cost profile record
        rec = costprofile.recent(1)[0]
        assert rec["predicted_us"] > 0
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# tier-1 guard: the scheduler must never become the regression

def _hot_loop_secs(alpha, queries, reps):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for q in queries:
            alpha.query(q)
        best = min(best, time.perf_counter() - t0)
    return best


def test_costprior_hot_path_overhead_under_5_percent():
    """The serving path with cost-prior scheduling armed (the default:
    predict + learn per request, admission cost accounting) must stay
    within 5% of the same path with it disabled — mirroring
    test_admission.py's guard (min-of-N both sides, best ratio of 3)."""
    rng = np.random.default_rng(17)
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
    alpha.attach_admission(max_inflight=64, queue_depth=64)
    queries = [
        '{ q(func: ge(score, 8)) { name friend { name score } } }',
        '{ q(func: has(friend), first: 20) { name friend { friend '
        '{ name } } } }',
    ]
    for q in queries:  # warm parse/caches + shape memo once
        alpha.query(q)

    best_ratio = float("inf")
    for _attempt in range(3):
        alpha.cost_priors = False
        off = _hot_loop_secs(alpha, queries, reps=5)
        alpha.cost_priors = True
        on = _hot_loop_secs(alpha, queries, reps=5)
        best_ratio = min(best_ratio, on / off)
        if best_ratio <= 1.05:
            break
    assert best_ratio <= 1.05, (
        f"cost-prior overhead {best_ratio:.3f}x exceeds the 5% budget "
        f"on the uncontended query path")
