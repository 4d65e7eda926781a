"""Tracing correctness: span identity, nesting, trace ids, Chrome export,
and the observability-overhead tier-1 guard.

Reference parity: OpenCensus span semantics (unique span ids, parent
links) the reference gets from the library; ours is hand-rolled so the
invariants are pinned here — in particular the historical bug where the
thread-local parent was tracked by span NAME, aliasing concurrent (and
nested) spans that share a name.
"""

import json
import threading
import time

import numpy as np
import pytest

from dgraph_tpu.utils import tracing


@pytest.fixture(autouse=True)
def _clean():
    tracing.clear()
    tracing.set_enabled(True)
    yield
    tracing.set_enabled(True)
    tracing.clear()


def _by_id(spans):
    return {s.span_id: s for s in spans}


def test_nested_spans_have_distinct_ids_and_parent_links():
    with tracing.span("outer") as so:
        with tracing.span("inner") as si:
            pass
    assert so.span_id != si.span_id
    assert si.parent_id == so.span_id
    assert so.parent_id == 0


def test_nested_same_name_spans_do_not_alias():
    """The regression the span-id redesign fixes: nested spans sharing a
    name must keep distinct identities and a correct parent chain (the
    name-keyed thread-local could not represent this)."""
    with tracing.span("work") as a:
        with tracing.span("work") as b:
            with tracing.span("work") as c:
                pass
    assert len({a.span_id, b.span_id, c.span_id}) == 3
    assert c.parent_id == b.span_id
    assert b.parent_id == a.span_id
    assert a.parent_id == 0


def test_concurrent_same_name_spans_keep_thread_local_parents():
    """Two threads running same-named span trees concurrently: every
    inner span's parent must be ITS thread's outer span, never the
    other thread's (name-keyed tracking aliased exactly this)."""
    barrier = threading.Barrier(2)
    results = {}

    def worker(tag):
        barrier.wait()
        with tracing.span("work", tag=tag) as outer:
            barrier.wait()  # both outers open before any inner opens
            with tracing.span("work", tag=tag) as inner:
                barrier.wait()
        results[tag] = (outer, inner)

    ts = [threading.Thread(target=worker, args=(t,)) for t in ("a", "b")]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for tag, (outer, inner) in results.items():
        assert inner.parent_id == outer.span_id, tag
        assert inner.tid == outer.tid, tag
    ids = [s.span_id for pair in results.values() for s in pair]
    assert len(set(ids)) == 4


def test_trace_context_groups_spans_and_exports_chrome_json():
    with tracing.trace("request") as tid:
        with tracing.span("child", k="v"):
            pass
    assert tid and tracing.current_trace_id() == ""
    spans = tracing.trace_spans(tid)
    names = [s.name for s in spans]
    assert names == ["child", "request"]  # children complete first
    assert all(s.trace_id == tid for s in spans)
    root = spans[-1]
    assert spans[0].parent_id == root.span_id

    doc = tracing.to_chrome(spans)
    # must survive a JSON round trip and carry the complete-event form
    doc2 = json.loads(json.dumps(doc))
    assert len(doc2["traceEvents"]) == 2
    for ev in doc2["traceEvents"]:
        assert ev["ph"] == "X"
        assert ev["dur"] >= 1
        assert isinstance(ev["ts"], int)
        assert ev["args"]["trace_id"] == tid
    child = next(e for e in doc2["traceEvents"] if e["name"] == "child")
    assert child["args"]["k"] == "v"


def test_otlp_export_round_trips(tmp_path):
    """OTLP/JSON export (ROADMAP: span export to an external collector):
    the document carries the OTLP shape a collector's /v1/traces
    accepts — resourceSpans/scopeSpans, 32-hex traceId, 16-hex spanId,
    nanosecond timestamps, typed attributes — and `from_otlp` restores
    the exact Span objects (identity, nesting, timing, attrs)."""
    with tracing.trace("request") as tid:
        with tracing.span("child", k="v", n=3, ratio=1.5, flag=True):
            pass
    spans = tracing.trace_spans(tid)
    doc = json.loads(json.dumps(tracing.to_otlp(spans)))  # JSON-clean

    rs = doc["resourceSpans"][0]
    svc = rs["resource"]["attributes"][0]
    assert svc["key"] == "service.name"
    otlp_spans = rs["scopeSpans"][0]["spans"][0:]
    assert len(otlp_spans) == 2
    for o in otlp_spans:
        assert len(o["traceId"]) == 32
        assert len(o["spanId"]) == 16
        assert int(o["endTimeUnixNano"]) >= int(o["startTimeUnixNano"])
    child = next(o for o in otlp_spans if o["name"] == "child")
    root = next(o for o in otlp_spans if o["name"] == "request")
    assert child["parentSpanId"] == root["spanId"]
    attrs = {a["key"]: a["value"] for a in child["attributes"]}
    assert attrs["k"] == {"stringValue": "v"}
    assert attrs["n"] == {"intValue": "3"}          # int64 as string
    assert attrs["ratio"] == {"doubleValue": 1.5}
    assert attrs["flag"] == {"boolValue": True}

    back = tracing.from_otlp(doc)
    assert [s.to_dict() for s in back] == [s.to_dict() for s in spans]

    # file form (--trace_export's shutdown hook)
    p = tmp_path / "spans.otlp.json"
    n = tracing.export_otlp(str(p), spans)
    assert n == 2
    again = tracing.from_otlp(json.loads(p.read_text()))
    assert [s.to_dict() for s in again] == [s.to_dict() for s in spans]


def test_otlp_handles_non_hex_trace_ids():
    """trace() accepts arbitrary trace_id strings (tests do) — export
    must not crash on them and the raw id still round-trips via the
    dgraph.trace_id attribute."""
    with tracing.trace("t", trace_id="not-hex!"):
        pass
    spans = tracing.trace_spans("not-hex!")
    doc = tracing.to_otlp(spans)
    back = tracing.from_otlp(doc)
    assert [s.trace_id for s in back] == ["not-hex!"] * len(spans)


def test_disabled_tracing_records_nothing():
    tracing.set_enabled(False)
    with tracing.span("ghost") as sp:
        sp.attrs["x"] = 1  # the null sink accepts attr writes
    assert tracing.recent(10) == []


def test_ring_buffer_and_trace_index_bounded():
    for i in range(tracing._MAX_TRACES + 10):
        with tracing.trace(f"t{i}"):
            pass
    with tracing._LOCK:
        assert len(tracing._TRACES) <= tracing._MAX_TRACES


# ---------------------------------------------------------------------------
# phases: a span's two further outlets

def _phase_series(span: str) -> dict:
    """{endpoint: (sum, count)} of phase_us for one span name."""
    from dgraph_tpu.utils.metrics import METRICS
    out = {}
    for series, h in METRICS.hist_snapshot().items():
        if series.startswith("phase_us{") and f'span="{span}"' in series:
            ep = series.split('endpoint="', 1)[1].split('"', 1)[0]
            out[ep] = (h["sum"], h["n"])
    return out


def test_phase_span_feeds_phase_us_once_with_its_duration():
    with tracing.span("t.phase_once", phase=True) as sp:
        time.sleep(0.002)
    with tracing.span("t.phase_once"):       # unflagged: a span only
        pass
    assert sp.dur_us >= 2000
    assert _phase_series("t.phase_once") == {"": (float(sp.dur_us), 1)}
    assert [s.name for s in tracing.recent(2)] == ["t.phase_once"] * 2


def test_root_endpoint_reaches_nested_phases_on_its_thread_only():
    other = {}

    def elsewhere():
        with tracing.span("t.phase_ep", phase=True) as sp:
            pass
        other["span"] = sp

    with tracing.trace("http.t", endpoint="t_endpoint") as tid:
        with tracing.span("outer"):
            with tracing.span("t.phase_ep", phase=True) as inner:
                pass
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    with tracing.span("t.phase_ep", phase=True) as after:
        pass
    got = _phase_series("t.phase_ep")
    assert got["t_endpoint"] == (float(inner.dur_us), 1)
    # another thread, and this one once the request is over: no endpoint
    assert got[""] == (float(other["span"].dur_us + after.dur_us), 2)
    assert inner.trace_id == tid and other["span"].trace_id == ""


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: records, in one list
    shared with the fake start/stop, what was entered and left."""
    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


@pytest.fixture()
def fake_profiler(monkeypatch):
    import jax
    log = _FakeAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, profiler_options=None: log.append(
            ("start", d, profiler_options.python_tracer_level,
             profiler_options.host_tracer_level)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: log.append(("stop",)))
    yield log
    if tracing.profile_status()["running"]:
        tracing.profile_stop()


def test_spans_are_trace_annotations_only_while_a_capture_runs(
        fake_profiler, tmp_path):
    import jax

    log = fake_profiler
    with tracing.span("before"):
        pass
    assert log == []
    tracing.profile_start(str(tmp_path))
    # the capture leaves the Python tracer out and XLA's host events in
    default_host_level = jax.profiler.ProfileOptions().host_tracer_level
    assert log == [("start", str(tmp_path), 0, default_host_level)]
    with tracing.span("outer", phase=True):
        with tracing.span("inner"):
            pass
    assert log[1:] == [("enter", "outer"), ("enter", "inner"),
                       ("exit", "inner"), ("exit", "outer")]
    tracing.profile_stop()
    del log[:]
    with tracing.span("after"):
        pass
    assert log == []


def test_profile_stop_marks_the_end_before_it_stops(fake_profiler,
                                                    tmp_path):
    log = fake_profiler
    tracing.profile_start(str(tmp_path))
    del log[:]
    tracing.profile_stop()
    assert tracing.PROFILE_STOP_MARKER.endswith("stop_trace")
    assert log == [("enter", tracing.PROFILE_STOP_MARKER),
                   ("exit", tracing.PROFILE_STOP_MARKER), ("stop",)]


# ---------------------------------------------------------------------------
# the served path's phases

BATCH_PHASES = ["http.decode", "admission.admit", "mvcc.read_view",
                "batch.plan", "batch.seed", "batch.device_wait",
                "batch.fetch", "batch.scan", "batch.walk_back",
                "batch.render", "http.encode"]


def _chain_alpha(n: int):
    """p0 -> p1 -> ... -> p(n-1), and a shortcut p0 -> p2."""
    from dgraph_tpu.server.api import Alpha
    a = Alpha(device_threshold=10**9)
    a.alter("name: string @index(exact) .\nfollows: [uid] @reverse .")
    lines = [f'_:p{i} <name> "p{i}" .' for i in range(n)]
    lines += [f"_:p{i} <follows> _:p{i + 1} ." for i in range(n - 1)]
    lines.append("_:p0 <follows> _:p2 .")
    uids = a.mutate(set_nquads="\n".join(lines))["uids"]
    return a, [uids[f"_:p{i}"] for i in range(n)]


def _shortest(u_from: str, u_to: str) -> str:
    return ('{ path as shortest(from: %s, to: %s) { follows } '
            'p(func: uid(path)) { name } }' % (u_from, u_to))


def _wait_for_root(tid: str, name: str) -> list:
    """The request's spans once its root has closed: it closes after the
    response is on the wire, so a client can be here first."""
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        spans = tracing.trace_spans(tid)
        if spans and spans[-1].name == name:
            return spans
        time.sleep(0.005)
    raise AssertionError(f"{name} of trace {tid} never closed")


def test_query_batch_opens_each_phase_once_and_they_cover_the_request():
    import urllib.request

    from dgraph_tpu.server.http import make_http_server, serve_background

    alpha, u = _chain_alpha(13)
    alpha.attach_admission(max_inflight=4, queue_depth=4)
    alpha.slow_query_ms = 0.0001            # every request is slow
    srv = make_http_server(alpha)
    serve_background(srv)
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    # 64 distinct pairs, none over 8 hops apart: one launch
    pairs = [(i, j) for i in range(12) for j in range(i + 1, 13)
             if j - i <= 8][:64]
    assert len(pairs) == 64
    qs = [_shortest(u[i], u[j]) for i, j in pairs]
    try:
        for attempt in ("cold", "warm"):
            if attempt == "warm":           # new texts: a plan-cache miss
                qs = [q.replace("p(func", "r(func") for q in qs]
            before = {p: _phase_series(p).get("query_batch", (0, 0))
                      for p in BATCH_PHASES}
            req = urllib.request.Request(
                base + "/query/batch",
                data=json.dumps({"queries": qs}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as r:
                out = json.loads(r.read())
            assert len(out["data"]) == 64
            tid = out["extensions"]["trace_id"]
            spans = _wait_for_root(tid, "http.query_batch")
            root = spans[-1]
            by_name = {}
            for s in spans:
                by_name.setdefault(s.name, []).append(s)
            for p in BATCH_PHASES:
                assert len(by_name.get(p, ())) == 1, (attempt, p)
                s, n = _phase_series(p)["query_batch"]
                assert (s - before[p][0], n - before[p][1]) == \
                    (by_name[p][0].dur_us, 1), (attempt, p)
            # the phases are a constant number of spans a request; one a
            # query is only `engine.block`, the companion block's own
            assert len(by_name["engine.block"]) == 64
            assert len(spans) - 64 < 25
            kernel = by_name["batch.shortest_kernel"][0]
            for p in ("batch.device_wait", "batch.fetch", "batch.scan"):
                assert by_name[p][0].parent_id == kernel.span_id
            assert by_name["batch.fetch"][0].attrs["bytes"] > 0
            # what no phase names is the shell between them: some tenths
            # of a millisecond, which shows only against a warm batch on
            # a store this small (20 ms)
            covered = sum(by_name[p][0].dur_us for p in BATCH_PHASES)
            least = 0.95 if attempt == "cold" else 0.8
            assert least * root.dur_us <= covered <= root.dur_us, (
                attempt, covered, root.dur_us,
                {p: by_name[p][0].dur_us for p in BATCH_PHASES})
        # the slow-query ring carries the request's phase table
        from dgraph_tpu.server.http import slow_queries_snapshot
        entry = slow_queries_snapshot(tid)[-1]
        assert set(BATCH_PHASES[:-1]) <= set(entry["phases_ms"])
        assert entry["phases_ms"]["batch.device_wait"] == round(
            by_name["batch.device_wait"][0].dur_us / 1000.0, 1)
    finally:
        srv.shutdown()


def test_hops_used_is_the_longest_found_path_of_the_launch():
    from dgraph_tpu.engine.batch import SHORTEST_STAGE
    from dgraph_tpu.utils.metrics import METRICS

    alpha, u = _chain_alpha(12)

    def hops():
        return (METRICS.get("kernel_hops_run_total", family="shortest"),
                METRICS.get("kernel_hops_used_total", family="shortest"))

    # p0 -> p6 is 5 edges by the shortcut; the others are shorter
    # (four queries: a smaller group is served one by one)
    run0, used0 = hops()
    out = alpha.query_batch([_shortest(u[0], u[6]), _shortest(u[1], u[3]),
                             _shortest(u[4], u[5]), _shortest(u[0], u[2])])
    lengths = [len(o["p"]) - 1 for o in out]
    assert lengths == [5, 2, 1, 1]
    run1, used1 = hops()
    # the launch stops itself at the hop that closes its last lane, the
    # one that reaches a row two edges before the farthest target, two
    # hops short of the path's edges: the device's count of hops run is
    # the host's count of hops used
    assert (run1 - run0, used1 - used0) == (max(lengths) - 2,
                                            max(lengths) - 2)
    # a lane still open at the stage's end uses the whole stage: p0 -> p11
    # is 10 edges, p9 shows at the stage's last hop
    alpha.query_batch([_shortest(u[0], u[11])] +
                      [_shortest(u[i], u[i + 1]) for i in range(3)])
    run2, used2 = hops()
    assert (run2 - run1, used2 - used1) == (SHORTEST_STAGE, SHORTEST_STAGE)
    assert used2 - used0 <= run2 - run0


# ---------------------------------------------------------------------------
# tier-1 guard: observability must never become the regression

class _CountingLock:
    """A lock that counts how often the thread that made it takes it."""

    def __init__(self):
        self._lock = threading.Lock()
        self._thread = threading.get_ident()
        self.taken = 0

    def __enter__(self):
        if threading.get_ident() == self._thread:
            self.taken += 1
        return self._lock.__enter__()

    def __exit__(self, *exc):
        return self._lock.__exit__(*exc)


def _best_secs(fn, reps: int) -> float:
    """The fastest of `reps` calls: load only ever adds time."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def test_query_path_overhead_under_5_percent(monkeypatch):
    """The instrumented query path (spans + counters armed, the serving
    default) must stay within 5% of the same path with observability
    disarmed, over test_query.py's kind of hot loop.

    The promise is held by counting, not by a ratio of two wall-clock
    loops (the armed share is 0.5%, far under what a loaded machine adds
    to either loop): what a disarmed span and a disarmed registry do at
    all, how many recordings the armed loop makes, and what one
    recording costs. Only that last step reads a clock, against a budget
    ten times its reading."""
    import jax

    from dgraph_tpu.engine import Engine
    from dgraph_tpu.store import StoreBuilder, parse_schema
    from dgraph_tpu.utils.metrics import METRICS

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

    def loop():
        for q in queries:
            engine.query(q)

    loop()   # warm parse/caches once

    span_lock, reg_lock = _CountingLock(), _CountingLock()
    monkeypatch.setattr(tracing, "_LOCK", span_lock)
    monkeypatch.setattr(METRICS, "_lock", reg_lock)
    annotations = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: annotations.append(name))

    # disarmed: a span is one flag check and the shared null span (no
    # id drawn, so no Span made), a recording one flag check; no lock
    tracing.set_enabled(False)
    METRICS.set_enabled(False)
    try:
        with tracing.span("ghost", phase=True, k=1) as sp:
            assert sp is tracing._NULL_SPAN
        next_id = next(tracing._IDS)
        loop()
        assert next(tracing._IDS) == next_id + 1
        assert (span_lock.taken, reg_lock.taken) == (0, 0)
        off = _best_secs(loop, reps=15)
    finally:
        tracing.set_enabled(True)
        METRICS.set_enabled(True)

    # armed, no capture running: each span takes the registry of spans
    # once, each recording the registry of metrics once, and nothing
    # reaches the profiler (the `_PROFILE_DIR is None` fast path)
    assert tracing.profile_status()["running"] is False
    span_lock.taken = reg_lock.taken = 0
    loop()
    spans, recordings = span_lock.taken, reg_lock.taken
    assert annotations == []
    assert 0 < spans <= 16 and 0 < recordings <= 32, (spans, recordings)

    def one_span():
        with tracing.span("t.unit", phase=True, k=1):
            pass

    def unit_secs(fn, n=500):
        def many():
            for _ in range(n):
                fn()
        return _best_secs(many, reps=9) / n

    # a phase span is the dearest kind: it also records (one lock each)
    span_s = unit_secs(one_span)
    rec_s = unit_secs(lambda: METRICS.observe("t_unit_us", 5.0, k="v"))
    share = (spans * span_s + recordings * rec_s) / off
    assert share <= 0.05, (
        f"observability costs {share:.1%} of the hot query path: "
        f"{spans} spans at {span_s * 1e6:.1f} us and {recordings} "
        f"recordings at {rec_s * 1e6:.1f} us in {off * 1e3:.2f} ms")


# ---------------------------------------------------------------------------
# what the thread was doing: CPU beside wall, and what takes it off its CPU
# (counts and wide inequalities: no test here holds a clock to a budget)

def _counter(name: str, **labels) -> float:
    from dgraph_tpu.utils.metrics import METRICS
    return METRICS.get(name, **labels)


def _hist(name: str, **labels) -> tuple:
    """(sum, count) of one histogram series, (0, 0) where it is not."""
    from dgraph_tpu.utils.metrics import METRICS, _label_key, _series
    h = METRICS.hist_snapshot().get(_series(name, _label_key(labels)))
    return (h["sum"], h["n"]) if h else (0.0, 0)


@pytest.fixture()
def armed():
    """`tracing.arm()` as `alpha` calls it, and the collector's hook
    taken out again: the other tests' process stays as it was."""
    import gc
    tracing.arm()
    yield
    gc.callbacks.remove(tracing._gc_hook)
    tracing._GC_PENDING.clear()
    del tracing._GC_OPEN[:]


def test_a_sleeping_phase_is_off_cpu_and_a_spinning_one_on():
    with tracing.trace("http.t", endpoint="t_cpu"):
        with tracing.span("t.sleeps", phase=True) as slept:
            time.sleep(0.05)
        with tracing.span("t.spins", phase=True) as spun:
            t0 = time.thread_time_ns()
            while time.thread_time_ns() - t0 < 30_000_000:
                pass
    assert slept.dur_us >= 50_000
    assert slept.cpu_us + slept.sys_us < 10_000
    off = _counter("phase_offcpu_us_total", span="t.sleeps",
                   endpoint="t_cpu")
    assert off >= 40_000
    assert off == slept.dur_us - slept.cpu_us - slept.sys_us
    # the spin ran 30 ms of thread time by its own reading, all of it
    # inside the phase's wall time (the clocks are read just outside it)
    on = spun.cpu_us + spun.sys_us
    assert 30_000 <= on <= spun.dur_us + 1_000
    assert (_counter("phase_cpu_us_total", span="t.spins",
                     endpoint="t_cpu", mode="user"),
            _counter("phase_cpu_us_total", span="t.spins",
                     endpoint="t_cpu", mode="sys")) \
        == (spun.cpu_us, spun.sys_us)
    assert _counter("phase_offcpu_us_total", span="t.spins",
                    endpoint="t_cpu") == max(spun.dur_us - on, 0)


def test_a_coarse_clock_overdraws_a_phase_and_the_next_one_pays(
        monkeypatch):
    """The chip's machine counts a thread's CPU in ticks of 10 ms: the
    phase a tick lands in is billed all of it. What that bills beyond
    the phase's wall time is owed by the series' next off-CPU time, so
    the window's sum is its wall less its CPU."""
    ticks = iter([0, 10, 10, 10, 10, 20])      # ms of thread CPU so far
    monkeypatch.setattr(
        tracing, "_thread_clocks",
        lambda: (next(ticks) * 1_000_000, 0, 0, 0, 0, 0, 0, 0))
    spans = []
    for sleep_s in (0.001, 0.012, 0.001):
        with tracing.span("t.ticked", phase=True) as sp:
            time.sleep(sleep_s)
        spans.append(sp)
    assert [s.cpu_us for s in spans] == [10_000, 0, 10_000]
    wall = sum(s.dur_us for s in spans)
    assert 14_000 <= wall < 20_000     # 14 ms slept: the last tick is owed
    off = _counter("phase_offcpu_us_total", span="t.ticked", endpoint="")
    # the first phase overdrew 10 ms less its own wall; the second paid
    assert off == spans[0].dur_us + spans[1].dur_us - 10_000 > 0
    assert tracing._OFFCPU_OWED["t.ticked", ""] \
        == 10_000 - spans[2].dur_us


def test_a_root_bills_its_request_and_feeds_no_phase_series():
    from dgraph_tpu.utils.metrics import METRICS
    with tracing.trace("http.t", endpoint="t_root") as tid:
        with tracing.span("t.inner", phase=True):
            time.sleep(0.002)            # one voluntary switch at least
    root = tracing.trace_spans(tid)[-1]
    assert root.name == "http.t" and root.cpu_us + root.sys_us > 0
    assert _counter("request_ctx_switches_total", endpoint="t_root",
                    kind="voluntary") >= 1
    rendered = METRICS.render()
    for series in ('request_ctx_switches_total{endpoint="t_root",'
                   'kind="involuntary"}',
                   'request_page_faults_total{endpoint="t_root",'
                   'kind="minor"}',
                   'request_page_faults_total{endpoint="t_root",'
                   'kind="major"}',
                   'request_other_cpu_us_total{endpoint="t_root"}'):
        assert "dgraph_tpu_" + series + " " in rendered, series
    # a root is no phase: only `t.inner` has phase series
    assert 'span="http.t"' not in rendered


@pytest.mark.parametrize("enabled", [False, True])
def test_thread_clocks_are_read_at_a_phase_or_root_and_nowhere_else(
        monkeypatch, enabled):
    real, calls = tracing._thread_clocks, []

    def counting():
        calls.append(1)
        return real()

    monkeypatch.setattr(tracing, "_thread_clocks", counting)
    tracing.set_enabled(enabled)
    seen = []
    with tracing.trace("http.t", endpoint="t_count"):
        seen.append(len(calls))          # the root's open
        with tracing.span("t.plain"):
            with tracing.span("t.plain2", k=1):
                pass
        seen.append(len(calls))          # spans that are neither: none
        with tracing.span("t.phase", phase=True):
            seen.append(len(calls))      # the phase's open
        seen.append(len(calls))          # its close
    seen.append(len(calls))              # the root's close
    with tracing.span("t.plain3"):
        pass
    seen.append(len(calls))
    assert seen == ([1, 1, 2, 3, 4, 4] if enabled else [0] * 6)


def test_gc_collect_inside_a_phase_is_counted_and_a_child_span(
        armed, monkeypatch):
    import gc
    monkeypatch.setattr(tracing, "GC_SPAN_MIN_US", 0)
    was = gc.isenabled()
    gc.disable()                 # the one collection is the explicit one
    try:
        before = {(g, on): _hist("gc_pause_us", gen=g, on=on)
                  for g in range(3) for on in ("request", "background")}
        with tracing.trace("http.t", endpoint="t_gc") as tid:
            with tracing.span("t.collects", phase=True) as ph:
                gc.collect()
        gc.collect(0)            # and one outside any request
        after = {k: _hist("gc_pause_us", gen=k[0], on=k[1])
                 for k in before}
    finally:
        if was:
            gc.enable()
    grew = {k: after[k][1] - before[k][1] for k in before}
    assert grew == {**dict.fromkeys(before, 0), (2, "request"): 1,
                    (0, "background"): 1}
    spans = tracing.trace_spans(tid)
    pauses = [s for s in spans if s.name == "gc.collect"]
    assert len(pauses) == 1 and pauses[0].parent_id == ph.span_id
    assert pauses[0].attrs["gen"] == 2 and "collected" in pauses[0].attrs
    assert pauses[0].tid == ph.tid
    assert after[2, "request"][0] - before[2, "request"][0] \
        == pauses[0].dur_us <= ph.dur_us
    # the background one is in the ring, outside any trace
    assert [s.trace_id for s in tracing.recent(50)
            if s.name == "gc.collect"] == [tid, ""]


def test_a_short_pause_feeds_the_histogram_only_and_disarmed_nothing(
        armed, monkeypatch):
    import gc
    monkeypatch.setattr(tracing, "GC_SPAN_MIN_US", 10**9)
    n0 = _hist("gc_pause_us", gen=0, on="background")[1]
    gc.collect(0)
    tracing.set_enabled(False)
    gc.collect(0)
    tracing.set_enabled(True)
    assert _hist("gc_pause_us", gen=0, on="background")[1] == n0 + 1
    assert not [s for s in tracing.recent(50) if s.name == "gc.collect"]


def test_a_collection_is_a_host_event_of_a_running_capture(
        armed, fake_profiler, tmp_path):
    import gc
    gc.collect(0)
    assert fake_profiler == []
    tracing.profile_start(str(tmp_path))
    del fake_profiler[:]
    was = gc.isenabled()
    gc.disable()
    try:
        gc.collect(1)
    finally:
        if was:
            gc.enable()
    assert fake_profiler == [("enter", "gc.collect"),
                             ("exit", "gc.collect")]


def test_arm_creates_at_zero_what_no_request_feeds(armed):
    from dgraph_tpu.utils.metrics import METRICS
    rendered = METRICS.render()
    for g in range(3):
        for on in ("request", "background"):
            assert (f'dgraph_tpu_gc_pause_us_count{{gen="{g}",on="{on}"}} '
                    in rendered)
    for thread in tracing.BACKGROUND_THREADS:
        assert (f'dgraph_tpu_background_ticks_total{{thread="{thread}"}} '
                in rendered)
    # the launches' own counts: the look-ahead's (PR 41) and the slots of
    # the pushed hops (PR 44), which a window of pulls alone leaves at 0
    for by in ("seed", "seed2", "ahead", "ahead2", "exhausted"):
        assert ('dgraph_tpu_kernel_lanes_closed_total{by="%s",'
                'family="shortest"} ' % by in rendered)
    for name in ("near2_edges", "near2_capped"):
        assert f"dgraph_tpu_kernel_{name}_total " in rendered
    for family in ("shortest", "tree"):
        assert ('dgraph_tpu_kernel_push_slots_total{family="%s"} '
                % family in rendered)
    import gc
    assert gc.callbacks.count(tracing._gc_hook) == 1
    tracing.arm()                        # twice is once
    assert gc.callbacks.count(tracing._gc_hook) == 1


def test_background_bills_one_tick_and_its_thread_time():
    ticks = _counter("background_ticks_total", thread="t_daemon")
    cpu = _counter("background_cpu_us_total", thread="t_daemon")
    with tracing.background("t_daemon"):
        t0 = time.thread_time_ns()
        while time.thread_time_ns() - t0 < 2_000_000:
            pass
    assert _counter("background_ticks_total", thread="t_daemon") \
        == ticks + 1
    assert _counter("background_cpu_us_total", thread="t_daemon") \
        >= cpu + 2_000
    tracing.set_enabled(False)
    with tracing.background("t_daemon"):
        pass
    assert _counter("background_ticks_total", thread="t_daemon") \
        == ticks + 1


def test_every_daemon_loop_bills_its_tick():
    """The five call sites, by their label: each loop's tick runs inside
    `tracing.background(<its name>)`."""
    import inspect

    from dgraph_tpu import cli
    from dgraph_tpu.store import maintenance
    from dgraph_tpu.utils import flightrec, push, timeseries
    sites = {"flightrec": flightrec.Watchdog._loop,
             "timeseries": timeseries.Sampler._loop,
             "maintenance": maintenance.MaintenanceScheduler._loop,
             "heartbeat": cli.run_heartbeat_loop,
             "push": push.TelemetryPusher._run}
    assert sorted(sites) == sorted(tracing.BACKGROUND_THREADS)
    for thread, fn in sites.items():
        assert f'tracing.background("{thread}")' in inspect.getsource(fn)
    # and one of them driven: a heartbeat step is one tick
    ticks = _counter("background_ticks_total", thread="heartbeat")
    stop = threading.Event()
    cli.run_heartbeat_loop("t", 0.001, stop.set, None, stop=stop)
    assert _counter("background_ticks_total", thread="heartbeat") \
        == ticks + 1


def test_jit_call_on_a_seen_key_feeds_jit_dispatch_us():
    from dgraph_tpu.utils import jitcache
    key = ("t.kernel", 7)
    with jitcache.jit_call("t.dispatch", key) as compiling:
        assert compiling
    assert _hist("jit_dispatch_us", kernel="t.dispatch") == (0.0, 0)
    with jitcache.jit_call("t.dispatch", key) as compiling:
        assert not compiling
        time.sleep(0.002)
    total, n = _hist("jit_dispatch_us", kernel="t.dispatch")
    assert n == 1 and total >= 2_000


def test_cpu_fields_survive_the_exports():
    with tracing.trace("http.t", endpoint="t_export") as tid:
        with tracing.span("t.phase", phase=True):
            t0 = time.thread_time_ns()
            while time.thread_time_ns() - t0 < 2_000_000:
                pass
        with tracing.span("t.plain"):
            pass
    spans = tracing.trace_spans(tid)
    phase = next(s for s in spans if s.name == "t.phase")
    assert phase.cpu_us + phase.sys_us >= 2_000
    assert tracing.Span(**phase.to_dict()) == phase
    by_name = {e["name"]: e["args"]
               for e in tracing.to_chrome(spans)["traceEvents"]}
    assert (by_name["t.phase"]["cpu_us"], by_name["t.phase"]["sys_us"]) \
        == (phase.cpu_us, phase.sys_us)
    assert "cpu_us" not in by_name["t.plain"]    # it read no clock
    back = tracing.from_otlp(json.loads(json.dumps(tracing.to_otlp(spans))))
    assert back == spans


RECURSE_PHASES = ["batch.seed", "batch.device_wait", "batch.fetch",
                  "batch.render"]


def test_lane_recurse_route_opens_the_routes_phases_once_a_request():
    alpha, _u = _chain_alpha(13)
    qs = ['{ q(func: eq(name, "p%d")) @recurse(depth: 3) '
          '{ name follows } }' % i for i in range(8)]
    for attempt in ("cold", "warm"):
        if attempt == "warm":            # new texts: a plan-cache miss
            qs = [q.replace("q(func", "r(func") for q in qs]
        with tracing.trace("http.query_batch",
                           endpoint="query_batch") as tid:
            out = alpha.query_batch(qs)
        assert len(out) == 8 and all(o for o in out)
        by_name = {}
        for s in tracing.trace_spans(tid):
            by_name.setdefault(s.name, []).append(s)
        kernel = by_name["batch.recurse_kernel"][0]
        for p in RECURSE_PHASES:
            assert len(by_name.get(p, ())) == 1, (attempt, p)
        for p in ("batch.device_wait", "batch.fetch"):
            assert by_name[p][0].parent_id == kernel.span_id
        assert by_name["batch.fetch"][0].attrs["bytes"] > 0
        assert len(by_name["batch.plan"]) == 1


def test_shortest_seed_names_its_five_parts():
    alpha, u = _chain_alpha(11)
    with tracing.trace("http.query_batch", endpoint="query_batch") as tid:
        alpha.query_batch([_shortest(u[0], u[6]), _shortest(u[1], u[5]),
                           _shortest(u[4], u[9]), _shortest(u[0], u[3])])
    spans = tracing.trace_spans(tid)
    seed = next(s for s in spans if s.name == "batch.seed")
    parts = [s for s in spans if s.name.startswith("seed.")]
    assert [s.name for s in parts] == ["seed.ranks", "seed.near",
                                      "seed.near2", "seed.masks",
                                      "seed.upload"]
    assert all(s.parent_id == seed.span_id for s in parts)
    assert sum(s.dur_us for s in parts) <= seed.dur_us
    # children, not phases: no clock read, no series
    assert all(s.cpu_us == s.sys_us == 0 for s in parts)
    assert not _phase_series("seed.masks")
    # the chain's second levels: one row a lane, one in-edge each but
    # p2's two (the shortcut), which is the last target's in-neighbour
    near2 = parts[2].attrs
    assert (near2["rows"], near2["edges"], near2["capped"]) == (4, 5, 0)
