"""Fleet observability (ISSUE 14): cross-process trace propagation,
cluster-wide /debug/fleet aggregation, peer-correlated diagnostics.

The load-bearing contracts:

  * a cross-group query yields ONE trace — worker-side spans carry the
    coordinator's trace id and their parent ids resolve to coordinator
    spans inside the merged trace, with zero use of the ?peer= proxy;
    the Chrome export renders both originating processes' rows;
  * /debug/fleet's cost-digest merge is bit-identical to an in-process
    Aggregator merge of the same per-node states, and the endpoint
    degrades (partial snapshot + per-peer error) when a peer is dark —
    never a 500;
  * a watchdog conviction of a request stuck inside an outstanding RPC
    names the implicated PEER and the bundle carries that peer's
    in-flight snapshot (pulled over the DebugFlight RPC);
  * maintenance jobs triggered over admin HTTP join the triggering
    request's trace; HTTP echoes X-Trace-Id inbound/outbound;
  * identity metrics (build_info, process_uptime_s) ride the
    exposition; the armed hot path stays under the 5% overhead bar.
"""

import json
import os
import threading
import time
import urllib.request

import pytest

from dgraph_tpu.cluster import start_cluster_alpha
from dgraph_tpu.cluster.zero import ZeroClient, make_zero_server
from dgraph_tpu.server.api import Alpha
from dgraph_tpu.server.http import make_http_server, serve_background
from dgraph_tpu.utils import costprofile, flightrec, tracing
from dgraph_tpu.utils.metrics import METRICS

SCHEMA = """
name: string @index(exact) .
age: int @index(int) .
friend: [uid] @reverse .
"""

SPAN_Q = ('{ q(func: eq(name, "alice")) '
          '{ name age friend { name friend { name } } } }')


@pytest.fixture(autouse=True)
def _clean():
    flightrec.disarm()
    costprofile.reset()
    costprofile.set_enabled(True)
    tracing.set_enabled(True)
    yield
    flightrec.disarm()
    costprofile.reset()
    tracing.set_enabled(True)


@pytest.fixture()
def cluster():
    """Zero + two single-node groups, the test_cluster split: `name`/
    `age` on group 1, `friend` on group 2."""
    zserver, zport, _zstate = make_zero_server()
    zserver.start()
    ztarget = f"127.0.0.1:{zport}"
    a1, s1, addr1 = start_cluster_alpha(ztarget, device_threshold=10**9)
    a2, s2, addr2 = start_cluster_alpha(ztarget, device_threshold=10**9)
    assert a1.groups.gid != a2.groups.gid
    zc = ZeroClient(ztarget)
    for pred in ("name", "age", "dgraph.type"):
        zc.should_serve(pred, a1.groups.gid)
    zc.should_serve("friend", a2.groups.gid)
    a1.alter(SCHEMA)
    a1.groups.refresh()
    a2.groups.refresh()
    a1.mutate(set_nquads="""
      _:a <name> "alice" .
      _:a <age> "29"^^<xs:int> .
      _:b <name> "bob" .
      _:c <name> "carol" .
      _:a <friend> _:b .
      _:b <friend> _:c .
    """)
    yield a1, a2, addr1, addr2, s1, s2
    for s in (s1, s2, zserver):
        s.stop(None)


def _wait_for(pred, timeout=10.0, step=0.01):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if pred():
            return True
        time.sleep(step)
    return False


# ---------------------------------------------------------------------------
# tracing.attach — the propagation primitive

def test_attach_reestablishes_trace_and_parent():
    tracing.clear()
    with tracing.trace("coordinator") as tid:
        parent = tracing.current_span_id()
        assert parent
    # a "remote handler" thread re-establishes the forwarded context
    def handler():
        with tracing.attach(tid, parent):
            with tracing.span("worker.leg"):
                pass
    t = threading.Thread(target=handler)
    t.start()
    t.join()
    spans = tracing.trace_spans(tid)
    leg = next(s for s in spans if s.name == "worker.leg")
    assert leg.trace_id == tid
    assert leg.parent_id == parent
    # propagated spans count toward the fleet trace-health stats
    st = tracing.stats()
    assert st["spans_total"] >= 2 and st["propagated_total"] >= 1
    # empty trace id = no-op (the untraced-RPC fast path)
    before = tracing.stats()["propagated_total"]
    with tracing.attach(""):
        with tracing.span("untraced"):
            pass
    assert tracing.stats()["propagated_total"] == before


def test_span_ids_are_process_salted():
    """Cross-process uniqueness: locally-issued span ids carry the pid
    salt in their high bits, so a foreign parent id (another process's
    salt) can never collide with a local id."""
    with tracing.span("x") as s:
        pass
    assert s.span_id >> 40 == os.getpid() & 0xFFFF
    assert s.pid == os.getpid()


# ---------------------------------------------------------------------------
# tentpole 1: one trace across a cross-group hop

def test_cross_group_query_yields_one_trace(cluster):
    a1, _a2, _addr1, _addr2, _s1, _s2 = cluster
    tracing.clear()
    with tracing.trace("request") as tid:
        out = a1.query(SPAN_Q)
    assert out["q"][0]["friend"][0]["name"] == "bob"
    spans = tracing.trace_spans(tid)
    ids = {s.span_id for s in spans}
    worker = [s for s in spans if s.name.startswith("worker.")]
    # the worker-side handler spans joined THIS trace — no ?peer= proxy
    assert any(s.name == "worker.serve_task" for s in worker)
    for s in worker:
        assert s.trace_id == tid
        # parentage resolves WITHIN the merged trace: each worker span
        # hangs off a coordinator span (its rpc.* client span)
        assert s.parent_id in ids, (s.name, s.parent_id)
    parents = {s.span_id: s for s in spans}
    st = next(s for s in worker if s.name == "worker.serve_task")
    assert parents[st.parent_id].name == "rpc.serve_task"
    # Chrome/Perfetto export renders the merged trace (one process in
    # this in-process harness; the pid rides every event so separate
    # processes land on separate rows)
    doc = tracing.to_chrome(spans)
    evs = [e for e in doc["traceEvents"]
           if e["name"] == "worker.serve_task"]
    assert evs and all(e["pid"] == os.getpid() for e in evs)


def test_cross_process_chrome_export_two_process_rows():
    """A merged trace whose spans came from TWO processes (simulated:
    foreign span dicts with a different pid, the shape /debug/fleet or
    OTLP import delivers) renders as two distinct Perfetto process
    rows on one timeline."""
    tracing.clear()
    with tracing.trace("request") as tid:
        with tracing.span("rpc.serve_task"):
            parent = tracing.current_span_id()
    local = tracing.trace_spans(tid)
    foreign = tracing.Span(name="worker.serve_task", span_id=7,
                           parent_id=parent, trace_id=tid,
                           start_us=local[0].start_us, dur_us=10,
                           tid=1, pid=os.getpid() + 1)
    merged = local + [foreign]
    ids = {s.span_id for s in merged}
    assert all(s.parent_id in ids or s.parent_id == 0 for s in merged)
    doc = tracing.to_chrome(merged)
    assert len({e["pid"] for e in doc["traceEvents"]}) == 2
    # and the OTLP round-trip keeps the process identity
    back = tracing.from_otlp(tracing.to_otlp(merged))
    assert {s.pid for s in back} == {s.pid for s in merged}


# ---------------------------------------------------------------------------
# tentpole 2: /debug/fleet

def test_fleet_snapshot_merges_exactly_and_degrades(cluster):
    a1, _a2, addr1, addr2, _s1, s2 = cluster
    srv = make_http_server(a1)
    serve_background(srv)
    port = srv.server_address[1]
    base = f"http://127.0.0.1:{port}"
    try:
        a1.query(SPAN_Q)  # some cost records exist
        with urllib.request.urlopen(base + "/debug/fleet") as r:
            assert r.status == 200
            doc = json.loads(r.read())
        assert doc["self"] == addr1
        assert set(doc["nodes"]) == {addr1, addr2}
        assert doc["errors"] == {}
        # per-node fragments carry identity + health
        n1 = doc["nodes"][addr1]
        assert n1["build"]["version"] and n1["uptime_s"] >= 0
        assert "spans" in n1 and "breakers" in n1 and "gates" in n1
        # cost-digest merge is BIT-IDENTICAL to an in-process merge of
        # the same per-node states (integer state, associative)
        frags = {addr1: a1.groups.pool(addr1).debug_fleet(),
                 addr2: a1.groups.pool(addr2).debug_fleet()}
        expect = costprofile.Aggregator()
        for frag in frags.values():
            expect.merge(costprofile.Aggregator.from_state(
                frag["costs"]))
        assert doc["costs_state"] == json.loads(
            json.dumps(expect.to_state()))
        # merged exposition is instance-labeled per node
        assert f'instance="{addr1}"' in doc["metrics"]
        assert f'instance="{addr2}"' in doc["metrics"]

        # degraded-not-failed: kill the peer, snapshot stays 200 with
        # a per-peer error and the survivor's data intact
        s2.stop(None)
        with urllib.request.urlopen(
                base + "/debug/fleet?budget_ms=1500") as r:
            assert r.status == 200
            down = json.loads(r.read())
        assert addr1 in down["nodes"]
        assert addr2 not in down["nodes"]
        assert addr2 in down["errors"]
        assert down["costs"]["records_total"] >= 0
        assert METRICS.get("fleet_fanout_total", outcome="error") >= 1
    finally:
        srv.shutdown()


def test_fleet_flight_route_and_peer_proxy(cluster):
    a1, _a2, _addr1, addr2, _s1, _s2 = cluster
    srv = make_http_server(a1)
    serve_background(srv)
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        with urllib.request.urlopen(base + "/debug/fleet/flight") as r:
            local = json.loads(r.read())
        assert set(local) >= {"armed", "inflight", "ring", "watchdog",
                              "rpcs_in_flight", "dumps"}
        with urllib.request.urlopen(
                base + "/debug/fleet/flight?peer=" + addr2) as r:
            peer = json.loads(r.read())
        assert set(peer) >= {"armed", "inflight", "ring", "watchdog"}
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# tentpole 3: peer-correlated diagnostics

def test_watchdog_conviction_names_wedged_peer(cluster, tmp_path):
    """A coordinator request stuck inside an outstanding RPC leg to the
    `friend` owner is convicted; the bundle names that peer and pulls
    its in-flight snapshot over DebugFlight — with no operator
    action."""
    a1, _a2, _addr1, addr2, _s1, _s2 = cluster
    a1.query(SPAN_Q)  # warm routing/tablet claims before the fault
    flightrec.arm(diag_dir=str(tmp_path / "diag"), poll_s=0.02,
                  stall_factor=2.0, stall_floor_ms=50.0,
                  min_dump_interval_s=60.0, alpha=a1)
    # one-shot injected wedge on the pooled link to the friend-owner:
    # the first wire attempt sleeps well past the conviction threshold
    # (the same fault_check seam the fuzzers use); later attempts — the
    # bundle's own DebugFlight pull included — pass clean
    fired = threading.Event()

    def stall_once():
        if not fired.is_set():
            fired.set()
            time.sleep(2.0)

    client = a1.groups.pool(addr2)
    client.fault_check = stall_once
    try:
        done = threading.Event()
        threading.Thread(target=lambda: (a1.query(SPAN_Q),
                                         done.set()),
                         daemon=True).start()
        diag = tmp_path / "diag"
        assert _wait_for(lambda: diag.exists() and any(
            f.startswith("flight-watchdog")
            for f in os.listdir(diag)), timeout=15.0)
        assert done.wait(30.0)
        fname = next(f for f in os.listdir(diag)
                     if f.startswith("flight-watchdog"))
        bundle = json.loads((diag / fname).read_text())
        assert bundle["reason"]["kind"] == "request"
        # the conviction names the implicated PEER and its RPC
        assert bundle["reason"]["peer"] == addr2
        assert bundle["reason"]["peer_rpc"]
        # ... and the bundle carries that peer's in-flight snapshot
        pf = bundle["peer_flight"]
        assert pf["addr"] == addr2
        assert "flight" in pf, pf.get("error")
        assert set(pf["flight"]) >= {"inflight", "ring", "watchdog"}
        assert METRICS.get("peer_flight_pulls_total",
                           outcome="ok") >= 1
    finally:
        client.fault_check = None
        flightrec.disarm()


def test_debug_flight_rpc_direct(cluster):
    a1, _a2, _addr1, addr2, _s1, _s2 = cluster
    doc = a1.groups.pool(addr2).debug_flight(n=16)
    assert doc["armed"] is False
    assert doc["ring"] == [] and doc["inflight"] == []


# ---------------------------------------------------------------------------
# satellites: admin-trace join, X-Trace-Id, identity metrics, CLI

def test_maintenance_job_joins_admin_trace(tmp_path):
    alpha = Alpha(device_threshold=10**9)
    alpha.alter("name: string @index(exact) .")
    alpha.mutate(set_nquads='_:a <name> "alice" .')
    alpha.attach_maintenance(str(tmp_path / "p"))
    srv = make_http_server(alpha)
    serve_background(srv)
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    tid = "fleetadmintrace1"
    try:
        req = urllib.request.Request(
            base + "/admin/checkpoint?wait=true", data=b"{}",
            headers={"Content-Type": "application/json",
                     "X-Trace-Id": tid}, method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            doc = json.loads(r.read())
        assert doc["data"]["trace_id"] == tid
        # the admin request's root closes after the response's last byte
        deadline = time.monotonic() + 5.0
        while True:
            spans = tracing.trace_spans(tid)
            names = [s.name for s in spans]
            if "http.admin" in names or time.monotonic() > deadline:
                break
            time.sleep(0.005)
        # the admin request AND the scheduler-thread job are ONE trace
        assert "http.admin" in names
        assert "maintenance.job" in names
        job = next(s for s in spans if s.name == "maintenance.job")
        assert job.attrs["job"] == "checkpoint"
    finally:
        srv.shutdown()
        alpha.maintenance.stop(drain=False)


def test_http_x_trace_id_inbound_outbound():
    alpha = Alpha(device_threshold=10**9)
    alpha.alter("name: string @index(exact) .")
    alpha.mutate(set_nquads='_:a <name> "alice" .')
    srv = make_http_server(alpha)
    serve_background(srv)
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        tid = "abcdef0123456789"
        req = urllib.request.Request(
            base + "/query",
            data=b'{ q(func: eq(name, "alice")) { name } }',
            headers={"Content-Type": "application/dql",
                     "X-Trace-Id": tid}, method="POST")
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.headers["X-Trace-Id"] == tid
            body = json.loads(r.read())
        assert body["extensions"]["trace_id"] == tid
        assert tracing.trace_spans(tid)
        # without the header a fresh id is issued and still echoed
        req = urllib.request.Request(
            base + "/query",
            data=b'{ q(func: eq(name, "alice")) { name } }',
            headers={"Content-Type": "application/dql"}, method="POST")
        with urllib.request.urlopen(req, timeout=10) as r:
            fresh = r.headers["X-Trace-Id"]
            body = json.loads(r.read())
        assert fresh and fresh == body["extensions"]["trace_id"]
    finally:
        srv.shutdown()


def test_identity_metrics_on_exposition():
    alpha = Alpha(device_threshold=10**9)
    srv = make_http_server(alpha)
    serve_background(srv)
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        with urllib.request.urlopen(
                base + "/debug/prometheus_metrics") as r:
            text = r.read().decode()
        assert "dgraph_tpu_build_info{" in text
        assert 'version="' in text and 'jax="' in text \
            and 'backend="' in text
        up = [ln for ln in text.splitlines()
              if ln.startswith("dgraph_tpu_process_uptime_s")]
        assert up and float(up[0].split()[-1]) >= 0.0
    finally:
        srv.shutdown()


def test_diagnose_fleet_cli_writes_per_node_files(cluster, tmp_path,
                                                 capsys):
    from dgraph_tpu import cli
    a1, _a2, _addr1, addr2, _s1, _s2 = cluster
    srv = make_http_server(a1)
    serve_background(srv)
    port = srv.server_address[1]
    out_dir = tmp_path / "fleetdiag"
    try:
        rc = cli.main(["diagnose", f"127.0.0.1:{port}", "--fleet",
                       "--out", str(out_dir)])
        assert rc == 0
        printed = json.loads(capsys.readouterr().out.strip()
                             .splitlines()[-1])
        assert printed["dir"] == str(out_dir)
        assert printed["errors"] == {}
        files = set(os.listdir(out_dir))
        assert {"local.json", "fleet.json"} <= files
        peer_file = "".join(c if c.isalnum() else "-"
                            for c in addr2) + ".json"
        assert peer_file in files
        peer_doc = json.loads((out_dir / peer_file).read_text())
        assert set(peer_doc) >= {"armed", "inflight", "ring",
                                 "watchdog"}
        local = json.loads((out_dir / "local.json").read_text())
        assert "stacks" in local and "surfaces" in local
    finally:
        srv.shutdown()


def test_fleet_cli_summary(cluster, tmp_path, capsys):
    from dgraph_tpu import cli
    a1, _a2, addr1, addr2, _s1, _s2 = cluster
    srv = make_http_server(a1)
    serve_background(srv)
    port = srv.server_address[1]
    out = tmp_path / "fleet.json"
    try:
        rc = cli.main(["fleet", f"127.0.0.1:{port}",
                       "--out", str(out)])
        assert rc == 0
        printed = json.loads(capsys.readouterr().out.strip())
        assert printed["self"] == addr1
        assert set(printed["nodes"]) == {addr1, addr2}
        full = json.loads(out.read_text())
        assert "costs_state" in full and "metrics" in full
    finally:
        srv.shutdown()


def test_merge_exposition_instance_labels():
    from dgraph_tpu.server import fleet
    merged = fleet.merge_exposition({
        "n1:1": "# TYPE dgraph_tpu_x counter\ndgraph_tpu_x 3.0\n"
                'dgraph_tpu_y{a="b"} 1.0\n',
        "n2:2": "# TYPE dgraph_tpu_x counter\ndgraph_tpu_x 4.0\n",
    })
    lines = merged.splitlines()
    assert lines.count("# TYPE dgraph_tpu_x counter") == 1
    assert 'dgraph_tpu_x{instance="n1:1"} 3.0' in lines
    assert 'dgraph_tpu_x{instance="n2:2"} 4.0' in lines
    assert 'dgraph_tpu_y{instance="n1:1",a="b"} 1.0' in lines


# ---------------------------------------------------------------------------
# tier-1 guard: propagation armed must never become the regression

def _hot_loop_secs(alpha, queries, reps):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for q in queries:
            alpha.query(q)
        best = min(best, time.perf_counter() - t0)
    return best


def test_propagation_overhead_under_5_percent():
    """Tracing + propagation machinery armed (the production posture:
    per-span pid stamping, stat counting, metadata-readiness on every
    span) vs fully disabled, on the served query path — mirroring
    test_tracing's guard. min-of-N interleaved best-of damps scheduler
    noise."""
    import numpy as np

    from dgraph_tpu.store import StoreBuilder, parse_schema
    rng = np.random.default_rng(7)
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
    for q in queries:
        alpha.query(q)

    best_ratio = float("inf")
    for _attempt in range(3):
        tracing.set_enabled(True)
        armed = _hot_loop_secs(alpha, queries, 3)
        tracing.set_enabled(False)
        off = _hot_loop_secs(alpha, queries, 3)
        tracing.set_enabled(True)
        best_ratio = min(best_ratio, armed / off)
        if best_ratio < 1.05:
            break
    assert best_ratio < 1.05, f"propagation overhead {best_ratio:.3f}x"
