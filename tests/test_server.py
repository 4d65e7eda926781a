"""Transport-layer integration: gRPC services + HTTP endpoints.

Reference parity model: systest/-style tests against a real running server
on one machine (SURVEY §4 — "no mocked fake backend"); here a real grpc
server + ThreadingHTTPServer in-process.
"""

import json
import time
import urllib.request

import pytest

from dgraph_tpu.server.api import Alpha
from dgraph_tpu.server.http import make_http_server, serve_background
from dgraph_tpu.server.task import Client, make_server


@pytest.fixture()
def alpha():
    a = Alpha(device_threshold=10**9)
    a.alter("name: string @index(exact) .\nfriend: [uid] @reverse .")
    a.mutate(set_nquads="""
        _:a <name> "alice" .
        _:b <name> "bob" .
        _:c <name> "carol" .
        _:a <friend> _:b .
        _:a <friend> _:c .
        _:b <friend> _:c .
    """)
    return a


def test_grpc_query_mutate_alter(alpha):
    server, port = make_server(alpha)
    server.start()
    try:
        c = Client(f"127.0.0.1:{port}")
        out = c.query('{ q(func: eq(name, "alice")) { name friend { name } } }')
        assert out["q"][0]["name"] == "alice"
        assert len(out["q"][0]["friend"]) == 2

        resp = c.mutate(set_nquads='_:d <name> "dan" .', commit_now=True)
        assert resp.txn.commit_ts > 0
        out = c.query('{ q(func: eq(name, "dan")) { name } }')
        assert out == {"q": [{"name": "dan"}]}
        c.close()
    finally:
        server.stop(0)


def test_grpc_serve_task_seam(alpha):
    """The worker.Task boundary: frontier in → UidMatrix out."""
    server, port = make_server(alpha)
    server.start()
    try:
        c = Client(f"127.0.0.1:{port}")
        root = c.serve_task(func_name="eq", attr="name",
                            func_args=["alice", "bob"])
        uids = list(root.flat.uids)
        assert len(uids) == 2
        res = c.serve_task(attr="friend",
                           frontier={"uids": uids})
        assert res.edges_traversed == 3
        assert len(res.matrix.rows) == 2
        # flat union is deduped: alice→{bob,carol}, bob→{carol}
        assert len(res.flat.uids) == 2
        c.close()
    finally:
        server.stop(0)


def test_http_endpoints(alpha):
    srv = make_http_server(alpha)
    serve_background(srv)
    port = srv.server_address[1]
    base = f"http://127.0.0.1:{port}"

    def post(path, body, ctype="application/dql"):
        req = urllib.request.Request(
            base + path, data=body.encode(),
            headers={"Content-Type": ctype})
        with urllib.request.urlopen(req) as r:
            return json.loads(r.read())

    out = post("/query", '{ q(func: eq(name, "alice")) { name } }')
    assert out["data"] == {"q": [{"name": "alice"}]}
    assert "server_latency" in out["extensions"]

    out = post("/mutate?commitNow=true", '_:x <name> "erin" .',
               "application/rdf")
    assert out["data"]["txn"]["commit_ts"] > 0

    out = post("/query", json.dumps(
        {"query": "{ q(func: eq(name, $n)) { name } }",
         "variables": {"$n": "erin"}}), "application/json")
    assert out["data"] == {"q": [{"name": "erin"}]}

    with urllib.request.urlopen(base + "/health") as r:
        assert json.loads(r.read())[0]["status"] == "healthy"
    with urllib.request.urlopen(base + "/state") as r:
        st = json.loads(r.read())
        assert "friend" in st["groups"]["1"]["tablets"]
    with urllib.request.urlopen(base + "/debug/prometheus_metrics") as r:
        assert b"query_latency" in r.read()
    srv.shutdown()


def test_trace_id_echo_and_debug_surface(alpha):
    """Acceptance: a query through the HTTP surface returns a trace id
    whose spans are retrievable at /debug/traces (engine-level AND
    op-level spans present) and export as valid Chrome trace-event JSON
    at /debug/events."""
    srv = make_http_server(alpha)
    serve_background(srv)
    port = srv.server_address[1]
    base = f"http://127.0.0.1:{port}"

    req = urllib.request.Request(
        base + "/query",
        data=b'{ q(func: eq(name, "alice")) { name friend { name } } }',
        headers={"Content-Type": "application/dql"})
    with urllib.request.urlopen(req) as r:
        out = json.loads(r.read())
    tid = out["extensions"]["trace_id"]
    assert tid and out["data"]["q"][0]["name"] == "alice"

    # the root closes once the response is on the wire (it covers
    # `http.encode`): a client can ask before it has
    for _ in range(400):
        with urllib.request.urlopen(
                base + f"/debug/traces?trace_id={tid}") as r:
            spans = json.loads(r.read())["spans"]
        names = {s["name"] for s in spans}
        if "http.query" in names:
            break
        time.sleep(0.005)
    assert "http.query" in names           # request root
    assert {"http.decode", "mvcc.read_view", "http.encode"} <= names
    assert "engine.query" in names         # engine level
    assert "engine.block" in names
    # op level: the staged path's level/expand spans, or the whole-
    # query fused program's single span (ISSUE 15 — the default route)
    assert {"engine.level", "ops.expand", "engine.fused"} & names
    assert all(s["trace_id"] == tid for s in spans)
    # the hop recorded its route/shape and edge count, whichever route
    exp = [s for s in spans
           if s["name"] in ("ops.expand", "engine.fused")]
    assert exp and all("path" in s["attrs"] or "shape" in s["attrs"]
                       for s in exp)
    assert all("edges" in s["attrs"] for s in exp)

    with urllib.request.urlopen(
            base + f"/debug/events?trace_id={tid}") as r:
        doc = json.loads(r.read())
    evs = doc["traceEvents"]
    assert {e["name"] for e in evs} == names
    for e in evs:
        assert e["ph"] == "X" and e["dur"] >= 1
        assert e["args"]["trace_id"] == tid
    # bare /debug/traces serves the recent ring buffer
    with urllib.request.urlopen(base + "/debug/traces") as r:
        assert json.loads(r.read())["spans"]
    srv.shutdown()


def test_slow_query_log_counts_and_logs(alpha, caplog):
    import logging as _logging

    from dgraph_tpu.utils.metrics import METRICS
    srv = make_http_server(alpha)
    serve_background(srv)
    port = srv.server_address[1]
    alpha.slow_query_ms = 0.0001  # everything is slow
    before = METRICS.get("slow_queries_total")
    with caplog.at_level(_logging.WARNING, logger="dgraph_tpu.http"):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/query",
            data=b'{ q(func: eq(name, "alice")) { name } }',
            headers={"Content-Type": "application/dql"})
        out = json.loads(urllib.request.urlopen(req).read())
    assert METRICS.get("slow_queries_total") == before + 1
    msgs = [r.message for r in caplog.records if "slow query" in r.message]
    assert msgs and out["extensions"]["trace_id"] in msgs[0]
    alpha.slow_query_ms = 0
    srv.shutdown()


def test_http_error_paths(alpha):
    srv = make_http_server(alpha)
    serve_background(srv)
    port = srv.server_address[1]
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/query", data=b"{ bad query",
        headers={"Content-Type": "application/dql"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req)
    assert ei.value.code == 400
    srv.shutdown()


def test_served_mesh_engine_identical_json():
    """A mesh-configured Alpha (the `--mesh-devices 8` serve path) answers
    every query identically to the single-device server — the SPMD engine
    is live in production serving, not just in engine tests."""
    from dgraph_tpu.parallel.mesh import make_mesh

    nq = "\n".join(
        f'_:p{i} <name> "p{i}" .\n_:p{i} <score> "{i % 7}"^^<xs:int> .'
        for i in range(64))
    nq += "\n" + "\n".join(
        f"_:p{i} <friend> _:p{(i * 3 + 1) % 64} ." for i in range(64))
    schema = ("name: string @index(exact, term) .\n"
              "score: int @index(int) .\nfriend: [uid] @reverse .")
    queries = [
        '{ q(func: has(friend)) { name score friend { name } } }',
        '{ q(func: ge(score, 4)) @filter(has(friend)) { name } }',
        '{ q(func: has(name), first: 5, offset: 3) '
        '{ name friend (first: 2) @filter(ge(score, 2)) { name score } } }',
        '{ q(func: eq(name, "p7")) { name friend { friend { name } } } }',
    ]

    outs = []
    for mesh in (None, make_mesh(8)):
        # device_threshold=0 forces every hop through the device/mesh path
        a = Alpha(device_threshold=0, mesh=mesh)
        a.alter(schema)
        a.mutate(set_nquads=nq)
        server, port = make_server(a)
        server.start()
        try:
            c = Client(f"127.0.0.1:{port}")
            outs.append([c.query(q) for q in queries])
            c.close()
        finally:
            server.stop(0)
    assert outs[0] == outs[1]


def test_cli_mesh_flag(tmp_path, capsys):
    """`dgraph_tpu alpha --mesh-devices N` builds the mesh (smoke via the
    config plumbing; full serve loop is exercised by the cluster tests)."""
    from dgraph_tpu.utils.config import AlphaConfig, load_config

    cfg = load_config(AlphaConfig, None, {"mesh_devices": 8})
    assert cfg.mesh_devices == 8
    from dgraph_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(cfg.mesh_devices)
    a = Alpha.open(str(tmp_path / "p"), mesh=mesh)
    assert a.mesh is mesh
    a.alter("name: string @index(exact) .")
    a.mutate(set_nquads='_:x <name> "x" .')
    assert a.query('{ q(func: has(name)) { name } }') == {
        "q": [{"name": "x"}]}


def test_client_disconnect_cancels_request_and_frees_token(alpha):
    """ISSUE 5 satellite (ROADMAP PR-4 follow-on): a client that hangs
    up mid-query gets its request CANCELLED — the socket watcher calls
    ctx.cancel(), counted as request_cancelled_total{stage="disconnect"}
    — and the abandoned request releases its admission token early
    instead of computing into the void."""
    import socket
    import threading
    import time

    from dgraph_tpu.utils import deadline as dl
    from dgraph_tpu.utils.metrics import METRICS

    started = threading.Event()
    outcome = []

    def slow_query_raw(dql, variables=None, read_ts=None, acl_user=None,
                       deadline_ms=None):
        # a long-running query stub that cooperatively checkpoints —
        # exactly what a real engine hot loop does, without flakiness
        with alpha._request("read", deadline_ms):
            started.set()
            try:
                while True:
                    dl.checkpoint("slow_stub")
                    time.sleep(0.005)
            except BaseException:
                outcome.append("cancelled")
                raise

    alpha.query_raw = slow_query_raw
    alpha.attach_admission(max_inflight=2, queue_depth=2)
    srv = make_http_server(alpha)
    serve_background(srv)
    port = srv.server_address[1]
    c0 = METRICS.get("request_cancelled_total", stage="disconnect")

    s = socket.create_connection(("127.0.0.1", port), timeout=10)
    body = b"{ q(func: has(name)) { name } }"
    s.sendall(b"POST /query HTTP/1.1\r\nHost: t\r\n"
              b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
    assert started.wait(10), "the handler never started the query"
    s.close()  # the client walks away mid-query

    deadline_t = time.monotonic() + 10
    while time.monotonic() < deadline_t:
        if METRICS.get("request_cancelled_total",
                       stage="disconnect") > c0:
            break
        time.sleep(0.02)
    assert METRICS.get("request_cancelled_total",
                       stage="disconnect") == c0 + 1, (
        "the disconnect was never noticed")
    # the admission token drains (the request really ended)
    while time.monotonic() < deadline_t:
        if alpha.admission.status()["lanes"]["read"]["inflight"] == 0:
            break
        time.sleep(0.02)
    assert alpha.admission.status()["lanes"]["read"]["inflight"] == 0
    assert outcome == ["cancelled"]
    srv.shutdown()
