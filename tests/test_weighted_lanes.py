"""Facet-weighted `shortest` on the lanes (PR 46): the relaxing lane
program (ops/bfs.py make_ell_relax, engine/batch.py _run_weighted_batch)
against a whole heapq Dijkstra and against the host route, byte for byte;
what the family leaves to the host, counted with its reason; its cache
across writes; its phases."""

import heapq
import json
import urllib.request

import numpy as np
import pytest

from dgraph_tpu.engine import Engine
from dgraph_tpu.engine import batch
from dgraph_tpu.server.api import Alpha
from dgraph_tpu.utils import tracing
from dgraph_tpu.utils.metrics import METRICS

SCHEMA = ("name: string @index(exact) .\nknows: [uid] @reverse .\n"
          "likes: [uid] @reverse .")
Q = ('{ path as shortest(from: %s, to: %s%s) { knows @facets(weight) } '
     'p(func: uid(path)) { name } }')


def _alpha(edges: dict, n: int, extra: tuple = ()):
    """An Alpha over persons p0..p(n-1) and `edges` {(i, j): weight or
    None (an edge without the facet)}; (alpha, uids)."""
    a = Alpha(device_threshold=10**9)
    a.alter(SCHEMA)
    lines = [f'_:p{i} <name> "p{i}" .' for i in range(n)]
    for (i, j), w in edges.items():
        facet = "" if w is None else f" (weight={w})"
        lines.append(f"_:p{i} <knows> _:p{j}{facet} .")
    lines += list(extra)
    uids = a.mutate(set_nquads="\n".join(lines))["uids"]
    return a, [uids[f"_:p{i}"] for i in range(n)]


def _random_edges(seed: int, n: int, m: int, draw, bare: float = 0.0,
                  halves: bool = False) -> dict:
    """`m` draws of a directed pair, weighted by `draw(rng)`; a share
    `bare` of them without the facet; `halves` keeps every edge inside
    its half of the nodes (two components)."""
    rng = np.random.default_rng(seed)
    edges = {}
    for _ in range(m):
        i, j = (int(x) for x in rng.integers(0, n, 2))
        if i == j or (halves and (i < n // 2) != (j < n // 2)):
            continue
        edges[(i, j)] = None if rng.random() < bare else int(draw(rng))
    return edges


def _dijkstra(edges: dict, n: int, a: int) -> list:
    adj = [[] for _ in range(n)]
    for (i, j), w in edges.items():
        adj[i].append((j, 1 if w is None else w))
    cost, heap = [None] * n, [(0, a)]
    while heap:
        c, u = heapq.heappop(heap)
        if cost[u] is not None:
            continue
        cost[u] = c
        for v, w in adj[u]:
            if cost[v] is None:
                heapq.heappush(heap, (c + w, v))
    return cost


def _launches() -> tuple:
    return (METRICS.get("kernel_group_launches_total", family="weighted"),
            METRICS.get("kernel_group_queries_total", family="weighted"))


def _fallbacks(reason: str) -> float:
    return METRICS.get("weighted_host_fallbacks_total", reason=reason)


def _walk(obj) -> list:
    out = []
    while obj is not None:
        out.append(obj["uid"])
        obj = obj.get("knows")
    return out


# a hub everyone points at (an in-degree past the dense classes: tiles
# and their second level), and everyone a ring, so all is reachable
_HUB = {**{(i, 0): 3 + i % 5 for i in range(1, 90)},
        **{(i, (i + 1) % 90): 1 + i % 7 for i in range(90)},
        **{(0, 45): 2}}
# the target is reached first, in one round, by its dearest path
_DEAR = {(0, 9): 30, (0, 1): 2, (1, 2): 2, (2, 9): 2, (0, 3): 9,
         (3, 9): 9, (9, 4): 1, (4, 5): 1, (5, 6): 0, (6, 5): 0, (6, 0): 4}

GRAPHS = {
    "weights_1_to_40": (160, _random_edges(1, 160, 1100,
                                           lambda r: r.integers(1, 41))),
    "ties_everywhere": (120, _random_edges(2, 120, 900, lambda r: 5)),
    "zero_weight_edges_and_cycles": (
        120, _random_edges(3, 120, 800, lambda r: r.integers(0, 3))),
    "edges_without_the_facet": (
        140, _random_edges(4, 140, 900, lambda r: r.integers(1, 9),
                           bare=0.3)),
    "no_edge_has_the_facet": (
        100, _random_edges(5, 100, 500, lambda r: 1, bare=1.0)),
    "unreachable_targets": (
        120, _random_edges(6, 120, 700, lambda r: r.integers(1, 20),
                           halves=True)),
    "a_hub_past_the_dense_classes": (90, _HUB),
    "reached_first_by_a_dearer_path": (10, _DEAR),
}


@pytest.mark.parametrize("case", list(GRAPHS))
def test_the_lane_route_is_a_whole_dijkstra_s_answer(case):
    """One launch a batch; every answer's cost is the heapq Dijkstra's,
    its path is made of stored edges whose weights add up to it, and the
    whole response equals the host route's, byte for byte. `from == to`
    and an unknown uid ride along in every batch."""
    n, edges = GRAPHS[case]
    a, u = _alpha(edges, n)
    rng = np.random.default_rng(11)
    pairs = [(int(x), int(y)) for x, y in rng.integers(0, n, (40, 2))]
    pairs[0] = (pairs[0][0], pairs[0][0])              # from == to
    if case == "reached_first_by_a_dearer_path":
        pairs[1] = (0, 9)
    qs = [Q % (u[x], u[y], "") for x, y in pairs]
    qs.append(Q % (u[1], "0xfffff", ""))               # nobody's uid
    before = _launches()
    got = a.query_batch(qs)
    assert _launches() == (before[0] + 1, before[1] + len(qs))
    eng = Engine(a.mvcc.read_view(a.oracle.read_only_ts()),
                 device_threshold=10**9)
    assert json.dumps(got) == json.dumps([eng.query(q) for q in qs])
    assert not got[-1].get("_path_") and not got[-1].get("p")
    reached = 0
    for (x, y), ans in zip(pairs, got):
        want = _dijkstra(edges, n, x)[y]
        if want is None:
            assert not ans.get("_path_") and not ans.get("p"), (x, y)
            continue
        reached += 1
        (path,) = ans["_path_"]
        hops = _walk(path)
        assert (hops[0], hops[-1]) == (u[x], u[y])
        steps = [(u.index(s), u.index(t)) for s, t in zip(hops, hops[1:])]
        assert all(e in edges for e in steps), (x, y)
        assert sum(1 if edges[e] is None else edges[e]
                   for e in steps) == want == path["_weight_"]
        assert sorted(p["name"] for p in ans["p"]) == sorted(
            f"p{u.index(h)}" for h in set(hops))
    assert 10 <= reached <= (20 if case == "unreachable_targets" else 41)
    if case == "reached_first_by_a_dearer_path":
        assert got[1]["_path_"][0]["_weight_"] == 6.0
        assert len(_walk(got[1]["_path_"][0])) == 4


def test_the_slots_weights_lie_beside_their_indices():
    """ops/bfs.py ell_weights: slot for slot, the weight of the in-edge
    whose source the ELL's slot names, tiles and padding included."""
    from dgraph_tpu.ops import bfs
    n, edges = GRAPHS["a_hub_past_the_dense_classes"]
    src, dst = (np.array(x, np.int32) for x in zip(*sorted(edges)))
    w = np.array([edges[e] for e in sorted(edges)], np.uint8)
    indptr = np.concatenate([[0], np.cumsum(np.bincount(src, minlength=n))])
    g = bfs.build_ell(indptr.astype(np.int32), dst)
    assert g.tiles is not None and g.dense is None
    order = np.argsort(dst, kind="stable")
    in_ptr = np.concatenate([[0], np.cumsum(np.bincount(dst, minlength=n))])
    parts, tiles = bfs.ell_weights(g, in_ptr, w[order], np.uint8)
    off, seen = 0, 0
    for (kind, e, rows), wp in zip(g.parts, parts):
        for r in range(rows if kind == "ell" else 0):
            v = int(g.perm_order[off + r])
            for k in range(e.shape[1]):
                assert wp[r, k] == edges[(int(g.perm_order[e[r, k]]), v)]
                seen += 1
        off += rows
    real = g.tiles != n
    assert (tiles[~real] == 0).all()
    heavy = g.perm_order[off:]
    row_of_tile = np.repeat(heavy, -(-np.diff(in_ptr)[heavy] // 8))
    for t, k in zip(*np.nonzero(real)):
        assert tiles[t, k] == edges[(int(g.perm_order[g.tiles[t, k]]),
                                     int(row_of_tile[t]))]
        seen += 1
    assert seen == len(edges)
    assert bfs.relax_dtype(40, 64) == (np.dtype(np.int16), 32767 - 40)
    assert bfs.relax_dtype(40, 1000)[0] == np.dtype(np.int32)
    assert bfs.relax_dtype(2**31 - 1, 64) is None


def test_a_lane_left_open_at_the_cap_is_walked_on_the_host(monkeypatch):
    """A chain longer than the round cap: the lanes the program could
    not settle go to the host, counted under `rounds`, and the answers
    are the host's all the same."""
    n = 30
    a, u = _alpha({(i, i + 1): 1 for i in range(n - 1)}, n)
    qs = [Q % (u[0], u[n - 1 - i], ", depth: 4") for i in range(6)]
    before, lost = _launches(), _fallbacks("rounds")
    got = a.query_batch(qs)
    assert _launches()[0] == before[0] + 1
    assert _fallbacks("rounds") - lost == 6     # 8 rounds reach p8
    eng = Engine(a.mvcc.read_view(a.oracle.read_only_ts()),
                 device_threshold=10**9)
    assert json.dumps(got) == json.dumps([eng.query(q) for q in qs])
    assert got[0]["_path_"][0]["_weight_"] == n - 1


# what the family leaves to the host: (edges' weights, the block's extra
# arguments, the edge block(s), the counter's reason)
REFUSED = {
    "a_float_among_the_weights": (lambda i: 2.5 if i == 7 else 3, "",
                                  "knows @facets(weight)", "facet_type"),
    "a_negative_weight": (lambda i: -2 if i == 7 else 3, "",
                          "knows @facets(weight)", "negative"),
    "maxweight": (lambda i: 3, ", maxweight: 40", "knows @facets(weight)",
                  "bounds"),
    "numpaths_2": (lambda i: 3, ", numpaths: 2", "knows @facets(weight)",
                   "numpaths"),
    "two_edge_blocks": (lambda i: 3, "",
                        "knows @facets(weight) likes @facets(weight)",
                        "edge_blocks"),
    "a_query_that_comes_alone": (lambda i: 3, "", "knows @facets(weight)",
                                 "unbatched"),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_what_the_family_does_not_take_goes_to_the_host_and_is_counted(
        case):
    weight, args, block, reason = REFUSED[case]
    rng = np.random.default_rng(8)
    edges = {}
    for k in range(300):
        i, j = (int(x) for x in rng.integers(0, 60, 2))
        if i != j:
            edges[(i, j)] = weight(k)
    a, u = _alpha(edges, 60, extra=("_:p1 <likes> _:p2 (weight=1) .",))
    count = 6
    qs = [('{ path as shortest(from: %s, to: %s%s) { %s } '
           'p(func: uid(path)) { name } }' % (u[i], u[i + 20], args, block))
          for i in range(count)]
    before, lost = _launches(), _fallbacks(reason)
    got = ([a.query(q) for q in qs] if reason == "unbatched"
           else a.query_batch(qs))
    assert _launches() == before                # no weighted launch
    assert _fallbacks(reason) - lost == count
    eng = Engine(a.mvcc.read_view(a.oracle.read_only_ts()),
                 device_threshold=10**9)
    lost = _fallbacks(reason)
    assert json.dumps(got) == json.dumps([eng.query(q) for q in qs])
    assert _fallbacks(reason) - lost == count   # a lone query counts too
    assert any(ans.get("_path_") for ans in got)


def test_a_relation_with_a_hub_block_is_left_to_the_host(monkeypatch):
    """The hub block takes edges out of the lists and has no min-plus
    product: the launch is not made, the host answers, and once the ELL
    is known the counter names the block."""
    from dgraph_tpu.ops import bfs
    monkeypatch.setattr(bfs, "DENSE_MIN_EDGES", 100)
    core = {(i, j): 1 + (i + j) % 9 for i in range(40) for j in range(40)
            if i != j}
    rim = {(40 + i, i % 40): 3 for i in range(60)}
    rim.update({(i % 40, 40 + i): 4 for i in range(60)})
    a, u = _alpha({**core, **rim}, 100)
    qs = [Q % (u[40 + i], u[70 + i], "") for i in range(6)]
    before, lost = _launches(), _fallbacks("hub_block")
    got = a.query_batch(qs)
    store = a.mvcc.read_view(a.oracle.read_only_ts())
    assert batch._ell_for(store, "knows", False).dense is not None
    assert _launches() == before
    assert _fallbacks("hub_block") - lost == 6
    eng = Engine(store, device_threshold=10**9)
    assert json.dumps(got) == json.dumps([eng.query(q) for q in qs])
    assert got[0]["_path_"][0]["_weight_"] >= 8
    # the planner knows it from now on
    from dgraph_tpu.dql.parser import parse
    assert batch.plan_batch_groups(store, [parse(q) for q in qs]) == (
        [], list(range(6)))


def test_query_batch_over_http_is_the_host_route_s_json():
    """A mixed batch through `POST /query/batch`: weighted blocks (one
    launch), unweighted ones (the first-visit family), `numpaths: 2`
    weighted ones (the host), an @recurse: every answer in its place and
    equal to the per-query route's, byte for byte."""
    from dgraph_tpu.server.http import make_http_server, serve_background
    n, edges = GRAPHS["weights_1_to_40"]
    a, u = _alpha(edges, n)
    weighted = [Q % (u[i], u[i + 50], "") for i in range(8)]
    plain = ['{ path as shortest(from: %s, to: %s) { knows } '
             'p(func: uid(path)) { name } }' % (u[i], u[i + 60])
             for i in range(6)]
    two = [Q % (u[i], u[i + 70], ", numpaths: 2") for i in range(4)]
    rec = ['{ q(func: uid(%s)) @recurse(depth: 2) { name knows } }' % u[3]]
    qs = [q for trio in zip(weighted, plain + plain[:2], two + two)
          for q in trio] + rec
    srv = make_http_server(a)
    serve_background(srv)
    try:
        before = _launches()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.server_address[1]}/query/batch",
            data=json.dumps({"queries": qs}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as r:
            out = json.loads(r.read())
    finally:
        srv.shutdown()
    assert _launches() == (before[0] + 1, before[1] + 8)
    eng = Engine(a.mvcc.read_view(a.oracle.read_only_ts()),
                 device_threshold=10**9)
    assert json.dumps(out["data"]) == json.dumps(
        [eng.query(q) for q in qs])
    assert sum("_weight_" in json.dumps(d) for d in out["data"]) >= 12


def test_the_weights_are_carried_with_the_ell_and_dropped_with_it():
    """A fold that leaves `knows` alone carries the slot-aligned weights
    with the ELL; a write to the relation's facet drops both, and the
    next batch builds them again and reads the new weight."""
    edges = {(i, i + 1): 5 for i in range(11)}
    edges[(0, 11)] = 60
    a, u = _alpha(edges, 12)
    qs = [Q % (u[0], u[11 - i], "") for i in range(6)]
    first = a.query_batch(qs)
    assert first[0]["_path_"][0]["_weight_"] == 55.0
    key = ("knows", False, "weight")
    store = a.mvcc.read_view(a.oracle.read_only_ts())
    held = batch._cache_host(store, "knows", False)._ell_devs[key]
    assert held.largest == 60 and held.width == 1
    a.mutate(set_nquads=f'<{u[4]}> <name> "renamed" .')
    kept = a.mvcc.rollup()
    assert kept._ell_devs[key] is held
    assert kept._ell_devs[("knows", False)] is store._ell_devs[
        ("knows", False)]
    assert [d["_path_"] for d in a.query_batch(qs)] == \
        [d["_path_"] for d in first]
    a.mutate(set_nquads=f"<{u[0]}> <knows> <{u[11]}> (weight=7) .")
    new = a.mvcc.rollup()
    assert key not in getattr(new, "_ell_devs", {})
    again = a.query_batch(qs)
    assert again[0]["_path_"][0]["_weight_"] == 7.0
    assert new._ell_devs[key] is not held and \
        new._ell_devs[key].largest == 7
    eng = Engine(new, device_threshold=10**9)
    assert json.dumps(again) == json.dumps([eng.query(q) for q in qs])


PHASES = ["http.decode", "admission.admit", "mvcc.read_view", "batch.plan",
          "batch.seed", "batch.device_wait", "batch.fetch",
          "batch.walk_back", "batch.render", "http.encode"]


def test_the_route_s_phases_open_once_each_and_cover_the_request():
    """By the spans' structure, no clock: every phase once a request,
    each a child of the request's root or of the kernel span, none
    inside another, and nothing else under the root but the cost record;
    the builds of a cold request sit inside `batch.seed`."""
    import time

    from dgraph_tpu.server.http import make_http_server, serve_background
    edges = {(i, i + 1): 1 + i % 3 for i in range(12)}
    edges[(0, 2)] = 9
    a, u = _alpha(edges, 13)
    a.attach_admission(max_inflight=4, queue_depth=4)
    srv = make_http_server(a)
    serve_background(srv)
    pairs = [(i, j) for i in range(12) for j in range(i + 1, 13)
             if j - i <= 8][:64]
    qs = [Q % (u[i], u[j], "") for i, j in pairs]
    try:
        for attempt in ("cold", "warm"):
            if attempt == "warm":           # new texts: a plan-cache miss
                qs = [q.replace("p(func", "r(func") for q in qs]
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.server_address[1]}/query/batch",
                data=json.dumps({"queries": qs}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as r:
                tid = json.loads(r.read())["extensions"]["trace_id"]
            spans = []
            for _ in range(2000):           # the root closes last
                spans = tracing.trace_spans(tid)
                if spans and spans[-1].name == "http.query_batch":
                    break
                time.sleep(0.005)
            root = spans[-1]
            assert root.name == "http.query_batch"
            by_name = {}
            for s in spans:
                by_name.setdefault(s.name, []).append(s)
            for p in PHASES:
                assert len(by_name.get(p, ())) == 1, (attempt, p)
            assert "batch.scan" not in by_name      # no levels to scan
            kernel, = by_name["batch.shortest_kernel"]
            assert kernel.attrs["weight"] == "weight"
            assert by_name["batch.device_wait"][0].attrs["rounds"] >= 9
            under = {s.name for s in spans if s.parent_id == root.span_id}
            assert under == set(PHASES) - {
                "batch.device_wait", "batch.fetch"} | {
                "batch.shortest_kernel", "query.cost"}
            assert [s.name for s in spans
                    if s.parent_id == kernel.span_id] == [
                "batch.device_wait", "batch.fetch"]
            builds = [s for n in ("batch.build_ell", "batch.upload_ell")
                      for s in by_name.get(n, ())]
            assert len(builds) == (4 if attempt == "cold" else 0)
            seed, = by_name["batch.seed"]
            assert all(s.parent_id == seed.span_id for s in builds)
            assert sorted(s.attrs.get("part", "ell") for s in builds) == (
                ["ell", "ell", "weights", "weights"] if builds else [])
            walk, = by_name["batch.walk_back"]
            assert walk.attrs["steps"] == 8 and walk.attrs["host_lanes"] == 0
    finally:
        srv.shutdown()
