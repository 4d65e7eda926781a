"""Distributed hop kernels vs a numpy oracle, on the 8-device virtual mesh.

Plays the role of the reference's systest/ multi-node cluster tests
(docker-compose there, `xla_force_host_platform_device_count` here —
SURVEY §4): same query semantics must hold when the posting store is
partitioned across devices.
"""

import numpy as np
import pytest

from dgraph_tpu.ops.uidalgebra import SENTINEL32
from dgraph_tpu.parallel.dhop import chain_hop, matrix_hop, ring_matrix_hop
from dgraph_tpu.parallel.mesh import make_mesh
from dgraph_tpu.parallel.pshard import device_put_rel, shard_frontier, shard_rel
from dgraph_tpu.store.store import EdgeRel


def random_csr(n, avg_deg, seed):
    rng = np.random.default_rng(seed)
    m = n * avg_deg
    src = np.sort(rng.integers(0, n, m).astype(np.int32))
    dst = rng.integers(0, n, m).astype(np.int32)
    # dedupe + sort within rows
    pairs = np.unique(np.stack([src, dst], axis=1), axis=0)
    src, dst = pairs[:, 0], pairs[:, 1]
    indptr = np.zeros(n + 1, np.int32)
    np.add.at(indptr, src + 1, 1)
    indptr = np.cumsum(indptr).astype(np.int32)
    return EdgeRel(indptr=indptr, indices=dst.astype(np.int32))


def np_neighbors(rel, frontier):
    out = []
    for r in frontier:
        out.append(rel.indices[rel.indptr[r]:rel.indptr[r + 1]])
    return np.unique(np.concatenate(out)) if out else np.array([], np.int32)


def np_edges(rel, frontier):
    return int(sum(rel.indptr[r + 1] - rel.indptr[r] for r in frontier))


def np_pairs(rel, frontier):
    """The (parent, child) rows of every out-edge of the frontier, sorted."""
    pairs = [(int(r), int(c)) for r in frontier for c in rel.row(int(r))]
    return np.array(sorted(pairs), np.int64).reshape(-1, 2)


def matrix_pairs(frontier, nbrs, seg, totals):
    """matrix_hop's sharded edge matrix as sorted (parent, child) rows."""
    nbrs, seg, totals = np.asarray(nbrs), np.asarray(seg), np.asarray(totals)
    pairs = [(int(frontier[seg[d, k]]), int(nbrs[d, k]))
             for d in range(nbrs.shape[0]) for k in range(int(totals[d]))]
    return np.array(sorted(pairs), np.int64).reshape(-1, 2)


def pad(a, size):
    out = np.full(size, SENTINEL32, np.int32)
    out[:len(a)] = a
    return out


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


@pytest.fixture(scope="module")
def graph():
    return random_csr(n=503, avg_deg=7, seed=0)


def test_shard_rel_reconstructs(graph):
    srel = shard_rel(graph, 8)
    for d in range(8):
        lo = int(srel.row_lo[d])
        for r_local in range(srel.rows_per_shard):
            g = lo + r_local
            if g >= graph.indptr.shape[0] - 1 or g >= (int(srel.row_lo[d + 1]) if d < 7 else 10**9):
                continue
            a, b = srel.indptr_s[d, r_local], srel.indptr_s[d, r_local + 1]
            np.testing.assert_array_equal(
                srel.indices_s[d, a:b], graph.row(g))


@pytest.mark.parametrize("fsize", [1, 17, 100])
def test_matrix_hop(mesh, graph, fsize):
    rng = np.random.default_rng(fsize)
    frontier = np.unique(rng.integers(0, 503, fsize)).astype(np.int32)
    srel = device_put_rel(shard_rel(graph, 8), mesh)
    nbrs, seg, _pos, totals, max_shard_edges = matrix_hop(
        mesh, srel, pad(frontier, 128), edge_cap=4096)
    got = matrix_pairs(frontier, nbrs, seg, totals)
    np.testing.assert_array_equal(got, np_pairs(graph, frontier))
    np.testing.assert_array_equal(np.unique(got[:, 1]),
                                  np_neighbors(graph, frontier))
    totals = np.asarray(totals)
    assert int(totals.sum()) == np_edges(graph, frontier)
    assert 0 < int(max_shard_edges) == int(totals.max())


@pytest.mark.parametrize("fsize", [5, 120])  # 5: some chunks are empty
def test_ring_matrix_hop_matches_matrix_hop(mesh, graph, fsize):
    rng = np.random.default_rng(7)
    frontier = np.unique(rng.integers(0, 503, fsize)).astype(np.int32)
    srel = device_put_rel(shard_rel(graph, 8), mesh)
    chunks = shard_frontier(frontier, 8, f_cap=32)
    nbrs, seg, _pos, totals, max_step_edges = ring_matrix_hop(
        mesh, srel, chunks, edge_cap=4096)
    nbrs, seg, totals = np.asarray(nbrs), np.asarray(seg), np.asarray(totals)
    assert int(max_step_edges) == int(totals.max())
    assert int(totals.sum()) == np_edges(graph, frontier)
    # shard d at ring step i expands the chunk that started on (d - i) % 8
    pairs = [(int(chunks[(d - i) % 8][seg[d, i, k]]), int(nbrs[d, i, k]))
             for d in range(8) for i in range(8)
             for k in range(int(totals[d, i]))]
    got = np.array(sorted(pairs), np.int64).reshape(-1, 2)
    np.testing.assert_array_equal(got, np_pairs(graph, frontier))
    mnbrs, mseg, _mpos, mtotals, _mx = matrix_hop(
        mesh, srel, pad(frontier, 128), edge_cap=4096)
    np.testing.assert_array_equal(
        got, matrix_pairs(frontier, mnbrs, mseg, mtotals))


@pytest.mark.parametrize("start,depth", [([3, 77], 3), ([500], 5)])
def test_chain_hop_matches_bfs(mesh, graph, start, depth):
    start = np.array(start, np.int32)
    srel = device_put_rel(shard_rel(graph, 8), mesh)
    caps = dict(edge_cap=8192, out_cap=1024, seen_cap=2048)
    fr, seen = pad(start, 1024), pad(start, 2048)
    # numpy oracle: BFS layers with global seen set (loop=false semantics)
    seen_np = set(start.tolist())
    frontier = start
    for _ in range(depth):
        fr_in = np.asarray(fr)
        fr, seen, edges, needs, nbrs, seg, shard_edges, kept = chain_hop(
            mesh, srel, fr, seen, **caps)
        assert np.all(np.asarray(needs) <= np.array([1024, 2048, 8192]))
        assert (int(edges) == int(np.asarray(shard_edges).sum())
                == np_edges(graph, frontier))
        nxt = np_neighbors(graph, frontier)
        # the kept edges are the frontier's out-edges to nodes not seen
        # before this hop, parents read through seg
        nbrs, seg = np.asarray(nbrs), np.asarray(seg)
        m = nbrs != SENTINEL32
        assert int(kept) == int(m.sum())
        want = np_pairs(graph, frontier)
        want = want[~np.isin(want[:, 1], sorted(seen_np))]
        got = np.stack([fr_in[seg[m]], nbrs[m]], axis=1).astype(np.int64)
        np.testing.assert_array_equal(got[np.lexsort(got.T[::-1])], want)
        fresh = np.array(sorted(set(nxt.tolist()) - seen_np), np.int32)
        seen_np |= set(fresh.tolist())
        frontier = fresh
    got_seen = np.asarray(seen)
    got_seen = got_seen[got_seen != SENTINEL32]
    np.testing.assert_array_equal(got_seen, np.array(sorted(seen_np), np.int32))
    got_last = np.asarray(fr)
    got_last = got_last[got_last != SENTINEL32]
    np.testing.assert_array_equal(got_last, frontier)


def test_overflow_is_detectable(mesh, graph):
    """Truncation must surface in the returned counts: a shard's (or a ring
    step's) edges over edge_cap, and a chained hop's frontier, seen set or
    edges over its caps."""
    frontier = np.arange(200, dtype=np.int32)
    srel = device_put_rel(shard_rel(graph, 8), mesh)
    *_, mse = matrix_hop(mesh, srel, pad(frontier, 256), edge_cap=16)
    assert int(mse) > 16
    # the witness is the true need, not the clipped count
    *_, full, _mx = matrix_hop(mesh, srel, pad(frontier, 256), edge_cap=4096)
    assert int(mse) == int(np.asarray(full).max())

    chunks = shard_frontier(frontier, 8, f_cap=32)
    *_, rmse = ring_matrix_hop(mesh, srel, chunks, edge_cap=8)
    assert int(rmse) > 8

    small = 32
    start = np.arange(20, dtype=np.int32)
    needs = np.asarray(chain_hop(
        mesh, srel, pad(start, small), pad(start, 64), edge_cap=4096,
        out_cap=small, seen_cap=64)[3])
    assert needs[0] > small
    needs = np.asarray(chain_hop(
        mesh, srel, pad(start, 1024), pad(start, 64), edge_cap=4096,
        out_cap=1024, seen_cap=64)[3])
    assert needs[0] <= 1024 and needs[1] > 64
    needs = np.asarray(chain_hop(
        mesh, srel, pad(start, 1024), pad(start, 2048), edge_cap=8,
        out_cap=1024, seen_cap=2048)[3])
    assert needs[2] > 8


def test_engine_mesh_matches_host_at_scale():
    """Full DQL engine on the 8-device mesh vs the host engine over a
    powerlaw graph: expansion, filters, recurse, reverse edges
    (reference: query results must not depend on cluster topology)."""
    from dgraph_tpu.engine import Engine
    from dgraph_tpu.models.synthetic import powerlaw_rel
    from dgraph_tpu.parallel.mesh import make_mesh
    from dgraph_tpu.store.schema import parse_schema
    from dgraph_tpu.store.store import StoreBuilder

    rel = powerlaw_rel(600, 4.0, seed=11)
    b = StoreBuilder(parse_schema(
        "friend: [uid] @reverse .\nscore: int @index(int) ."))
    n = rel.indptr.shape[0] - 1
    for s in range(n):
        b.add_value(s + 1, "score", (s * 7) % 100)
        for o in rel.row(s):
            b.add_edge(s + 1, "friend", int(o) + 1)
    st = b.finalize()

    host = Engine(st, device_threshold=10**9)
    mesh = Engine(st, device_threshold=0, mesh=make_mesh(8))
    for q in [
        "{ q(func: uid(0x1, 0x5, 0x9)) { uid friend { uid } } }",
        "{ q(func: le(score, 30), first: 40) { uid friend "
        "  @filter(gt(score, 50)) { uid score } } }",
        "{ r(func: uid(0x2)) @recurse(depth: 4) { uid friend } }",
        "{ q(func: uid(0x3)) { friend { friend { uid } } ~friend { uid } } }",
    ]:
        assert mesh.query(q) == host.query(q), q


def test_mesh_topk_matches_host_ordering():
    """Order-by pushdown (SortOverNetwork analog): per-shard top-k +
    on-mesh merge must equal the host lexsort for asc/desc, offsets,
    missing values, and datetime keys."""
    from unittest import mock

    from dgraph_tpu.engine import Engine
    from dgraph_tpu.parallel import dsort
    from dgraph_tpu.parallel.mesh import make_mesh
    from dgraph_tpu.store.schema import parse_schema
    from dgraph_tpu.store.store import StoreBuilder

    rng = np.random.default_rng(5)
    b = StoreBuilder(parse_schema(
        "score: int @index(int) .\nheight: float .\nborn: datetime ."))
    n = 500
    for u in range(1, n + 1):
        b.add_value(u, "score", int(rng.integers(0, 10_000)))
        if u % 3:  # a third of nodes have no height (missing sorts last)
            b.add_value(u, "height", float(rng.uniform(1.0, 2.0)))
        b.add_value(u, "born",
                    f"19{50 + int(rng.integers(0, 50)):02d}-01-0{1 + u % 9}")
    st = b.finalize()
    host = Engine(st, device_threshold=10**9)
    mesh = Engine(st, device_threshold=0, mesh=make_mesh(8))

    calls = []
    orig = dsort.mesh_topk

    def spy(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    queries = [
        "{ q(func: has(score), orderasc: score, first: 25) { uid score } }",
        "{ q(func: has(score), orderdesc: score, first: 10, offset: 5) "
        "  { uid score } }",
        "{ q(func: has(score), orderasc: height, first: 400) { uid } }",
        "{ q(func: has(score), orderdesc: born, first: 12) { uid born } }",
    ]
    # mesh engine has device_threshold=0, so eligible orderings route
    # through the pushdown; the spy proves the path is actually taken
    with mock.patch.object(dsort, "mesh_topk", spy):
        for q in queries:
            assert mesh.query(q) == host.query(q), q
    assert calls, "pushdown path never taken"


def test_ring_frontier_engine_route():
    """Frontiers past ring_threshold ride the sharded ring path from the
    ENGINE (VERDICT r2 item 7: previously a demo unreachable from DQL);
    results must match the host engine exactly."""
    import numpy as np

    from dgraph_tpu.engine import Engine
    from dgraph_tpu.models.synthetic import powerlaw_rel
    from dgraph_tpu.parallel.mesh import make_mesh
    from dgraph_tpu.store.store import StoreBuilder

    rel = powerlaw_rel(600, 5.0, seed=12)
    b = StoreBuilder()
    src = np.repeat(np.arange(600, dtype=np.int64),
                    np.diff(rel.indptr).astype(np.int64))
    b.add_edges("link", src + 1, rel.indices.astype(np.int64) + 1)
    for i in range(600):
        b.add_value(i + 1, "score", i % 17)
    store = b.finalize()

    q = ('{ q(func: has(link), first: 40) '
         '{ uid link { uid link { count(uid) } } } }')
    host = Engine(store, device_threshold=10**9).query(q)

    mesh_engine = Engine(store, device_threshold=0, mesh=make_mesh(8))
    ring = mesh_engine.query(q)
    assert ring == host

    # force EVERY mesh hop through the ring path
    from dgraph_tpu.engine.execute import Executor
    old = Executor.ring_threshold
    Executor.ring_threshold = 4
    try:
        forced = Engine(store, device_threshold=0,
                        mesh=make_mesh(8)).query(q)
    finally:
        Executor.ring_threshold = old
    assert forced == host
