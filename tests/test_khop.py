"""The k-hop neighbourhood count (Graph500's deep-traversal query) on the
level-tree lane kernel: an @recurse stage that no block renders is counted
on the device, and each consumer of its var is handed what it reads.

The graph is the benchmark's own generator (benchmark/generators/
graph500.py) at a small scale; every batch answer is held to the
per-query engine AND to the benchmark's plain reference
(benchmark/references/graph500.py: numpy only, a breadth-first search).
"""

import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from generators import graph500 as gen          # noqa: E402
from loaders import graph500 as loader          # noqa: E402
from references import graph500 as reference    # noqa: E402

from dgraph_tpu.dql.parser import parse         # noqa: E402
from dgraph_tpu.engine import Engine            # noqa: E402
from dgraph_tpu.engine.batch import plan_batch_groups, run_batch  # noqa: E402
from dgraph_tpu.engine.treebatch import TreePlan  # noqa: E402
from dgraph_tpu.utils.metrics import METRICS    # noqa: E402

PARAMS = {"scale": 11, "edgefactor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
          "structure_seed": 22}
COUNT = ("{ N as var(func: uid(%s)) @recurse(depth: %d, loop: false) "
         "{ link } q(func: uid(N)) { count(uid) } }")


@pytest.fixture(scope="module")
def g500():
    """(data, store, reference): the generator's arrays, the store the
    benchmark's loader builds from them, the plain reference on them."""
    from dgraph_tpu.store.schema import parse_schema
    from dgraph_tpu.store.store import Store, build_indexes
    data = gen.generate(PARAMS, seed=7)
    schema = parse_schema(gen.SCHEMA)
    uids, preds = loader.build(data, schema)
    build_indexes(preds)
    return (data, Store(uids=uids, schema=schema, preds=preds),
            reference.make(data, {}))


def reads(by: str) -> float:
    return METRICS.get("tree_var_reads_total", by=by)


def serve(store, qs):
    """(answers, plans) of one batch through plan_batch_groups ->
    run_batch; every query must ride one tree launch."""
    plans, leftover = plan_batch_groups(store, [parse(q) for q in qs])
    assert len(plans) == 1 and not leftover
    plan, idxs = plans[0]
    assert isinstance(plan, TreePlan) and idxs == list(range(len(qs)))
    out = run_batch(store, plan, 10**9)
    assert out is not None
    return out, plan


def check_counts(g500, nodes, depth):
    """A batch of count queries from `nodes` (node indices): equal to the
    per-query engine and to the plain reference, every one answered by
    the device's count."""
    _data, store, ref = g500
    qs = [COUNT % (hex(int(i) + 1), depth) for i in nodes]
    before = {by: reads(by) for by in ("count", "column", "edge_walk")}
    got, plan = serve(store, qs)
    assert [s.keep_hops for s in plan.stages] == [False]
    eng = Engine(store, device_threshold=10**9)
    assert got == [eng.query(q) for q in qs]
    assert got == [ref.answer({"seed": int(i) + 1, "depth": depth})
                   for i in nodes]
    assert reads("count") - before["count"] == len(qs)
    assert reads("column") == before["column"]
    assert reads("edge_walk") == before["edge_walk"]
    return got


@pytest.mark.parametrize("lanes", [32, 64])
@pytest.mark.parametrize("depth", [1, 2, 3, 6])
def test_counts_equal_engine_and_reference(g500, depth, lanes):
    data = g500[0]
    sources = np.nonzero(data["row_len"] > 0)[0]
    rng = np.random.default_rng([depth, lanes])
    check_counts(g500, rng.choice(sources, lanes, replace=False), depth)


def test_hub_seeds(g500):
    """The vertices with the most out-edges and the most in-edges: a lane
    whose first hop already covers a large share of the graph."""
    data = g500[0]
    indeg = np.bincount(data["dst"], minlength=int(data["n_nodes"]))
    indeg[data["row_len"] == 0] = -1          # a seed has an out-edge
    hubs = np.unique(np.concatenate([np.argsort(-data["row_len"])[:24],
                                     np.argsort(-indeg)[:24]]))[:32]
    got = check_counts(g500, hubs, 3)
    assert max(a["q"][0]["count"] for a in got) > int(data["n_nodes"]) // 2


def test_seeds_whose_neighbours_lead_nowhere(g500):
    """Every target of the seed has no out-edge: the frontier dies at
    hop 2 and the count is the seed and its targets."""
    data, _store, ref = g500
    rl, rs, dst = data["row_len"], data["row_start"], data["dst"]
    dead = [i for i in np.nonzero(rl > 0)[0]
            if not rl[dst[rs[i]:rs[i] + rl[i]]].any()]
    assert len(dead) >= 2
    rest = [i for i in np.nonzero(rl > 0)[0] if i not in dead]
    got = check_counts(g500, dead + rest[:32 - len(dead)], 3)
    assert [a["q"][0]["count"] for a in got[:len(dead)]] == [
        1 + len(set(dst[rs[i]:rs[i] + rl[i]].tolist())) for i in dead]


def test_partly_filled_word_and_aliased_count(g500):
    """40 queries ride 64 lanes; an alias names the count."""
    data, store, ref = g500
    nodes = np.nonzero(data["row_len"] > 0)[0][:40]
    qs = [COUNT.replace("count(uid)", "reach: count(uid)")
          % (hex(int(i) + 1), 3) for i in nodes]
    got, _plan = serve(store, qs)
    assert got == [{"q": [{"reach": ref.within(int(i), 3)}]}
                   for i in nodes]


def test_launch_returns_counts_and_no_hop_masks(g500):
    """The program of the count shape: per lane the population count of
    `seen` and the traversed edges, and no [depth, n+1, W] buffer."""
    from dgraph_tpu.engine.batch import _ell_for
    from dgraph_tpu.engine.treebatch import _pack_global, _tree_kernel_for
    data, store, ref = g500
    nodes = np.nonzero(data["row_len"] > 0)[0][:32]
    qs = [COUNT % (hex(int(i) + 1), 3) for i in nodes]
    plans, _left = plan_batch_groups(store, [parse(q) for q in qs])
    plan = plans[0][0]
    n = store.n_nodes
    rels = {("link", False): _ell_for(store, "link", False)}
    fn = _tree_kernel_for(store, plan, rels, n, 1)
    seeds = _pack_global(n, [np.array([i], np.int32) for i in nodes], 32)
    (seen, count, edges, pushed, slots, hops), = fn((seeds,), ())
    assert hops is None and seen.shape == (n + 1, 1)
    assert pushed.dtype == np.int32 and 0 <= int(pushed) <= 3
    assert slots.dtype == np.int32 and bool(slots) == bool(pushed)
    assert count.dtype == np.int32 and count.shape == (32,)
    assert count.tolist() == [ref.within(int(i), 3) for i in nodes]
    # traversed edges: the out-degrees of everything within 2 hops
    outdeg = np.asarray(data["row_len"])
    for q, i in enumerate(nodes):
        near = np.zeros(n, bool)
        near[i] = True
        for _ in range(2):
            near[ref._targets(np.nonzero(near)[0])] = True
        assert int(edges[q]) == int(outdeg[near].sum())


# ---------------------------------------------------------------------------
# the Kronecker graph's hub core as a dense block beside the ELL

@pytest.fixture(scope="module")
def g500_hub(g500):
    """g500 in a store of its own, whose `link` ELL is built under a rule
    of its size (ops/bfs.py _choose_dense at 32,768 edges: no break-even
    to speak of, a block of at most 256 x 512, a floor of 500 edges)."""
    from dgraph_tpu.engine.batch import _ell_for
    from dgraph_tpu.ops import bfs
    from dgraph_tpu.store.schema import parse_schema
    from dgraph_tpu.store.store import Store, build_indexes
    data, _store, ref = g500
    schema = parse_schema(gen.SCHEMA)
    uids, preds = loader.build(data, schema)
    build_indexes(preds)
    store = Store(uids=uids, schema=schema, preds=preds)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(bfs, "DENSE_CELLS_PER_EDGE", 1e9)
        mp.setattr(bfs, "DENSE_MAX_BYTES", 256 * 512)
        mp.setattr(bfs, "DENSE_MIN_EDGES", 500)
        g = _ell_for(store, "link", False)
    assert g.dense is not None and g.dense_edges > g.nnz // 10
    return data, store, ref


@pytest.mark.parametrize("lanes", [32, 64])
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_counts_over_the_hub_block(g500_hub, depth, lanes):
    """The same counts with a tenth and more of the in-edges answered by
    the block's product, and the pulled hops counted in in-edges."""
    from dgraph_tpu.engine.batch import _ell_for
    data, store, _ref = g500_hub
    g = _ell_for(store, "link", False)
    sources = np.nonzero(data["row_len"] > 0)[0]
    rng = np.random.default_rng([depth, lanes, 38])

    def counters():
        return [METRICS.get(f"kernel_{k}_total", family="tree")
                for k in ("edges_pulled", "edges_dense", "hops_run",
                          "hops_push")]

    before = counters()
    check_counts(g500_hub, rng.choice(sources, lanes, replace=False), depth)
    pulled, dense, run, push = (x - y for x, y in zip(counters(), before))
    assert run == depth
    assert (pulled, dense) == ((run - push) * g.nnz,
                               (run - push) * g.dense_edges)


# ---------------------------------------------------------------------------
# other consumers of the stage's var read its column, or, under a filter
# that index lookups answer, ask it about the filter's candidates

@pytest.fixture(scope="module")
def scored():
    """The same generator at scale 8 behind an Alpha, with a value on
    every vertex to filter and order by."""
    from dgraph_tpu.server.api import Alpha
    data = gen.generate({**PARAMS, "scale": 8}, seed=3)
    a = Alpha(device_threshold=10**9)
    a.alter("link: [uid] .\nscore: int @index(int) .")
    n = int(data["n_nodes"])
    lines = [f'<{i + 1}> <score> "{i * 7 % 23}"^^<xs:int> .'
             for i in range(n)]
    lines += [f"<{s + 1}> <link> <{d + 1}> ."
              for s, d in zip(data["src"].tolist(), data["dst"].tolist())]
    a.mutate(set_nquads="\n".join(lines))
    store = a.mvcc.read_view(a.oracle.read_only_ts())
    return data, store


COLUMN_SHAPES = {
    "filter_order_page":
        "{ N as var(func: uid(%s)) @recurse(depth: 3, loop: false) { link } "
        "q(func: uid(N), orderdesc: score, first: 7) "
        "@filter(le(score, 12)) { uid score } }",
    "not_filter_order_page":
        "{ N as var(func: uid(%s)) @recurse(depth: 3, loop: false) { link } "
        "q(func: uid(N), orderdesc: score, first: 7) "
        "@filter(not le(score, 12)) { uid score } }",
    "expanded_root":
        "{ N as var(func: uid(%s)) @recurse(depth: 2, loop: false) { link } "
        "q(func: uid(N)) { uid link (first: 2) { uid } } }",
    "uid_in_filter":
        "{ N as var(func: uid(%s)) @recurse(depth: 2, loop: false) { link } "
        "q(func: le(score, 3)) @filter(uid(N)) { uid } }",
    "count_var_reads_nodes":
        "{ N as var(func: uid(%s)) @recurse(depth: 2, loop: false) { link } "
        "q(func: uid(N)) { c as count(uid) } }",
    "leaf_var_in_recurse":
        "{ N as var(func: uid(%s)) @recurse(depth: 2, loop: false) "
        "{ link s as score } q(func: uid(N)) { count(uid) } "
        "m() { sum(val(s)) } }",
}


@pytest.mark.parametrize("shape", list(COLUMN_SHAPES))
def test_other_consumers_read_the_column(scored, shape):
    data, store = scored
    nodes = np.nonzero(data["row_len"] > 0)[0][:12]
    qs = [COLUMN_SHAPES[shape] % hex(int(i) + 1) for i in nodes]
    # an evaluable filter on the reader's root: its candidates are probed
    by, other = (("probe", "column") if shape == "filter_order_page"
                 else ("column", "probe"))
    before = {k: reads(k) for k in ("column", "probe", "edge_walk")}
    got, plan = serve(store, qs)
    assert not plan.stages[0].keep_hops
    eng = Engine(store, device_threshold=10**9)
    assert got == [eng.query(q) for q in qs]
    assert reads(by) - before[by] >= len(qs)
    assert reads(other) == before[other]
    assert reads("edge_walk") == before["edge_walk"]


def test_rendered_recurse_var_is_an_edge_walk(scored):
    """A recurse block that IS rendered keeps its hop masks, and a reader
    of its var is counted as answered by the host's walk."""
    data, store = scored
    nodes = np.nonzero(data["row_len"] > 0)[0][:8]
    qs = ["{ N as q(func: uid(%s)) @recurse(depth: 2, loop: false) "
          "{ uid link @filter(le(score, 15)) } "
          "p(func: uid(N), first: 4) { uid } }" % hex(int(i) + 1)
          for i in nodes]
    before = reads("edge_walk")
    got, plan = serve(store, qs)
    assert plan.stages[0].keep_hops
    eng = Engine(store, device_threshold=10**9)
    assert got == [eng.query(q) for q in qs]
    assert reads("edge_walk") - before == len(qs)


# ---------------------------------------------------------------------------
# the integer counter

def test_lane_sums_pass_float32(g500):
    """Out-degrees whose sum a lane passes 2^24 (a 3-hop at scale 22
    passes 4*10^7): the device's integers equal numpy's."""
    import jax.numpy as jnp

    from dgraph_tpu.ops.bfs import _lane_sums
    rng = np.random.default_rng(5)
    n, W = 70_001, 2
    mask = rng.integers(0, 1 << 32, (n + 1, W), dtype=np.uint64
                        ).astype(np.uint32)
    mask[n] = 0
    weights = rng.integers(0, 2000, n).astype(np.int32)
    weights[rng.choice(n, 50, replace=False)] = rng.integers(
        100_000, 900_000, 50)
    bits = (mask[:n, :, None] >> np.arange(32, dtype=np.uint32)) & 1
    bits = bits.reshape(n, 64).astype(np.int64)
    want = (bits * weights[:, None].astype(np.int64)).sum(axis=0)
    assert want.min() > 1 << 24
    got = _lane_sums(jnp.asarray(mask), jnp.asarray(weights), n, W, 32)
    assert got.dtype == jnp.int32 and got.tolist() == want.tolist()
    assert _lane_sums(jnp.asarray(mask), None, n, W, 32).tolist() == \
        bits.sum(axis=0).tolist()


# -- a recurse stage's pushed hops, on the served path ----------------------

def _store_with_reverse():
    """The generator's graph (another deal of the names) as a store of its
    own, `link` with its reverse so that a shortest group can run over
    the same relation; its out-CSR is not built yet."""
    from dgraph_tpu.store.schema import parse_schema
    from dgraph_tpu.store.store import (PredicateData, Store,
                                        _csr_from_pairs, build_indexes)
    data = gen.generate(PARAMS, seed=3)
    n = int(data["n_nodes"])
    schema = parse_schema("link: [uid] @reverse .\n")
    pd = PredicateData(schema=schema.get("link"))
    pd.fwd = _csr_from_pairs(data["src"], data["dst"], n)
    pd.rev = _csr_from_pairs(data["dst"], data["src"], n)
    preds = {"link": pd}
    build_indexes(preds)
    return data, Store(uids=np.arange(1, n + 1, dtype=np.int64),
                       schema=schema, preds=preds)


@pytest.fixture
def out_csr_spans():
    """The names of the out-CSR's build and upload spans, as they close."""
    from dgraph_tpu.utils import tracing
    names = []

    def sink(s):
        if s.attrs.get("part") == "out_csr":
            names.append(s.name)

    tracing.add_sink(sink)
    yield names
    tracing.remove_sink(sink)


def _tree_hops():
    return [METRICS.get(f"kernel_{k}_total", family="tree")
            for k in ("hops_run", "hops_push", "push_slots")]


def _link_dev(store):
    from dgraph_tpu.engine.batch import _cache_host
    return _cache_host(store, "link", False)._ell_devs[("link", False)]


@pytest.mark.parametrize("depth", [2, 3])
def test_khop_counts_push_their_first_hop(out_csr_spans, depth):
    """A /query/batch-shaped group of k-hop counts rides one launch whose
    recurse stage pushes the hops its frontier fits (hop 1 of seeds with
    a few out-edges) and pulls the others: the counts are the host
    engine's, the device's two counts of hops and its count of the
    slots pushed reach the counters, and the relation's out-CSR is built
    and uploaded once, however many launches read it."""
    from dgraph_tpu.engine.batch import _ell_for
    from dgraph_tpu.ops.bfs import push_caps

    data, store = _store_with_reverse()
    eng = Engine(store, device_threshold=10**9)
    f_cap, e_cap, _chunk = push_caps(_ell_for(store, "link", False))
    rl = data["row_len"]
    few = np.nonzero((rl > 0) & (rl <= e_cap // f_cap))[0]
    assert f_cap >= 4 and len(few) >= 2 * f_cap
    hubs = np.argsort(-rl)[:f_cap]               # their hop 1 is over a cap
    for nodes, pushes in ((few[:f_cap], True), (few[f_cap:2 * f_cap], True),
                          (hubs, False)):
        qs = [COUNT % (hex(int(i) + 1), depth) for i in nodes]
        run0, push0, slots0 = _tree_hops()
        got, _plan = serve(store, qs)
        assert got == [eng.query(q) for q in qs]
        assert all(a["q"][0]["count"] > 1 for a in got)
        run1, push1, slots1 = _tree_hops()
        assert run1 - run0 == depth
        assert (1 <= push1 - push0 < depth) if pushes else push1 == push0
        # hop 1's slots are the seeds' own out-edges
        assert slots1 - slots0 >= rl[nodes].sum() if pushes \
            else slots1 == slots0
    assert out_csr_spans == ["batch.build_ell", "batch.upload_ell"]
    assert _link_dev(store).out is not None


def test_shortest_group_reuses_the_tree_groups_out_csr(out_csr_spans):
    """A shortest group after a tree group over the same relation reads
    the out-CSR the tree group brought: one DeviceEll.out, built once."""
    data, store = _store_with_reverse()
    eng = Engine(store, device_threshold=10**9)
    rl = data["row_len"]
    nodes = np.nonzero(rl > 0)[0][:4]
    serve(store, [COUNT % (hex(int(i) + 1), 3) for i in nodes])
    dev = _link_dev(store)
    out = dev.out
    assert out is not None
    # targets three edges away and no nearer, so that every lane is
    # opened (a pair one or two edges apart is settled before a launch)
    rs, dst = data["row_start"], data["dst"]
    pairs = []
    for i in nodes:
        seen, far = {int(i)}, [int(i)]
        for _ in range(3):
            far = sorted({int(d) for m in far
                          for d in dst[rs[m]:rs[m] + rl[m]]} - seen)
            seen.update(far)
        pairs.append((int(i), far[0] if far else int(dst[rs[i]])))
    qs = ['{ path as shortest(from: %s, to: %s) { link } '
          'p(func: uid(path)) { uid } }' % (hex(a + 1), hex(b + 1))
          for a, b in pairs]
    launches = METRICS.get("kernel_group_launches_total", family="shortest")
    plans, leftover = plan_batch_groups(store, [parse(q) for q in qs])
    assert len(plans) == 1 and not leftover
    assert not isinstance(plans[0][0], TreePlan)
    assert run_batch(store, plans[0][0], 10**9) == [eng.query(q) for q in qs]
    assert METRICS.get("kernel_group_launches_total",
                       family="shortest") == launches + 1
    assert dev.out is out
    assert out_csr_spans == ["batch.build_ell", "batch.upload_ell"]
