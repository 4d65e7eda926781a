"""Batched @recurse serving: lane kernel == per-query engine, exactly.

Reference parity: the reference serves concurrent query mixes with
per-query goroutines; here compatible @recurse queries share one
lane-packed kernel launch (engine/batch.py)."""

import json
import urllib.request

import numpy as np
import pytest

from dgraph_tpu.dql.parser import parse
from dgraph_tpu.engine import Engine
from dgraph_tpu.engine.batch import plan_batch, run_batch
from dgraph_tpu.server.api import Alpha

SCHEMA = """
name: string @index(exact) .
score: int .
follows: [uid] @reverse .
"""


@pytest.fixture(scope="module")
def alpha():
    rng = np.random.default_rng(5)
    a = Alpha(device_threshold=10**9)
    a.alter(SCHEMA)
    n = 400
    lines = [f'_:p{i} <name> "p{i}" .\n_:p{i} <score> "{i % 23}"^^<xs:int> .'
             for i in range(n)]
    for i in range(n):
        for j in rng.choice(n, 4, replace=False):
            if i != j:
                lines.append(f"_:p{i} <follows> _:p{j} .")
    a.mutate(set_nquads="\n".join(lines))
    return a


def _queries(n=12, depth=3):
    return [('{ q(func: eq(name, "p%d")) @recurse(depth: %d) '
             '{ name score follows } }' % (i * 17 % 400, depth))
            for i in range(n)]


def test_batch_equals_per_query(alpha):
    qs = _queries()
    store = alpha.mvcc.read_view(alpha.oracle.read_only_ts())
    plan = plan_batch(store, [parse(q) for q in qs])
    assert plan is not None, "batch plan should be eligible"
    got = run_batch(store, plan, 10**9)
    eng = Engine(store, device_threshold=10**9)
    want = [eng.query(q) for q in qs]
    assert got == want


def test_batch_reverse_and_depths(alpha):
    store = alpha.mvcc.read_view(alpha.oracle.read_only_ts())
    qs = [('{ q(func: eq(name, "p%d")) @recurse(depth: 2) '
           '{ name ~follows } }' % (i * 31 % 400)) for i in range(8)]
    plan = plan_batch(store, [parse(q) for q in qs])
    assert plan is not None and plan.reverse is True
    got = run_batch(store, plan, 10**9)
    eng = Engine(store, device_threshold=10**9)
    assert got == [eng.query(q) for q in qs]


def test_plan_rejects_incompatible(alpha):
    store = alpha.mvcc.read_view(alpha.oracle.read_only_ts())
    base = _queries(6)
    # mixed depths
    mixed = base[:5] + ['{ q(func: eq(name, "p1")) @recurse(depth: 9) '
                        '{ name follows } }']
    assert plan_batch(store, [parse(q) for q in mixed]) is None
    # filters on the edge: no longer a rejection — they take the
    # level-tree kernel (engine/treebatch.py) and must match the engine
    filt = ['{ q(func: eq(name, "p1")) @recurse(depth: 3) '
            '{ name follows @filter(ge(score, 5)) } }'] * 6
    from dgraph_tpu.engine.treebatch import TreePlan
    fplan = plan_batch(store, [parse(q) for q in filt])
    assert isinstance(fplan, TreePlan)
    eng = Engine(store, device_threshold=10**9)
    assert run_batch(store, fplan, 10**9) == [eng.query(q) for q in filt]
    # below MIN_BATCH
    assert plan_batch(store, [parse(q) for q in base[:2]]) is None
    # client-controlled depth beyond the kernel cap falls back to the
    # per-query engine (host loop early-exits; no unbounded device scan)
    deep = ['{ q(func: eq(name, "p1")) @recurse(depth: 100000) '
            '{ name follows } }'] * 6
    assert plan_batch(store, [parse(q) for q in deep]) is None


def test_query_batch_endpoint_and_fallback(alpha):
    from dgraph_tpu.server.http import make_http_server, serve_background
    srv = make_http_server(alpha, "127.0.0.1", 0)
    serve_background(srv)
    port = srv.server_address[1]
    qs = _queries(8)
    # one incompatible query forces the per-query fallback: results must
    # still be correct and ordered
    qs_mixed = qs[:4] + ['{ q(func: eq(name, "p3")) { name score } }'] \
        + qs[4:]
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/query/batch",
        data=json.dumps({"queries": qs_mixed}).encode(),
        headers={"Content-Type": "application/json"})
    out = json.load(urllib.request.urlopen(req, timeout=60))["data"]
    eng = Engine(alpha.mvcc.read_view(alpha.oracle.read_only_ts()),
                 device_threshold=10**9)
    assert out == [eng.query(q) for q in qs_mixed]
    # and the fully-compatible batch through the same endpoint
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/query/batch",
        data=json.dumps({"queries": qs}).encode(),
        headers={"Content-Type": "application/json"})
    out = json.load(urllib.request.urlopen(req, timeout=60))["data"]
    assert out == [eng.query(q) for q in qs]
    srv.shutdown()


def test_batch_error_isolation(alpha):
    """A malformed query yields an error object in its slot; the rest of
    the batch still answers (code-review finding)."""
    qs = _queries(5) + ["{ broken(func: frobnicate(name"]
    out = alpha.query_batch(qs)
    assert len(out) == 6
    assert "errors" in out[5]
    eng = Engine(alpha.mvcc.read_view(alpha.oracle.read_only_ts()),
                 device_threshold=10**9)
    assert out[:5] == [eng.query(q) for q in _queries(5)]


def test_batch_kernel_cache_reuse(alpha):
    """The ELL graph and compiled kernel build once per snapshot, even
    through per-request view wrappers (code-review finding)."""
    import dgraph_tpu.engine.batch as b
    qs = _queries(6)
    alpha.query_batch(qs)
    store = alpha.mvcc.read_view(alpha.oracle.read_only_ts())
    host = getattr(store, "_ell_host", store)
    assert hasattr(host, "_ell_cache") or hasattr(store, "_ell_cache")
    cache_holder = host if hasattr(host, "_ell_cache") else store
    n_before = len(cache_holder._ell_cache)
    alpha.query_batch(qs)       # second batch: no rebuild
    assert len(cache_holder._ell_cache) == n_before


def test_mixed_batch_splits_into_groups(alpha):
    """A mixed batch splits into compatible kernel groups plus per-query
    leftovers; results come back in order, identical to the per-query
    engine, and error slots stay isolated."""
    from dgraph_tpu.engine.batch import plan_batch_groups
    store = alpha.mvcc.read_view(alpha.oracle.read_only_ts())
    fwd = ['{ q(func: eq(name, "p%d")) @recurse(depth: 3) '
           '{ name follows } }' % i for i in range(5)]
    rev = ['{ q(func: eq(name, "p%d")) @recurse(depth: 2) '
           '{ name ~follows } }' % i for i in range(4)]
    odd = ['{ q(func: eq(name, "p1")) { name } }',
           '{ q(func: bogus_func(name)) { name } }']
    qs = [fwd[0], rev[0], fwd[1], odd[0], rev[1], fwd[2], rev[2],
          fwd[3], odd[1], rev[3], fwd[4]]
    plans, leftover = plan_batch_groups(store, [parse(q) for q in qs
                                                if "bogus" not in q])
    assert len(plans) == 2  # fwd-depth3 and rev-depth2 groups

    outs = alpha.query_batch(qs)
    eng = Engine(store, device_threshold=10**9)
    for q, o in zip(qs, outs):
        if "bogus" in q:
            assert "errors" in o, o
        else:
            assert o == eng.query(q), q


def _uid_of(alpha, name: str) -> str:
    eng = Engine(alpha.mvcc.read_view(alpha.oracle.read_only_ts()),
                 device_threshold=10**9)
    out = eng.query('{ q(func: eq(name, "%s")) { uid } }' % name)
    return out["q"][0]["uid"]


def test_plan_cache_skips_plan_and_build_spans(alpha):
    """A second identical batch is a plan-cache hit: no batch.plan span,
    no batch.build_ell span, no re-parse (ISSUE 7 plan memoization)."""
    from dgraph_tpu.utils import tracing
    from dgraph_tpu.utils.metrics import METRICS

    qs = _queries(7, depth=2)
    alpha.query_batch(qs)       # prime plan + ELL caches

    def counts():
        snap = METRICS.snapshot()["counters"]
        return (sum(v for k, v in snap.items()
                    if k.startswith("plan_cache_hits_total")),
                sum(v for k, v in snap.items()
                    if k.startswith("plan_cache_misses_total")))

    h0, m0 = counts()
    before = len([s for s in tracing.recent(512)
                  if s.name in ("batch.plan", "batch.build_ell")])
    out = alpha.query_batch(qs)
    h1, m1 = counts()
    after = len([s for s in tracing.recent(512)
                 if s.name in ("batch.plan", "batch.build_ell")])
    assert h1 == h0 + 1 and m1 == m0, "second batch must hit the memo"
    assert after == before, "warm batch must not re-plan or re-build"
    eng = Engine(alpha.mvcc.read_view(alpha.oracle.read_only_ts()),
                 device_threshold=10**9)
    assert out == [eng.query(q) for q in qs]


def test_warm_plan_dispatch_guard(alpha):
    """Tier-1 perf guard: with plans + ELL + kernels warm, batch dispatch
    overhead stays bounded — plan caching can't silently regress into
    re-planning/re-building per batch (generous wall bound; the real
    assertion is the span/memo one above)."""
    import time as _time
    qs = _queries(10, depth=2)
    alpha.query_batch(qs)       # cold: plan + build + compile
    t0 = _time.perf_counter()
    for _ in range(3):
        alpha.query_batch(qs)
    warm_avg = (_time.perf_counter() - t0) / 3
    assert warm_avg < 2.0, f"warm batch dispatch too slow: {warm_avg:.2f}s"


def test_shortest_batch_rides_kernel_group(alpha):
    """An IC13-shaped batch (shortest + uid(path) companion block) forms
    a shortest kernel group and is bit-identical to the host path."""
    from dgraph_tpu.engine.batch import _ShortestPlan
    from dgraph_tpu.utils.metrics import METRICS

    pairs = [("p1", "p40"), ("p3", "p77"), ("p5", "p250"),
             ("p7", "p123"), ("p11", "p319"), ("p13", "p2")]
    qs = ['{ path as shortest(from: %s, to: %s) { follows } '
          'p(func: uid(path)) { name } }'
          % (_uid_of(alpha, a), _uid_of(alpha, b)) for a, b in pairs]
    store = alpha.mvcc.read_view(alpha.oracle.read_only_ts())
    plan = plan_batch(store, [parse(q) for q in qs])
    assert isinstance(plan, _ShortestPlan), "IC13 shape must group"
    snap0 = METRICS.snapshot()["counters"]
    q0 = sum(v for k, v in snap0.items()
             if k.startswith("kernel_group_queries_total")
             and 'family="shortest"' in k)
    got = run_batch(store, plan, 10**9)
    snap1 = METRICS.snapshot()["counters"]
    q1 = sum(v for k, v in snap1.items()
             if k.startswith("kernel_group_queries_total")
             and 'family="shortest"' in k)
    assert q1 == q0 + len(qs)
    eng = Engine(store, device_threshold=10**9)
    assert got == [eng.query(q) for q in qs]


def test_shortest_numpaths_batch_matches_host(alpha):
    """IC14-shaped (numpaths > 1, unweighted) rides the level-DAG kernel
    family; path sets AND enumeration order match the host exactly."""
    from dgraph_tpu.engine.batch import _ShortestPlan

    pairs = [("p2", "p41"), ("p4", "p78"), ("p6", "p251"),
             ("p8", "p124"), ("p10", "p320")]
    qs = ['{ path as shortest(from: %s, to: %s, numpaths: 2) '
          '{ follows } }'
          % (_uid_of(alpha, a), _uid_of(alpha, b)) for a, b in pairs]
    store = alpha.mvcc.read_view(alpha.oracle.read_only_ts())
    plan = plan_batch(store, [parse(q) for q in qs])
    assert isinstance(plan, _ShortestPlan) and not plan.first_visit
    got = run_batch(store, plan, 10**9)
    eng = Engine(store, device_threshold=10**9)
    assert got == [eng.query(q) for q in qs]


def test_shortest_mixed_batch_and_endpoint(alpha):
    """shortest groups coexist with recurse groups + leftovers through
    the serving endpoint, results in order."""
    u = [_uid_of(alpha, f"p{i}") for i in (1, 2, 3, 4, 9, 12, 15, 21)]
    sp = ['{ path as shortest(from: %s, to: %s) { follows } }'
          % (u[i], u[i + 4]) for i in range(4)]
    rec = _queries(5)
    odd = ['{ q(func: eq(name, "p3")) { name } }']
    qs = [sp[0], rec[0], sp[1], odd[0], rec[1], sp[2], rec[2],
          sp[3], rec[3], rec[4]]
    out = alpha.query_batch(qs)
    eng = Engine(alpha.mvcc.read_view(alpha.oracle.read_only_ts()),
                 device_threshold=10**9)
    assert out == [eng.query(q) for q in qs]


def test_rebuild_single_query_lane_extraction(alpha):
    """_rebuild_recurse_data regression: the single-query rebuild picks
    the right lane past word 0 (q ≥ 32) and matches the per-query
    engine's recurse tree."""
    import jax

    from dgraph_tpu.engine.batch import (_ell_for, _rebuild_recurse_data,
                                         _recurse_for)
    from dgraph_tpu.engine.recurse import RecurseData  # noqa: F401

    store = alpha.mvcc.read_view(alpha.oracle.read_only_ts())
    qs = _queries(40, depth=3)
    blocks = [parse(q) for q in qs]
    plan = plan_batch(store, blocks)
    assert plan is not None and len(plan.blocks) == 40
    from dgraph_tpu.engine.execute import Executor
    from dgraph_tpu.ops.bfs import pack_seed_masks
    ex0 = Executor(store, device_threshold=10**9)
    seeds = [ex0.root_ranks(sg) for sg in plan.blocks]
    g = _ell_for(store, plan.attr, plan.reverse)
    seed_lists = seeds + [np.zeros(0, np.int32)] * (64 - len(seeds))
    mask0 = pack_seed_masks(g, seed_lists)
    fn = _recurse_for(store, plan.attr, plan.reverse, mask0.shape[1])
    _l, _s, _e, hops = fn(jax.device_put(mask0), plan.depth, True)
    hops = np.asarray(hops)
    rel = store.rel(plan.attr, plan.reverse)
    q = 35
    roots = np.unique(seeds[q]).astype(np.int32)
    data = _rebuild_recurse_data(store, g, rel, hops, q, plan.blocks[q],
                                 roots, plan.depth)
    # oracle: host recurse edge set for the same query
    eng = Engine(store, device_threshold=10**9)
    want = eng.query(qs[q])
    got = run_batch(store, plan, 10**9)[q]
    assert got == want
    if 0 in data.edges:
        p, c = data.edges[0]
        assert len(p) == len(c) and len(np.unique(data.all_nodes)) == \
            len(data.all_nodes)


def test_fold_carries_ell_cache(alpha):
    """Rollup with layers that do NOT touch `follows` (and add no new
    uids) carries the ELL cache to the new snapshot instead of
    rebuilding (ISSUE 7 incremental rebuild on fold)."""
    alpha.query_batch(_queries(6))          # prime ELL cache
    store = alpha.mvcc.read_view(alpha.oracle.read_only_ts())
    from dgraph_tpu.engine.batch import _cache_host
    host = _cache_host(store, "follows", False)
    g_old = host._ell_cache[("follows", False)]
    assert g_old is not None
    # touch an EXISTING node's value on another predicate: vocab stable
    uid = _uid_of(alpha, "p9")
    alpha.mutate(set_nquads=f'<{uid}> <score> "99"^^<xs:int> .')
    new_store = alpha.mvcc.rollup()
    carried = getattr(new_store, "_ell_cache", {})
    assert carried.get(("follows", False)) is g_old, \
        "untouched predicate's ELL must carry across the fold"
    # and the folded store still answers identically through the cache
    out = alpha.query_batch(_queries(6))
    eng = Engine(alpha.mvcc.read_view(alpha.oracle.read_only_ts()),
                 device_threshold=10**9)
    assert out == [eng.query(q) for q in _queries(6)]


@pytest.fixture(scope="module")
def chain():
    """p0 -> p1 -> ... -> p13 with a shortcut p0 -> p2: paths longer
    than one SHORTEST_STAGE, two routes to every node past p1, and no
    way back. Apart from it (p14 on), a ring c0 -> c1 -> ... -> c5 -> c0
    with a tail c3 -> t0 <-> t1: every ring node lies on a cycle through
    every other, and t1 is its own second level."""
    a = Alpha(device_threshold=10**9)
    a.alter("name: string @index(exact) .\nfollows: [uid] @reverse .")
    lines = [f'_:p{i} <name> "p{i}" .' for i in range(22)]
    lines += [f"_:p{i} <follows> _:p{i + 1} ." for i in range(13)]
    lines.append("_:p0 <follows> _:p2 .")
    lines += [f"_:p{14 + i} <follows> _:p{14 + (i + 1) % 6} ."
              for i in range(6)]
    lines += ["_:p17 <follows> _:p20 .", "_:p20 <follows> _:p21 .",
              "_:p21 <follows> _:p20 ."]
    uids = a.mutate(set_nquads="\n".join(lines))["uids"]
    return a, [uids[f"_:p{i}"] for i in range(22)]


# pairs of the chain 1 to 6 edges apart (p0 -> p5 takes the shortcut)
BY_LENGTH = [(0, 1), (3, 5), (1, 4), (0, 5), (1, 6), (1, 7)]
C, T = 14, 20       # the ring's c0 and the tail's t0

# (pairs, shortest's extra arguments, hops of each launch, lanes closed
# by each rule): a launch stops at the hop that closes its last open
# lane, and a numpaths = 1 lane closes TWO hops ahead of its target, at
# the hop that reaches an in-neighbour of one of the target's
# in-neighbours: a path of d edges takes d - 2 hops, and d - 1 where the
# lane was left with the first level alone
STOPPING_GROUPS = {
    # p0 -> p12 is 11 edges (p0 -> p2, then ten on): p10 shows at hop
    # 9, a full stage and 1 more. p1 -> p4 closes at hop 1, p2 -> p9 at
    # hop 5; p3 -> p5 is two edges, settled before the launch
    "two-launches": ([(0, 12), (1, 4), (3, 5), (2, 9)], "", [8, 1],
                     {"seed2": 1, "ahead2": 3}),
    # nothing leads to p0, so the searches from p13 and from p9 are
    # settled before the launch, as is p4 -> p6 (two edges); p0 -> p5 is
    # 4 edges, closed at hop 2 (p3 shows)
    "unreachable": ([(13, 0), (9, 0), (0, 5), (4, 6)], "", [2],
                    {"seed": 2, "seed2": 1, "ahead2": 1}),
    # the level-DAG closes a lane only when nothing is left to expand:
    # from p0 every node is passed by hop 13, hop 14 is empty
    "numpaths-2": ([(0, 3), (1, 4), (0, 12), (5, 6)], ", numpaths: 2",
                   [8, 6], {"exhausted": 4}),
    # p0 -> p1, p0 -> p2 and p5 -> p6 are edges, p3 -> p5 is two:
    # settled before a launch that is never made
    "one-edge": ([(0, 1), (0, 2), (3, 5), (5, 6)], "", [],
                 {"seed": 3, "seed2": 1}),
    # every target is p0, which nothing leads to: no lane is opened and
    # no launch made, as for a batch whose every source is its target
    "no-in-edge": ([(13, 0), (9, 0), (5, 0), (1, 0)], "", [], {"seed": 4}),
    # the cap counts edges: p0 -> p5 and p1 -> p5 are 4 (p3 shows at
    # hop 2), p0 -> p6 and p1 -> p6 are 5: they close at hop 3, the last
    # the cap allows, on a path of one edge too many, and answer nothing
    "depth-cap": ([(0, 5), (0, 6), (1, 5), (1, 6)], ", depth: 4", [3],
                  {"ahead2": 4}),
    # a cap of one edge allows no hop: the three pairs one edge apart are
    # settled before the launch, p3 -> p5 (2 edges) is cut by the cap,
    # and no launch is made
    "depth-one": ([(0, 1), (0, 2), (3, 5), (5, 6)], ", depth: 1", [],
                  {"seed": 3}),
    # one launch of every length: the pairs one and two edges apart are
    # settled before it, the others close at hops 1, 2, 3 and 4
    "lengths": (BY_LENGTH, "", [4], {"seed": 1, "seed2": 1, "ahead2": 4}),
    # the same pairs under every cap: depth - 1 hops at most, and a lane
    # that closes on a path of depth + 1 edges answers nothing
    "lengths-depth-1": (BY_LENGTH, ", depth: 1", [], {"seed": 1}),
    "lengths-depth-2": (BY_LENGTH, ", depth: 2", [1],
                        {"seed": 1, "seed2": 1, "ahead2": 1}),
    "lengths-depth-3": (BY_LENGTH, ", depth: 3", [2],
                        {"seed": 1, "seed2": 1, "ahead2": 2}),
    "lengths-depth-4": (BY_LENGTH, ", depth: 4", [3],
                        {"seed": 1, "seed2": 1, "ahead2": 3}),
    "lengths-depth-5": (BY_LENGTH, ", depth: 5", [4],
                        {"seed": 1, "seed2": 1, "ahead2": 4}),
    # NEAR2_MAX_EDGES patched to 1: p2, the one in-neighbour of p3, has
    # two in-edges, so the lanes to p3 keep the first level alone and
    # close a hop later than whole ones would (p1 -> p3, two edges, at
    # hop 1 where it would be settled before the launch; p0 -> p3 too);
    # p4 -> p6 and p1 -> p4 read one in-edge and are whole
    "capped": ([(1, 3), (0, 3), (4, 6), (1, 4)], "", [1],
               {"seed2": 1, "ahead": 2, "ahead2": 1}),
    # p12 reaches p13 and nothing more, p13 nothing: both searches for
    # p5 die out, at hops 2 and 1
    "exhausted": ([(12, 5), (13, 5), (1, 4), (3, 5)], "", [2],
                  {"seed2": 1, "ahead2": 1, "exhausted": 2}),
    # p0, the one in-neighbour of p1, has no in-edge: the lanes to p1
    # have no second level and run as with one, until they die out
    "no-second-level": ([(12, 1), (13, 1), (11, 1), (0, 1)], "", [3],
                        {"seed": 1, "exhausted": 3}),
    # around the ring: c1 -> c0 is 5 edges and c0 -> c5 too (hop 3);
    # c4 -> c0 is two, through c5; c0 -> t1 is 5 (c3 shows at hop 3),
    # and t1, which t0 leads back to, sits in its own second level
    "cycle": ([(C + 1, C), (C, C + 5), (C + 4, C), (C, T + 1)], "", [3],
              {"seed2": 1, "ahead2": 3}),
    # a source that its own target leads back to: t0 -> t1 and t1 -> t0
    # are edges; c3 -> t1 is two; c2 -> t1 three
    "cycle-of-two": ([(T, T + 1), (T + 1, T), (C + 3, T + 1),
                      (C + 2, T + 1)], "", [1],
                     {"seed": 2, "seed2": 1, "ahead2": 1}),
}
NEAR2_CAPS = {"capped": 1}
FOUND = {      # nodes on each answer's path, where the case has a gap
    "unreachable": [0, 0, 5, 3],
    "one-edge": [2, 2, 3, 2],
    "no-in-edge": [0, 0, 0, 0],
    "depth-cap": [5, 0, 5, 0],
    "depth-one": [2, 2, 0, 2],
    "lengths": [2, 3, 4, 5, 6, 7],
    **{f"lengths-depth-{k}": [d + 1 if d <= k else 0 for d in range(1, 7)]
       for k in range(1, 6)},
    "capped": [3, 3, 3, 4],
    "exhausted": [0, 0, 4, 3],
    "no-second-level": [0, 0, 0, 2],
    "cycle": [6, 6, 3, 6],
    "cycle-of-two": [2, 2, 3, 4],
}
CLOSED_BY = ("seed", "seed2", "ahead", "ahead2", "exhausted")


@pytest.mark.parametrize("case", sorted(STOPPING_GROUPS))
def test_shortest_batch_stops_with_its_last_lane(chain, case, monkeypatch):
    """Answers are byte-equal to the per-query engine's, each launch runs
    the hops some open lane needs and none after (the host's scan reads
    the device's exit: hops used are hops run), and every lane that
    closes is counted once, under the rule that closed it."""
    from dgraph_tpu.engine import batch
    from dgraph_tpu.utils.metrics import METRICS

    alpha, u = chain
    pairs, extra, launches, closed = STOPPING_GROUPS[case]
    if case in NEAR2_CAPS:
        monkeypatch.setattr(batch, "NEAR2_MAX_EDGES", NEAR2_CAPS[case])
    qs = ['{ path as shortest(from: %s, to: %s%s) { follows } '
          'p(func: uid(path)) { name } }' % (u[i], u[j], extra)
          for i, j in pairs]

    def hops():
        return (METRICS.get("kernel_hops_run_total", family="shortest"),
                METRICS.get("kernel_hops_used_total", family="shortest"),
                METRICS.get("jit_cache_hits_total", kernel="bfs.ell_step")
                + METRICS.get("jit_compile_total", kernel="bfs.ell_step"),
                METRICS.get("kernel_group_launches_total",
                            family="shortest"),
                METRICS.get("kernel_near2_capped_total"))

    def lanes_closed():
        return {by: METRICS.get("kernel_lanes_closed_total",
                                family="shortest", by=by)
                for by in CLOSED_BY}

    before, closed0 = hops(), lanes_closed()
    got = alpha.query_batch(qs)
    after, closed1 = hops(), lanes_closed()
    assert tuple(b - a for a, b in zip(before, after)) == \
        (sum(launches), sum(launches), len(launches), bool(launches),
         2 * (case in NEAR2_CAPS))
    assert {by: closed1[by] - closed0[by] for by in CLOSED_BY} == \
        {**dict.fromkeys(CLOSED_BY, 0), **closed}
    eng = Engine(alpha.mvcc.read_view(alpha.oracle.read_only_ts()),
                 device_threshold=10**9)
    want = [eng.query(q) for q in qs]
    assert json.dumps(got) == json.dumps(want)
    if case in FOUND:
        assert [len(o.get("p", [])) for o in got] == FOUND[case]


def _plain_launch(store, attr, pairs, levels, near2_max):
    """The plain count behind a launch of first-visit lanes and its
    counters: a search a lane from each pair's source, but for the pairs
    settled before the launch (a target with no in-edge, or one or two
    edges from its source); a lane closes at the hop that reaches an
    in-neighbour of its target, or an in-neighbour of one where those
    rows have `near2_max` in-edges at most, or nothing new; a hop pushes
    when the rows that some lane reached last hop and that have an
    out-edge, the sum of their out-degrees and the largest of them fit
    ops/bfs.py push_caps of the relation's ELL. Returns (hops run, hops
    pushed, the slots they pushed, lanes closed by each rule)."""
    from dgraph_tpu.engine.batch import _ell_for
    from dgraph_tpu.ops.bfs import push_caps

    rel, rrel = store.rel(attr, False), store.rel(attr, True)
    deg = np.diff(rel.indptr)
    f_cap, e_cap, chunk = push_caps(_ell_for(store, attr, False))
    src = store.rank_of(np.asarray([a for a, _ in pairs], np.int64))
    dst = store.rank_of(np.asarray([b for _, b in pairs], np.int64))
    closed = dict.fromkeys(CLOSED_BY, 0)
    near, near2, open_ = {}, {}, set()
    for q, (s, d) in enumerate(zip(src.tolist(), dst.tolist())):
        near[q] = set(rrel.row(d).tolist())
        far = [v for u in sorted(near[q]) for v in rrel.row(u).tolist()]
        near2[q] = set(far) if len(far) <= near2_max else set()
        if not near[q] or s in near[q]:
            closed["seed"] += 1
        elif s in near2[q]:
            closed["seed2"] += 1
        else:
            open_.add(q)
    fresh = [{int(s)} if q in open_ else set() for q, s in enumerate(src)]
    seen = [set(f) for f in fresh]
    ran = pushed = slots = 0
    while open_ and ran < levels:
        rows = np.array(sorted(set().union(*fresh)), np.int64)
        rows = rows[deg[rows] > 0]
        if f_cap and len(rows) <= f_cap and deg[rows].sum() <= e_cap \
                and deg[rows].max(initial=0) <= chunk:
            pushed += 1
            slots += int(deg[rows].sum())
        ran += 1
        for q in range(len(pairs)):
            nxt = {int(v) for u in fresh[q] for v in rel.row(u)} - seen[q]
            fresh[q] = nxt
            seen[q] |= nxt
            by = "exhausted" if not nxt else "ahead" if nxt & near[q] \
                else "ahead2" if nxt & near2[q] else None
            if by and q in open_:
                open_.discard(q)
                closed[by] += 1
    return ran, pushed, slots, closed


def test_shortest_batch_mixes_pushed_and_pulled_hops():
    """On a graph large enough that a launch's first hop fits the pushed
    hop's caps and its later hops do not, the batch answers what the
    per-query path answers; the push counters move by the hops pushed and
    their slots, and stay under the hops run; and only the step program
    brings the out-CSR to the device: a @recurse batch on the same store
    does not."""
    from dgraph_tpu.engine.batch import NEAR2_MAX_EDGES, SHORTEST_STAGE, \
        _cache_host
    from dgraph_tpu.utils.metrics import METRICS

    rng = np.random.default_rng(11)
    a = Alpha(device_threshold=10**9)
    a.alter(SCHEMA)
    n = 1200            # 4,800 edges: the caps hold 7 rows, not hop 3's 16
    lines = [f'_:p{i} <name> "p{i}" .' for i in range(n)]
    for i in range(n):
        lines += [f"_:p{i} <follows> _:p{j} ."
                  for j in rng.choice(n, 4, replace=False) if i != j]
    uids = a.mutate(set_nquads="\n".join(lines))["uids"]

    def counters():
        return [METRICS.get(f"kernel_{k}_total", family="shortest")
                for k in ("hops_run", "hops_push", "push_slots")]

    store = a.mvcc.read_view(a.oracle.read_only_ts())
    a.query_batch(_queries(6, depth=2))
    dev = _cache_host(store, "follows", False)._ell_devs[("follows", False)]
    assert dev.out is None, "the lane @recurse family needs no out-CSR"

    pairs = [(uids[f"_:p{i}"], uids[f"_:p{j}"])
             for i, j in ((1, 240), (1, 77), (1, 950), (1, 123))]
    qs = ['{ path as shortest(from: %s, to: %s) { follows } '
          'p(func: uid(path)) { name } }' % p for p in pairs]
    before = counters()
    got = a.query_batch(qs)
    moved = tuple(b - a for a, b in zip(before, counters()))
    assert dev.out is not None
    eng = Engine(store, device_threshold=10**9)
    assert json.dumps(got) == json.dumps([eng.query(q) for q in qs])
    want_run, want_push, want_slots, _closed = _plain_launch(
        store, "follows", [(int(x, 16), int(y, 16)) for x, y in pairs],
        SHORTEST_STAGE, NEAR2_MAX_EDGES)
    assert moved == (want_run, want_push, want_slots)
    assert 0 < want_push < want_run, "the batch must mix both kinds of hop"


@pytest.mark.parametrize("near2_max", [None, 12, 0])
def test_two_level_look_ahead_is_the_plain_scan(alpha, monkeypatch,
                                                near2_max):
    """64 lanes over a random graph full of cycles, in one launch: the
    answers (paths and their order) are the per-query engine's, and the
    hops run, the hops pushed, their slots and the lanes closed under
    each rule are
    the plain search's, whether every lane takes the second level (the
    constant as it stands), some do (12 in-edges: about a third) or
    none (0: the launch the first level alone gives)."""
    from dgraph_tpu.engine import batch
    from dgraph_tpu.utils.metrics import METRICS

    if near2_max is None:
        near2_max = batch.NEAR2_MAX_EDGES
    else:
        monkeypatch.setattr(batch, "NEAR2_MAX_EDGES", near2_max)
    rng = np.random.default_rng(41)
    store = alpha.mvcc.read_view(alpha.oracle.read_only_ts())
    uid_of = {}
    for i in set(rng.integers(0, 400, 128).tolist()):
        uid_of[i] = _uid_of(alpha, f"p{i}")
    names = sorted(uid_of)
    pairs = [(uid_of[names[i]], uid_of[names[j]])
             for i, j in rng.integers(0, len(names), (64, 2)) if i != j]
    qs = ['{ path as shortest(from: %s, to: %s) { follows } '
          'p(func: uid(path)) { name } }' % p for p in pairs]

    def counters():
        return ([METRICS.get(f"kernel_{k}_total", family="shortest")
                 for k in ("hops_run", "hops_used", "hops_push",
                           "push_slots")]
                + [METRICS.get("kernel_lanes_closed_total",
                               family="shortest", by=by)
                   for by in CLOSED_BY]
                + [METRICS.get("kernel_near2_capped_total")])

    before = counters()
    got = alpha.query_batch(qs)
    run, used, push, slots, *closed = (
        b - a for a, b in zip(before, counters()))
    eng = Engine(store, device_threshold=10**9)
    assert json.dumps(got) == json.dumps([eng.query(q) for q in qs])
    want_run, want_push, want_slots, want_closed = _plain_launch(
        store, "follows", [(int(x, 16), int(y, 16)) for x, y in pairs],
        batch.SHORTEST_STAGE, near2_max)
    assert (run, used, push, slots) == (want_run, want_run, want_push,
                                        want_slots)
    assert dict(zip(CLOSED_BY, closed)) == want_closed
    capped = closed[-1]
    if near2_max == 0:
        assert not want_closed["seed2"] and not want_closed["ahead2"]
        assert capped == len(pairs) - want_closed["seed"]
    elif near2_max == 12:
        assert 0 < capped < len(pairs) - want_closed["seed"]
        assert want_closed["ahead"] and want_closed["ahead2"]
    else:
        assert not capped and not want_closed["ahead"]
        assert want_closed["ahead2"] > len(pairs) // 2


# -- the dense hub block under the batch routes (ops/bfs.py _choose_dense) ---

def _hub_alpha():
    """A skewed `follows`: 40 sources of high out-degree, 40 targets of
    high in-degree, the core between them filled to seven tenths, and a
    periphery of 520 nodes with three out-edges each."""
    rng = np.random.default_rng(38)
    a = Alpha(device_threshold=10**9)
    a.alter(SCHEMA)
    n = 600
    lines = [f'_:p{i} <name> "p{i}" .\n_:p{i} <score> "{i % 23}"^^<xs:int> .'
             for i in range(n)]
    lines += [f"_:p{s} <follows> _:p{t} ." for s in range(40)
              for t in range(40, 80) if rng.random() < 0.7]
    for i in range(80, n):
        lines += [f"_:p{i} <follows> _:p{j} ."
                  for j in rng.choice(n, 3, replace=False) if i != j]
    uids = a.mutate(set_nquads="\n".join(lines))["uids"]
    return a, uids


HUB_QUERIES = {
    "shortest": lambda uids: [
        '{ path as shortest(from: %s, to: %s) { follows } '
        'p(func: uid(path)) { name } }' % (uids[f"_:p{i}"], uids[f"_:p{j}"])
        for i, j in ((100, 41), (230, 77), (300, 555), (411, 60), (90, 5))],
    "recurse": lambda uids: [
        '{ q(func: eq(name, "p%d")) @recurse(depth: 3) { name follows } }'
        % i for i in (100, 3, 230, 41, 300, 17)],
    "tree": lambda uids: [
        '{ N as var(func: uid(%s)) @recurse(depth: 3, loop: false) '
        '{ follows } q(func: uid(N)) { count(uid) } }' % uids[f"_:p{i}"]
        for i in (100, 3, 230, 41, 300, 17)],
}


@pytest.mark.parametrize("block", [True, False])
@pytest.mark.parametrize("family", sorted(HUB_QUERIES))
def test_batch_over_a_hub_block_answers_and_counts_its_pulls(
        monkeypatch, family, block):
    """Every batch route over a relation with a hub block answers what
    the per-query engine answers, and counts each pulled hop in in-edges:
    all the relation's, and the block's share of them (0, not nothing,
    where the relation builds no block)."""
    from dgraph_tpu.engine.batch import _cache_host
    from dgraph_tpu.ops import bfs
    from dgraph_tpu.utils.metrics import METRICS

    if block:       # the rule at the size of 600 nodes (tests/test_bfs.py)
        monkeypatch.setattr(bfs, "DENSE_CELLS_PER_EDGE", 1e9)
        monkeypatch.setattr(bfs, "DENSE_MAX_BYTES", 256 * 128)
        monkeypatch.setattr(bfs, "DENSE_MIN_EDGES", 50)
    a, uids = _hub_alpha()
    qs = HUB_QUERIES[family](uids)

    def counters():
        return [METRICS.get(f"kernel_{k}_total", family=family)
                for k in ("edges_pulled", "edges_dense", "hops_run",
                          "hops_push")]

    before = counters()
    got = a.query_batch(qs)
    pulled, dense, run, push = (x - y for x, y in zip(counters(), before))
    store = a.mvcc.read_view(a.oracle.read_only_ts())
    eng = Engine(store, device_threshold=10**9)
    assert json.dumps(got) == json.dumps([eng.query(q) for q in qs])
    g = _cache_host(store, "follows", False)._ell_cache[("follows", False)]
    assert (g.dense is not None) == block
    assert g.nnz == store.rel("follows", False).nnz
    pulls = 3 if family == "recurse" else run - push
    assert pulls >= 2, "the batch must pull"
    assert (pulled, dense) == (pulls * g.nnz, pulls * g.dense_edges)
    assert (dense > 0) == block
    text = METRICS.render()
    rows, cols = g.dense[0].shape if block else (0, 0)
    for gauge, value in (("ell_dense_rows", rows), ("ell_dense_cols", cols),
                         ("ell_dense_edges", g.dense_edges)):
        assert (f'{gauge}{{pred="follows",reverse="False"}} '
                f'{float(value)}') in text.replace("dgraph_tpu_", "")
    assert ('kernel_edges_dense_total{family="%s"}' % family) in text
