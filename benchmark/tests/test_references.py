"""The plain references, their path checkers and their controls."""

import copy
import json
import os

import numpy as np
import pytest

from conftest import BENCH
from generators import follower, ldbc_snb
from references import follower as fref
from references import ldbc_snb as lref
from traffic_kinds import ic_mix, recurse_roots, shortest_pairs

FREQ = {"IC1": 26, "IC2": 37, "IC3": 69, "IC4": 36, "IC5": 57, "IC6": 129,
        "IC7": 87, "IC8": 45, "IC9": 157, "IC10": 30, "IC11": 16,
        "IC12": 44, "IC13": 19, "IC14": 49}
FOLLOWER = {"nodes": 20000, "mean_out_degree": 35, "structure_seed": 4}
SNB = {"sf": 0.02, "structure_seed": 9}
IC = {"frequency": FREQ, "schedule_seed": 3}
PAIRS = {"batch": 64, "warm_requests": 1, "schedule_seed": 3}


@pytest.fixture(scope="module")
def graph():
    data = follower.generate(FOLLOWER, 5)
    return data, fref.make(data, {})


def _some_path(ref, min_hops=2):
    rng = np.random.default_rng(0)
    while True:
        a, b = (int(x) for x in rng.integers(1, ref.n + 1, 2))
        ans = ref.answer({"a": a, "b": b})
        if len(ans.get("p", [])) > min_hops:
            return {"a": a, "b": b}, ans


def test_the_reference_accepts_its_own_answer(graph):
    _data, ref = graph
    meta, ans = _some_path(ref)
    assert ref.check(meta, ans) == (True, "")


def test_path_checker_refuses_a_non_edge(graph):
    _data, ref = graph
    meta, ans = _some_path(ref)
    hops = fref.walk(ans["_path_"][0], "follows")
    # put a node in the middle that the first hop does not reach
    stranger = next(u for u in range(1, ref.n + 1)
                    if not np.any(ref.row(hops[0] - 1) == u - 1)
                    and u not in hops)
    bad = copy.deepcopy(ans)
    bad["_path_"][0]["follows"]["uid"] = hex(stranger)
    ok, why = ref.check(meta, bad)
    assert not ok and "not an edge" in why


def test_path_checker_refuses_a_wrong_endpoint(graph):
    _data, ref = graph
    meta, ans = _some_path(ref)
    ok, why = ref.check({"a": meta["a"], "b": meta["b"] % ref.n + 1}, ans)
    assert not ok and "source and target" in why


def test_path_checker_refuses_a_longer_path(graph):
    _data, ref = graph
    # a pair one hop apart, answered by a real path of two hops
    for a in range(1, ref.n + 1):
        for mid in ref.row(a - 1)[:20]:
            both = np.intersect1d(ref.row(a - 1), ref.row(int(mid)))
            both = both[both != a - 1]
            if len(both):
                b = int(both[0]) + 1
                long = {"_path_": [{"uid": hex(a), "follows": {
                    "uid": hex(int(mid) + 1), "follows": {"uid": hex(b)}}}],
                    "p": [{"uid": hex(u)}
                          for u in sorted({a, int(mid) + 1, b})]}
                ok, why = ref.check({"a": a, "b": b}, long)
                assert not ok and "fewer are enough" in why
                return
    pytest.fail("no triangle in the graph")


def test_no_path_is_right_only_where_none_exists(graph):
    _data, ref = graph
    meta, _ans = _some_path(ref)
    ok, _why = ref.check(meta, {})
    assert not ok


def test_follower_control_is_not_correct(graph):
    """The control (rows cut at 8 edges) in the program's place, on a
    run's worth of pairs: some of its paths must be refused."""
    data, ref = graph
    ctrl = fref.make_control(data, {})
    mix = shortest_pairs.make(data, PAIRS, 5)
    metas = [m for r in mix.requests(2) for m in r["meta"]]
    wrong = sum(not ref.check(m, ctrl.answer(m))[0] for m in metas)
    assert wrong > len(metas) // 4


@pytest.fixture(scope="module")
def snb():
    data = ldbc_snb.generate(SNB, 5)
    return data, lref.make(data, {})


def test_snb_control_is_not_correct(snb):
    """float32 order keys and edge lists cut at 64, in the program's
    place on a sample of the mix: some answers must be refused."""
    data, ref = snb
    ctrl = lref.make_control(data, {})
    mix = ic_mix.make(data, IC, 5)
    tried = wrong = 0
    for r in mix.requests(128):
        ans = ctrl.answer(r["meta"])
        if ans is not None:
            tried += 1
            wrong += not ref.check(r["meta"], ans)[0]
    assert tried > 64 and wrong > 0


def test_snb_reference_refuses_a_reordered_answer(snb):
    data, ref = snb
    mix = ic_mix.make(data, IC, 5)
    for r in mix.requests(200):
        if r["meta"]["template"] != "IC2":
            continue
        ans = ref.answer(r["meta"])
        if ans["q"] and len(ans["q"][0]["knows"][0]["~has_creator"]) > 1:
            ans["q"][0]["knows"][0]["~has_creator"].reverse()
            assert not ref.check(r["meta"], ans)[0]
            return
    pytest.fail("no IC2 with two messages")


def test_every_seed_sends_the_same_requests_in_another_order(snb):
    data, _ref = snb
    bodies = [[r["body"] for r in ic_mix.make(data, IC, s).requests(300)]
              for s in (1, 2)]
    assert bodies[0] != bodies[1]
    assert sorted(bodies[0]) == sorted(bodies[1])
    names = {r["meta"]["template"]
             for r in ic_mix.make(data, IC, 1).requests(300)}
    assert len(names) == 14


def test_the_warm_up_is_a_draw_of_its_own(snb):
    data, _ref = snb
    mix = ic_mix.make(data, IC, 1)
    window = {r["body"].strip() for r in mix.requests(300)}
    warm = mix.warm_requests(300)
    assert len(warm) >= 300
    # another draw: at this size (197 persons) a few requests recur by
    # chance, as two users' would
    again = window & {r["body"].strip() for r in warm}
    assert len(again) < len(window) // 10


def test_structure_seed_fixes_the_shapes_and_the_seed_the_labels():
    p = {"nodes": 5000, "mean_out_degree": 35, "structure_seed": 4}
    a, b = follower.generate(p, 1), follower.generate(p, 2)
    assert len(a["src"]) == len(b["src"])
    assert not np.array_equal(a["src"], b["src"])
    for x in (a, b):       # the same in- and out-degree histograms
        assert np.array_equal(
            np.sort(np.bincount(x["dst"], minlength=5000)),
            np.sort(np.bincount(a["dst"], minlength=5000)))
    assert np.array_equal(follower.generate(p, 1)["dst"], a["dst"])
    q = {"sf": 0.02, "structure_seed": 9}
    c, d = ldbc_snb.generate(q, 1), ldbc_snb.generate(q, 2)
    for pred in ldbc_snb.EDGE_PREDS:
        assert len(c[pred]) == len(d[pred])
    assert not np.array_equal(c["knows"], d["knows"])
    assert len(np.unique(c["knows"], axis=0)) == len(c["knows"])


@pytest.mark.parametrize("draw", [None, 1, 2])
def test_every_run_asks_for_the_same_places_dealt_another_way(draw):
    """No `draw_requests`: the same set of places over the call, grouped
    another way a seed. A draw of d requests: the same set d requests at a
    time, so with 1 (the mix as it stands in `traffic/`) request i holds
    the same places in every run, on other lanes."""
    p = {"nodes": 5000, "mean_out_degree": 35, "structure_seed": 4}
    params = PAIRS if draw is None else {**PAIRS, "draw_requests": draw}
    places = []
    for seed in (1, 2):
        data = follower.generate(p, seed)
        mix = shortest_pairs.make(data, params, seed)
        place_of = np.argsort(data["node_of_structure"])
        places.append([[(int(place_of[m["a"] - 1]), int(place_of[m["b"] - 1]))
                        for m in r["meta"]] for r in mix.requests(4)])
    # another lane order for the same place in the queue in every case
    assert places[0][1] != places[1][1]
    sets = [[sorted(sum(reqs[i:i + (draw or 4)], []))
             for i in range(0, 4, draw or 4)] for reqs in places]
    assert sets[0] == sets[1]
    # and no smaller group of requests holds the same places in both
    if draw != 1:
        assert sorted(places[0][0]) != sorted(places[1][0])
    # another draw of requests shares nothing with it
    other = shortest_pairs.make(data, params, 2).requests(4, stream=1)
    assert not set(sum(places[1], [])) & {
        (int(place_of[m["a"] - 1]), int(place_of[m["b"] - 1]))
        for r in other for m in r["meta"]}
    if draw == 1:
        with open(os.path.join(BENCH, "traffic", "shortest-batch.json")) as f:
            assert json.load(f) == {
                "kind": "shortest_pairs", "endpoint": "/query/batch",
                "loop": "closed", "clients": 1, "batch": 64,
                "draw_requests": 1, "warm_requests": 4,
                "schedule_seed": 20260927}


def test_without_the_key_the_deal_is_the_one_it_always_was():
    """The tests' use of the kind (no `draw_requests`) sends what it sent
    before the key existed: one permutation of the call's pairs."""
    p = {"nodes": 5000, "mean_out_degree": 35, "structure_seed": 4}
    data = follower.generate(p, 7)
    mix = shortest_pairs.make(data, PAIRS, 7)
    for count, stream in ((3, 0), (16, 1100)):
        rng = np.random.default_rng([PAIRS["schedule_seed"], stream])
        a, b = mix._pairs(rng, count * 64)
        deal = np.random.default_rng([7, 3, stream]).permutation(count * 64)
        want = [(int(x), int(y)) for x, y in zip(a[deal], b[deal])]
        got = [(m["a"], m["b"]) for r in mix.requests(count, stream)
               for m in r["meta"]]
        assert got == want
    assert mix.requests(0) == []


# -- the tree of first visits (`references/recurse_tree.py`) ----------------

ROOTS = {"batch": 16, "depth": 3, "draw_requests": 16, "recurse_loop": False,
         "predicate": "knows", "persons": "uniform-distinct",
         "endpoint": "/query/batch", "warm_requests": 1, "schedule_seed": 3}


@pytest.fixture(scope="module")
def a_tree(snb):
    """A request of the mix whose tree has all three levels, with the
    plain search's own tree for it."""
    data, ref = snb
    for r in recurse_roots.make(data, ROOTS, 5).requests(4):
        for meta in r["meta"]:
            ans = ref.answer(meta)
            two = [k for k in ans["q"][0].get("knows", []) if "knows" in k]
            if any("knows" in g for k in two for g in k["knows"]):
                return ref, meta, ans
    pytest.fail("no three-level tree in the mix")


def test_the_tree_check_accepts_the_plain_search_s_own_tree(a_tree):
    ref, meta, ans = a_tree
    assert ref.check(meta, ans) == (True, "")


def _second_level(ans):
    """A node of the first level that has children, and one of them that
    has children of its own."""
    kid = next(k for k in ans["q"][0]["knows"]
               if any("knows" in g for g in k.get("knows", [])))
    return kid, next(g for g in kid["knows"] if "knows" in g)


def _drop_an_edge(ans, _ref, _meta):
    kid, _g = _second_level(ans)
    kid["knows"].pop()


def _repeat_an_edge(ans, _ref, _meta):
    kid, _g = _second_level(ans)
    kid["knows"].insert(0, copy.deepcopy(kid["knows"][0]))


def _hang_one_level_too_deep(ans, _ref, _meta):
    # a real edge of the graph, out of a node of the last level
    _kid, grand = _second_level(ans)
    leaf = grand["knows"][0]
    nbr = int(_ref.tree_adj("knows").row(int(leaf["uid"], 16))[0])
    leaf["knows"] = [{"uid": hex(nbr)}]


def _hang_a_stranger(ans, ref, _meta):
    kid, _g = _second_level(ans)
    row = set(ref.tree_adj("knows").row(int(kid["uid"], 16)).tolist())
    stranger = next(int(u) for u in ref.d["person_uids"] if int(u) not in row)
    kid["knows"][-1] = {"uid": hex(stranger)}


def _swap_two_children(ans, _ref, _meta):
    kids = ans["q"][0]["knows"]
    kids[0], kids[1] = kids[1], kids[0]


def _another_root(ans, ref, meta):
    ans["q"][0]["uid"] = hex(meta["params"]["p"] % len(ref.d["person_uids"])
                             + 1)


FAULTS = {"a missing edge": (_drop_an_edge, "of its"),
          "an edge repeated": (_repeat_an_edge, "repeated"),
          "an edge one level too deep": (_hang_one_level_too_deep, "depth 4"),
          "a node that is not a neighbour": (_hang_a_stranger, "no edge of"),
          "children out of order": (_swap_two_children, "out of order"),
          "another root": (_another_root, "the root is")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_tree_check_refuses(a_tree, fault):
    ref, meta, ans = a_tree
    bad = copy.deepcopy(ans)
    spoil, says = FAULTS[fault]
    spoil(bad, ref, meta)
    ok, why = ref.check(meta, bad)
    assert not ok and says in why, why


@pytest.mark.parametrize("got", [{}, {"q": []}, {"q": [{"uid": "0x1"}] * 2},
                                 {"q": [{"knows": []}]}, None,
                                 {"q": [{"uid": "0x1", "likes": []}]}])
def test_the_tree_check_refuses_what_is_no_tree(a_tree, got):
    ref, meta, _ans = a_tree
    assert not ref.check(meta, got)[0]


def test_recurse_control_is_not_correct(snb):
    """`knows` rows cut at 8 in the program's place, on a run's worth of
    roots: its trees must be refused."""
    data, ref = snb
    ctrl = lref.make_control(data, {})
    metas = [m for r in recurse_roots.make(data, ROOTS, 5).requests(8)
             for m in r["meta"]]
    wrong = sum(not ref.check(m, ctrl.answer(m))[0] for m in metas)
    assert len(metas) == 128 and wrong > len(metas) // 2


def test_a_request_s_persons_all_differ_and_every_seed_asks_the_same():
    places = []
    for seed in (1, 2):
        data = ldbc_snb.generate(SNB, seed)
        mix = recurse_roots.make(data, ROOTS, seed)
        place_of = {int(u): i for i, u in
                    enumerate(data["person_of_structure"])}
        reqs = mix.requests(16)
        for r in reqs:
            roots = [m["params"]["p"] for m in r["meta"]]
            assert len(set(roots)) == len(roots) == 16
            assert r["path"] == "/query/batch" and r["queries"] == 16
        places.append([[place_of[m["params"]["p"]] for m in r["meta"]]
                       for r in reqs])
    assert places[0] != places[1]
    assert sorted(sum(places[0], [])) == sorted(sum(places[1], []))
    q = json.loads(reqs[0]["body"])["queries"][0]
    assert q == ("{ q(func: uid(%s)) @recurse(depth: 3, loop: false) "
                 "{ uid knows } }" % hex(reqs[0]["meta"][0]["params"]["p"]))
    # the warm-up is a draw of its own
    assert mix.warm_requests()[0]["body"] != reqs[0]["body"]


def test_a_draw_of_one_request_sends_every_run_the_same_requests():
    """`draw_requests: 1`, the mix as it stands in `traffic/`: request i
    asks about the same places in every run, on other lanes."""
    sent = []
    for seed in (1, 2):
        data = ldbc_snb.generate(SNB, seed)
        mix = recurse_roots.make(data, {**ROOTS, "draw_requests": 1}, seed)
        place_of = {int(u): i for i, u in
                    enumerate(data["person_of_structure"])}
        sent.append([[place_of[m["params"]["p"]] for m in r["meta"]]
                     for r in mix.requests(16)])
    assert sent[0] != sent[1]
    assert [sorted(r) for r in sent[0]] == [sorted(r) for r in sent[1]]
    with open(os.path.join(BENCH, "traffic", "recurse-batch.json")) as f:
        assert json.load(f) == {
            **ROOTS, "draw_requests": 1, "warm_requests": 4,
            "schedule_seed": 20260928, "kind": "recurse_roots",
            "loop": "closed", "clients": 1}
