"""The plain references, their path checkers and their controls."""

import copy

import numpy as np
import pytest

from generators import follower, ldbc_snb
from references import follower as fref
from references import ldbc_snb as lref
from traffic_kinds import ic_mix, shortest_pairs

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


def test_every_run_asks_for_the_same_places_dealt_another_way():
    p = {"nodes": 5000, "mean_out_degree": 35, "structure_seed": 4}
    places = []
    for seed in (1, 2):
        data = follower.generate(p, seed)
        mix = shortest_pairs.make(data, PAIRS, seed)
        place_of = np.argsort(data["node_of_structure"])
        places.append([[(int(place_of[m["a"] - 1]), int(place_of[m["b"] - 1]))
                        for m in r["meta"]] for r in mix.requests(3)])
    # another batch for the same place in the queue, the same set in all
    assert places[0][1] != places[1][1]
    assert sorted(sum(places[0], [])) == sorted(sum(places[1], []))
    # and another draw of requests shares nothing with it
    other = shortest_pairs.make(data, PAIRS, 2).requests(3, stream=1)
    assert other[0]["body"] != mix.requests(3)[0]["body"]
