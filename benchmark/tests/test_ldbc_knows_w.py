"""The configuration `ldbc-knows-7_5w-fb` and its cell
`knows-7_5w.cheapest-batch` (PR 46), at tiny size on the CPU: the
generator's graph bit for bit `ldbc_knows`', its weights, the loader
against the mutation path and its guard both ways, the plain reference
against a whole Dijkstra and what it refuses, the traffic kind, the
cell's entries, its metric files, and run.py end to end (`--rehearsal
--scale`). A file of its own: a PR adds to the benchmark and edits
nothing it already has."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import build_checkpoint
from conftest import BENCH, HERE, ROOT, holds_entries
from generators import ldbc_knows as base
from generators import ldbc_knows_w as gen
from harness.server import parse_prom
from readers import lane_relax_roofline, prom_ratio, prom_sum
from references import ldbc_knows_w as reference
from traffic_kinds import cheapest_pairs

CELL = "knows-7_5w.cheapest-batch"
PARAMS = {"persons": 4000, "knows": 36000, "degree_sigma": 1.0,
          "degree_cap": 200, "local_share": 0.8, "first_names": 96,
          "last_names": 12, "cities": 16, "name_zipf": 0.8,
          "structure_seed": 75, "interactions_local": 6,
          "interactions_far": 1.5}
SCALE = json.dumps({k: PARAMS[k] for k in (
    "persons", "knows", "degree_cap", "first_names", "last_names",
    "cities")})


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def data():
    return gen.generate(PARAMS, seed=2147483900)


# ---------------------------------------------------------------------------
# the generator

def test_the_graph_is_ldbc_knows_bit_for_bit(data):
    theirs = base.generate(PARAMS, seed=2147483900)
    for k in ("src", "dst", "row_start", "row_len", "node_of_structure",
              "n_nodes", "max_degree"):
        assert np.array_equal(data[k], theirs[k]), k
    assert gen.sizes(data) == base.sizes(theirs) == {
        "nodes": 4000, "knows": 72000, "max_degree": 122}
    assert not {"first_name", "last_name", "city"} & set(data)
    again = gen.generate(PARAMS, seed=2147483900)
    assert all(np.array_equal(again[k], data[k]) for k in data)


def test_the_weights_are_symmetric_small_and_fixed_under_the_seed(data):
    w = data["weight"]
    assert w.dtype == np.uint8 and w.min() >= 1 and w.max() <= 40
    fwd = data["src"].astype(np.int64) << 32 | data["dst"]
    back = data["dst"].astype(np.int64) << 32 | data["src"]
    assert (w[np.argsort(fwd)] == w[np.argsort(back)]).all()
    # pinned at structure_seed 75: most pairs a handful of replies
    hist = np.bincount(w, minlength=41)
    assert hist[36:].sum() > 0.85 * len(w) and 0 < hist[:30].sum() < 0.02 * len(w)
    assert hist.argmax() == 39 and hist[40] > 0.15 * len(w)
    # the seed renames the persons; a pair of the structure keeps its weight
    other = gen.generate(PARAMS, seed=5)
    keys = []
    for d in (data, other):
        place = gen.structure_places(d).astype(np.int64)
        key = place[d["src"]] << 32 | place[d["dst"]]
        order = np.argsort(key)
        keys.append((key[order], d["weight"][order]))
    assert (keys[0][0] == keys[1][0]).all()
    assert (keys[0][1] == keys[1][1]).all()
    assert (other["src"] != data["src"]).any()
    # a pair inside a community interacts more: it weighs less
    place = gen.structure_places(data)
    comm = gen.communities(PARAMS)
    inside = comm[place[data["src"]]] == comm[place[data["dst"]]]
    assert 0.70 < inside.mean() < 0.80
    assert w[inside].mean() < w[~inside].mean() - 0.5
    # the law itself: 40 - sqrt(count), rounded, at least 1
    for count, want in ((0, 40), (1, 39), (6, 38), (100, 30), (1521, 1),
                        (4000, 1)):
        assert max(round(40 - count ** 0.5), 1) == want


# ---------------------------------------------------------------------------
# the loader

def test_array_built_checkpoint_equals_the_mutation_path(tmp_path, data):
    from dgraph_tpu.server.api import Alpha
    build_checkpoint.save_arrays(data, str(tmp_path / "arrays"))
    build_checkpoint.build("ldbc_knows_w", str(tmp_path / "arrays"),
                           str(tmp_path / "p"))
    assert not [f for f in os.listdir(tmp_path / "p")
                if f.endswith("facets.json")]          # one typed column
    ours = Alpha.open(str(tmp_path / "p"))
    theirs = Alpha()
    theirs.alter(gen.SCHEMA)
    theirs.mutate(set_nquads="\n".join(
        f"<{s + 1}> <knows> <{d + 1}> (weight={w}) ."
        for s, d, w in zip(data["src"].tolist(), data["dst"].tolist(),
                           data["weight"].tolist())))
    for q in (
            cheapest_pairs.QUERY % ("0x1", "0x7d0"),
            cheapest_pairs.QUERY % ("0x5", "0x5"),
            "{ q(func: uid(0x2, 0x3)) { uid knows @facets(weight) { uid } "
            "~knows @facets(weight) { uid } count(knows) } }",
            "{ q(func: uid(0x9)) { knows @facets(ge(weight, 39)) "
            "{ uid } } }",
            "{ q(func: uid(0x9)) { knows @facets(orderasc: weight) "
            "@facets(weight) { uid } } }"):
        assert ours.query_raw(q) == theirs.query_raw(q), q
    # the served batch rides the lanes, and says what the host says
    qs = [cheapest_pairs.QUERY % (hex(i + 1), hex(i + 900))
          for i in range(8)]
    assert ours.query_batch(qs) == [theirs.query(q) for q in qs]


def test_the_loader_asks_the_planner_first():
    from loaders import ldbc_knows_w as loader
    plans, leftover = loader.lane_plans()
    assert len(plans) == 1 and not leftover
    assert plans[0][0].weight_key == "weight"


@pytest.mark.parametrize("stub", ["no_plan", "some_left_to_the_host"])
def test_the_loader_refuses_a_program_with_no_lane_plan(monkeypatch, stub):
    """Before anything is built: the build child exits with one sentence
    for a program whose planner returns no lane plan for the batch."""
    from dgraph_tpu.engine import batch
    from dgraph_tpu.store.schema import parse_schema
    from loaders import ldbc_knows_w as loader
    answer = {"no_plan": ([], [0, 1, 2, 3]),
              "some_left_to_the_host": ([("plan", [0, 1, 2])], [3])}[stub]
    monkeypatch.setattr(batch, "plan_batch_groups",
                        lambda store, blocks: answer)
    with pytest.raises(SystemExit, match="no deployment of this"):
        loader.build({}, parse_schema(gen.SCHEMA))


# ---------------------------------------------------------------------------
# the reference and its control

def some_metas(count: int, seed: int = 3) -> list:
    rng = np.random.default_rng(seed)
    return [{"a": int(a) + 1, "b": int(b) + 1}
            for a, b in rng.integers(0, 4000, (count, 2)) if a != b]


def test_the_reference_s_balls_agree_with_a_whole_dijkstra(data):
    ref = reference.make(data, {})
    for m in some_metas(24):
        cost, path = ref.search(m["a"] - 1, m["b"] - 1)
        assert path[0] == m["a"] - 1 and path[-1] == m["b"] - 1
        assert not ref.cheaper_exists(m["a"] - 1, m["b"] - 1, cost)
        assert ref.cheaper_exists(m["a"] - 1, m["b"] - 1, cost + 1)
        ball = ref.ball(m["a"] - 1, cost)
        assert ball[m["b"] - 1] == cost
        ok, why = ref.check(m, ref.answer(m))
        assert ok, why


def test_the_reference_refuses_what_is_not_a_cheapest_path(data):
    ref = reference.make(data, {})
    m = next(m for m in some_metas(64)
             if len(ref.search(m["a"] - 1, m["b"] - 1)[1]) >= 4)
    right = ref.answer(m)
    cost, path = ref.search(m["a"] - 1, m["b"] - 1)

    def shaped(nodes, weight):
        obj = {"uid": hex(nodes[-1] + 1)}
        for u in reversed(nodes[:-1]):
            obj = {"uid": hex(u + 1), "knows": obj}
        obj["_weight_"] = float(weight)
        return {"_path_": [obj], "p": [{"uid": hex(u + 1)}
                                       for u in sorted(set(nodes))]}

    assert ref.check(m, shaped(path, cost)) == (True, "")
    # a dearer path: a detour through a friend of the source, its sum right
    friends, ws = ref.row(path[0])
    for f, w in zip(friends.tolist(), ws.tolist()):
        c2, p2 = ref.search(f, path[-1])
        if f not in path and path[0] not in p2 and w + c2 > cost:
            ok, why = ref.check(m, shaped([path[0]] + p2, w + c2))
            assert not ok and "cheaper path exists" in why
            break
    else:
        raise AssertionError("no detour found")
    # a path with an edge that is not stored
    stranger = next(v for v in range(4000)
                    if v not in set(ref.row(path[0])[0].tolist())
                    and v != path[0])
    ok, why = ref.check(m, shaped([path[0], stranger] + path[1:], cost))
    assert not ok and "is not an edge" in why
    # a sum that is not the weights'
    ok, why = ref.check(m, shaped(path, cost - 1))
    assert not ok and "add up to" in why
    # the wrong ends, two paths, no path, a wrong listing
    assert not ref.check(m, shaped(path[:-1], cost))[0]
    assert not ref.check(m, {**right, "_path_": right["_path_"] * 2})[0]
    assert ref.check(m, {}) == (False, "no path returned, one exists")
    assert not ref.check(m, {**right, "p": right["p"][1:]})[0]


def test_the_control_disagrees_on_nearly_every_answer(data):
    """Adjacency rows cut at 8 edges: what a fixed-width device row that
    drops its overflow would find."""
    ref = reference.make(data, {})
    ctrl = reference.make_control(data, {})
    metas = some_metas(64)
    bad = sum(not ref.check(m, ctrl.answer(m))[0] for m in metas)
    assert bad >= 50


# ---------------------------------------------------------------------------
# the traffic

def test_the_traffic_is_the_same_pairs_under_every_seed(data):
    traffic = load(BENCH, "traffic", "cheapest-batch.json")
    assert traffic == {
        "kind": "cheapest_pairs", "endpoint": "/query/batch",
        "loop": "closed", "clients": 1, "batch": 64, "draw_requests": 1,
        "warm_requests": 2, "schedule_seed": 20261005}
    other = gen.generate(PARAMS, seed=5)
    mixes = [cheapest_pairs.make(d, traffic, s)
             for d, s in ((data, 2147483900), (other, 5))]
    streams = [m.requests(16, stream=100) for m in mixes]
    places = []
    for mix, reqs, d in zip(mixes, streams, (data, other)):
        place = gen.structure_places(d)
        for r in reqs:
            assert r["queries"] == 64 and r["path"] == "/query/batch"
            assert all(m["a"] != m["b"] for m in r["meta"])
            assert all(d["row_len"][m["a"] - 1] and d["row_len"][m["b"] - 1]
                       for m in r["meta"])
            qs = json.loads(r["body"])["queries"]
            assert qs[0] == cheapest_pairs.QUERY % (
                hex(r["meta"][0]["a"]), hex(r["meta"][0]["b"]))
            assert "knows @facets(weight)" in qs[0]
        places.append([sorted((int(place[m["a"] - 1]), int(place[m["b"] - 1]))
                              for m in r["meta"]) for r in reqs])
    # request by request the same pairs of the structure, other lanes
    assert places[0] == places[1]
    assert [m["a"] for m in streams[0][0]["meta"]] != \
        [m["a"] for m in streams[1][0]["meta"]]
    # pinned: the stream's first request, as places of the structure
    assert places[0][0][:3] == [(22, 1934), (27, 1274), (42, 389)], \
        places[0][0][:3]
    # requests never sent twice: the warm-up's and a chunk's
    warm = [sorted((m["a"], m["b"]) for m in r["meta"])
            for r in mixes[0].warm_requests()]
    sent = [tuple(p) for p in places[0]]
    assert len(warm) == 2 and len(set(sent)) == 16


def test_the_cell_s_entries_are_what_the_benchmark_holds():
    bench = load(ROOT, "BENCHMARK.json")
    ent = load(HERE, "data", CELL + ".entries.json")
    holds_entries(bench, ent, CELL)
    assert [m["name"] for m in bench["per_layer"][-5:]] == [
        e["name"] for e in ent["per_layer"]]
    # the unweighted route's readers are not this cell's
    held = {m["name"]: m for m in bench["per_layer"]}
    for name in ("lane_queries_per_launch.batch", "lane_hops_run.batch",
                 "lane_hop_roofline.batch", "push_slots_per_query.batch"):
        assert CELL not in held[name]["workloads"]
    cfg = load(ROOT, ent["configs"][0]["file"])
    assert cfg["source"] == ent["configs"][0]["source"]
    assert len(cfg["source"]) <= 200 and cfg["architecture"] is None
    assert cfg["reduced"] == ["entities"] == list(cfg["reduced_why"])
    sibling = load(BENCH, "configs", "ldbc-knows-7_5-fb.json")
    assert {k: v for k, v in cfg["generator_params"].items()
            if not k.startswith("interactions")} == \
        sibling["generator_params"]
    assert " ".join(gen.SCHEMA.split()) == cfg["schema"]
    assert set(cfg["guarantees"]) == {"answers", "isolation", "durability"}
    assert "relaxes distances on the device" in cfg["served"]
    assert {"source_from_memory", "interactions", "no_interaction",
            "pairs"} <= set(cfg["assumed"])
    assert ent["workloads"][0]["chips"] == 1
    assert len(ent["workloads"][0]["why"]) <= 200
    assert len(bench["workloads"]) == 4
    assert all(w["chips"] == 1 for w in bench["workloads"])


# ---------------------------------------------------------------------------
# the metric files

BEFORE = """\
dgraph_tpu_kernel_group_launches_total{family="weighted"} 2.0
dgraph_tpu_kernel_group_queries_total{family="weighted"} 128.0
dgraph_tpu_kernel_relax_rounds_total{family="weighted"} 18.0
dgraph_tpu_kernel_relaxed_slots_total{family="weighted"} 1263420000.0
"""
AFTER = """\
dgraph_tpu_kernel_group_launches_total{family="weighted"} 7.0
dgraph_tpu_kernel_group_launches_total{family="shortest"} 3.0
dgraph_tpu_kernel_group_queries_total{family="weighted"} 448.0
dgraph_tpu_kernel_relax_rounds_total{family="weighted"} 63.0
dgraph_tpu_kernel_relaxed_slots_total{family="weighted"} 4421970000.0
dgraph_tpu_weighted_host_fallbacks_total{reason="rounds"} 2.0
"""
# a program from before this PR: no weighted family, none of its counters
PARENT = """\
dgraph_tpu_kernel_group_launches_total{family="shortest"} 3.0
dgraph_tpu_kernel_group_queries_total{family="shortest"} 192.0
"""


def read_metric(name, ctx):
    spec = load(BENCH, "layer_metrics", name + ".json")
    reader = {"prom_ratio": prom_ratio, "prom_sum": prom_sum,
              "lane_relax_roofline": lane_relax_roofline}[spec["reader"]]
    return reader.read(ctx, **spec["args"])


@pytest.mark.parametrize("name,want", [
    ("weighted_queries_per_launch.batch", 64.0),
    ("relax_rounds_per_launch.batch", 9.0),
    ("relaxed_slots_per_query.batch", 3158550000 / 320),
    ("weighted_host_fallbacks.batch", 2.0),
])
def test_a_counter_metric_reads_the_window_s_delta(name, want):
    ctx = {"prom_before": parse_prom(BEFORE), "prom_after": parse_prom(AFTER)}
    assert read_metric(name, ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", ["weighted_queries_per_launch.batch",
                                  "relax_rounds_per_launch.batch",
                                  "relaxed_slots_per_query.batch",
                                  "lane_relax_roofline.batch"])
def test_a_program_without_the_family_reads_nothing(name):
    ctx = {"prom_before": parse_prom(""), "prom_after": parse_prom(PARENT),
           "trace": {"device_plane": True, "busy_s": 3.0,
                     "device_span_s": 4.0}, "completed_qps": 100.0}
    assert read_metric(name, ctx) is None
    ctx["prom_before"] = parse_prom(PARENT)
    assert read_metric(name, ctx) is None
    # and a window with no fallback reads 0, not nothing
    assert read_metric("weighted_host_fallbacks.batch", ctx) == 0


def test_the_relax_program_s_roofline_share():
    """45 rounds for 320 queries at 7.5 queries/s with the device busy
    0.95 of the trace: the round's byte model over the v5e's peak."""
    sizes = {"nodes": 633_432, "knows": 68_371_494}
    ctx = {"prom_before": parse_prom(BEFORE), "prom_after": parse_prom(AFTER),
           "trace": {"device_plane": True, "busy_s": 3.8,
                     "device_span_s": 4.0, "modules": []},
           "completed_qps": 7.5, "root": BENCH,
           "device": {"kind": "TPU v5 lite"}, "traffic": {"batch": 64},
           "sizes": sizes}
    per_round = lane_relax_roofline.round_bytes(633_432, 68_371_494, 64, 2, 1)
    assert per_round == 5 * 68_371_494 + 128 * 68_371_494 + 256 * 633_433
    secs = 0.95 / 7.5 * 320
    got = read_metric("lane_relax_roofline.batch", ctx)
    assert got == pytest.approx(100.0 * 45 * per_round / 819e9 / secs)
    assert 0 < got < 100
    ctx["trace"] = {"device_plane": False, "modules": []}
    assert read_metric("lane_relax_roofline.batch", ctx) is None
    ctx["device"] = {"kind": "TPU v9"}
    ctx["trace"] = {"device_plane": True, "busy_s": 1.0, "device_span_s": 2.0}
    with pytest.raises(KeyError, match="no peaks"):
        read_metric("lane_relax_roofline.batch", ctx)


# ---------------------------------------------------------------------------
# run.py end to end

def run(*extra, seed=2147483900, seconds=4, trace=0):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace), "--rehearsal", "--scale", SCALE, *extra], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:] + proc.stdout[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_cell_runs_end_to_end():
    out = run()
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"completed_qps", "setup_s"}
    assert out["attempted"] >= 64 and out["device"]["platform"] == "cpu"
    assert out["checks"]["fallbacks"] == {"value": 0, "limit": 0}


def test_a_traced_run_reports_the_per_layer_metrics():
    out = run(trace=1, seed=4294967000)
    bench = load(ROOT, "BENCHMARK.json")
    names = {m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", [CELL])}
    want = set(load(HERE, "data", CELL + ".rehearsal.json")["per_layer"])
    assert want <= set(out["metrics"]) <= names
    # the device's readers find no device plane on a CPU
    assert {"device_ms_per_query.batch", "lane_relax_roofline.batch"} <= (
        names - set(out["metrics"]))
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["weighted_queries_per_launch.batch"] == 64
    assert m["weighted_host_fallbacks.batch"] == 0
    assert 3 <= m["relax_rounds_per_launch.batch"] <= 12
    assert m["relaxed_slots_per_query.batch"] > 1000
    assert m["compiles_in_window.batch"] == 0
    assert m["phase_cover.batch"] > 80
    assert out["checks"]["mismatches"] == {"value": 0, "limit": 0}


def test_an_altered_answer_reads_correct_false():
    out = run("--break-answer")
    assert out["correct"] is False and out["failed"] >= 1
    assert out["checks"]["mismatches"]["value"] == 1


def test_the_control_is_seen_to_fail_on_a_run_s_sample():
    out = run("--control")
    assert out["correct"] is True
    assert out["checks"]["control_compared"] == out["checks"]["compared"]
    assert out["checks"]["control_mismatches"] > 100
