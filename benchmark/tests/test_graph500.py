"""The configuration `graph500-22` and its cell `g500-22.khop3-batch`
(PR 34), at tiny size on the CPU: the generator's sizes, the plain
reference and its cut-rows control, the traffic kind, the cell's entries,
its four per-layer metric files, and run.py end to end (`--rehearsal
--scale`). A file of its own: a PR adds to the benchmark and edits nothing
it already has."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, HERE, ROOT, holds_entries
from generators import graph500 as gen
from harness.server import parse_prom
from readers import lane_hop_roofline, prom_ratio
from references import graph500 as reference
from traffic_kinds import khop_seeds

CELL = "g500-22.khop3-batch"
PARAMS = {"scale": 12, "edgefactor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
          "structure_seed": 22}
SCALE = '{"scale": 12}'


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def data():
    return gen.generate(PARAMS, seed=2147483900)


def test_the_generator_s_sizes_at_scale_12(data):
    """Pinned: vertices with an edge, distinct directed edges, the
    largest in-degree (no cap: 939 of 53,206 edges on one vertex)."""
    assert gen.sizes(data) == {"nodes": 3354, "link": 53206,
                               "max_in_degree": 939}
    src, dst = data["src"], data["dst"]
    assert not (src == dst).any()
    assert len(np.unique(src.astype(np.int64) << 32 | dst)) == len(src)
    touched = np.zeros(3354, bool)
    touched[src] = touched[dst] = True
    assert touched.all()


def test_the_seed_names_the_nodes_and_nothing_else(data):
    other = gen.generate(PARAMS, seed=5)
    assert gen.sizes(other) == gen.sizes(data)
    for key in ("src", "dst"):
        assert (np.sort(np.bincount(other[key], minlength=3354))
                == np.sort(np.bincount(data[key], minlength=3354))).all()
    assert (other["src"] != data["src"]).any()
    # the same structural edge list under other names
    back = np.argsort(other["node_of_structure"])
    fwd = data["node_of_structure"]
    assert (fwd[back[other["src"]]] == data["src"]).all()
    assert (fwd[back[other["dst"]]] == data["dst"]).all()


def brute(data, seed_node: int, k: int) -> int:
    out = {}
    for s, d in zip(data["src"].tolist(), data["dst"].tolist()):
        out.setdefault(s, set()).add(d)
    seen, frontier = {seed_node}, {seed_node}
    for _ in range(k):
        frontier = {d for s in frontier for d in out.get(s, ())} - seen
        seen |= frontier
    return len(seen)


@pytest.mark.parametrize("k", [1, 2, 3, 6])
def test_the_reference_is_a_breadth_first_search(data, k):
    ref = reference.make(data, {})
    nodes = np.nonzero(data["row_len"] > 0)[0][::97]
    assert [ref.within(int(i), k) for i in nodes] == \
        [brute(data, int(i), k) for i in nodes]
    # both ways of listing a hop's edges give the same targets
    frontier = np.nonzero(data["row_len"] > 0)[0][::3]
    dense = np.unique(ref.dst[ref._mark_edges(frontier)])
    reference.DENSE_SHARE, kept = 0, reference.DENSE_SHARE
    try:
        assert (np.unique(ref._targets(frontier)) == dense).all()
    finally:
        reference.DENSE_SHARE = kept


def test_the_control_disagrees_on_nearly_every_answer(data):
    """Adjacency rows cut at 8 edges: what a fixed-width device row that
    drops its overflow would count."""
    ref = reference.make(data, {})
    ctrl = reference.make_control(data, {})
    rng = np.random.default_rng(3)
    nodes = rng.choice(np.nonzero(data["row_len"] > 0)[0], 128, False)
    metas = [{"seed": int(i) + 1, "depth": 3} for i in nodes]
    bad = sum(not ref.check(m, ctrl.answer(m))[0] for m in metas)
    assert bad >= 120
    assert all(ref.check(m, ref.answer(m))[0] for m in metas[:16])
    ok, why = ref.check(metas[0], {"q": [{"count": 1}]})
    assert not ok and "is right" in why


def test_the_traffic_is_the_same_places_under_every_seed(data):
    traffic = load(BENCH, "traffic", "khop-batch.json")
    assert {k: traffic[k] for k in (
        "endpoint", "loop", "clients", "batch", "depth", "recurse_loop",
        "predicate", "draw_requests", "warm_requests")} == {
        "endpoint": "/query/batch", "loop": "closed", "clients": 1,
        "batch": 64, "depth": 3, "recurse_loop": False,
        "predicate": "link", "draw_requests": 1, "warm_requests": 4}
    mixes = [khop_seeds.make(d, traffic, s) for d, s in
             ((data, 2147483900), (gen.generate(PARAMS, seed=5), 5))]
    streams = [m.requests(16, stream=100) for m in mixes]
    places = []
    for mix, reqs, d in zip(mixes, streams, (data, None)):
        back = np.argsort(mix.node_of)
        for r in reqs:
            assert r["queries"] == 64 and r["path"] == "/query/batch"
            seeds = [m["seed"] for m in r["meta"]]
            assert len(set(seeds)) == 64
            qs = json.loads(r["body"])["queries"]
            assert qs[0] == (
                "{ N as var(func: uid(%s)) @recurse(depth: 3, loop: false)"
                " { link } q(func: uid(N)) { count(uid) } }"
                % hex(seeds[0]))
        places.append([sorted(back[[m["seed"] - 1 for m in r["meta"]]])
                       for r in reqs])
    # request by request the same places of the structure, other lanes
    assert places[0] == places[1]
    assert [m["seed"] for m in streams[0][0]["meta"]] != \
        [m["seed"] for m in streams[1][0]["meta"]]
    assert (data["row_len"][[m["seed"] - 1
                             for m in streams[0][3]["meta"]]] > 0).all()
    # requests never sent twice: the warm-up's and two chunks'
    sent = [tuple(sorted(p)) for p in places[0]]
    warm = mixes[0].warm_requests()
    assert len(warm) == 4 and len(set(sent)) == 16


def test_the_cell_s_entries_are_what_the_benchmark_holds():
    bench = load(ROOT, "BENCHMARK.json")
    ent = load(HERE, "data", CELL + ".entries.json")
    holds_entries(bench, ent, CELL)
    cfg = load(ROOT, ent["configs"][0]["file"])
    assert cfg["source"] == ent["configs"][0]["source"]
    assert len(cfg["source"]) <= 200 and cfg["reduced"] == []
    assert cfg["generator_params"]["scale"] == 22
    assert cfg["generator_params"]["edgefactor"] == 16
    assert set(cfg["guarantees"]) == {"answers", "isolation", "durability"}
    assert ent["workloads"][0]["chips"] == 1


# ---------------------------------------------------------------------------
# the four metric files

BEFORE = """\
dgraph_tpu_kernel_group_launches_total{family="tree"} 4.0
dgraph_tpu_kernel_group_queries_total{family="tree"} 256.0
dgraph_tpu_kernel_edges_traversed_total{family="tree"} 1000000.0
dgraph_tpu_tree_var_reads_total{by="count"} 256.0
"""
AFTER = """\
dgraph_tpu_kernel_group_launches_total{family="tree"} 36.0
dgraph_tpu_kernel_group_launches_total{family="shortest"} 9.0
dgraph_tpu_kernel_group_queries_total{family="tree"} 2304.0
dgraph_tpu_kernel_group_queries_total{family="shortest"} 90.0
dgraph_tpu_kernel_edges_traversed_total{family="tree"} 52201000000.0
dgraph_tpu_tree_var_reads_total{by="count"} 2304.0
dgraph_tpu_tree_var_reads_total{by="column"} 0.0
"""
# a program from before PR 34: the tree family's group series, no more
PARENT = """\
dgraph_tpu_kernel_group_launches_total{family="tree"} 36.0
dgraph_tpu_kernel_group_queries_total{family="tree"} 2304.0
"""


def read_metric(name, ctx):
    spec = load(BENCH, "layer_metrics", name + ".json")
    reader = {"prom_ratio": prom_ratio,
              "lane_hop_roofline": lane_hop_roofline}[spec["reader"]]
    return reader.read(ctx, **spec["args"])


@pytest.mark.parametrize("name,want", [
    ("tree_queries_per_launch.batch", 64.0),
    ("tree_device_count_share.batch", 100.0),
    ("traversed_edges_per_query.batch", 52200000000.0 / 2048),
])
def test_a_counter_metric_reads_the_window_s_delta(name, want):
    ctx = {"prom_before": parse_prom(BEFORE), "prom_after": parse_prom(AFTER)}
    assert read_metric(name, ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", ["tree_device_count_share.batch",
                                  "traversed_edges_per_query.batch"])
def test_a_program_without_the_counter_reads_nothing(name):
    ctx = {"prom_before": parse_prom(PARENT), "prom_after": parse_prom(PARENT)}
    assert read_metric(name, ctx) is None
    ctx["prom_before"] = parse_prom("")
    assert read_metric(name, ctx) is None


def test_the_tree_program_s_roofline_share():
    """Three turns of the scan a run of `jit_tree`, two runs, 0.9 s: the
    pull's byte model over the v5e's peak, and nothing without a device
    plane or without the program."""
    sizes = {"nodes": 2_396_000, "link": 64_155_000}
    ctx = {"trace": {"device_plane": True,
                     "modules": [["jit_tree", 0.9, 2, 6.0],
                                 ["jit_step", 0.5, 1, 4.0]]},
           "root": BENCH, "device": {"kind": "TPU v5 lite"},
           "traffic": {"batch": 64}, "sizes": sizes}
    need = 6 * lane_hop_roofline.hop_bytes(2_396_000, 64_155_000, 64)
    assert read_metric("lane_tree_roofline.batch", ctx) == pytest.approx(
        100.0 * need / 819e9 / 0.9)
    assert 0 < read_metric("lane_tree_roofline.batch", ctx) < 1.0
    ctx["trace"]["modules"] = [["jit_step", 0.5, 1, 4.0]]
    assert read_metric("lane_tree_roofline.batch", ctx) is None
    ctx["trace"] = {"device_plane": False, "modules": []}
    assert read_metric("lane_tree_roofline.batch", ctx) is None


def test_the_loader_asks_the_planner_first():
    from dgraph_tpu.store.schema import parse_schema
    from loaders import graph500 as loader
    assert loader.counts_on_device(parse_schema(gen.SCHEMA))


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
    assert {"device_ms_per_query.batch", "lane_tree_roofline.batch"} <= (
        names - set(out["metrics"]))
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["tree_queries_per_launch.batch"] == 64
    assert m["tree_device_count_share.batch"] == 100
    assert m["compiles_in_window.batch"] == 0
    assert m["traversed_edges_per_query.batch"] > 1000
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
