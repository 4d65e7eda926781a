"""The configuration `ldbc-knows-7_5-fb` and its cell `knows-7_5.ic1-batch`
(PR 37; built in PR 36), at tiny size on the CPU: the generator bit for
bit, its counts and degrees, the loader against the mutation path and its
guard both ways, the plain reference against a Python-loop IC1 and its
cut-rows control, the traffic kind, the cell's entries, its three
per-layer metric files, and run.py end to end (`--rehearsal --scale`). A file of its own: a PR adds to the benchmark and
edits nothing it already has."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import build_checkpoint
from conftest import BENCH, HERE, ROOT, holds_entries
from generators import ldbc_knows as gen
from harness.server import parse_prom
from readers import lane_hop_roofline, prom_ratio
from references import ldbc_knows as reference
from traffic_kinds import ic1_persons, ic_mix

CELL = "knows-7_5.ic1-batch"
PARAMS = {"persons": 4000, "knows": 36000, "degree_sigma": 1.0,
          "degree_cap": 200, "local_share": 0.8, "first_names": 96,
          "last_names": 12, "cities": 16, "name_zipf": 0.8,
          "structure_seed": 75}
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

def test_the_generator_s_counts_and_degrees(data):
    """Pinned at structure_seed 75: exactly `knows` distinct pairs, each
    stored both ways, no self-loop, rows sorted; the degrees' percentiles
    and the share of edges inside a community."""
    assert gen.sizes(data) == {"nodes": 4000, "knows": 72000,
                               "max_degree": 122}
    src, dst = data["src"].astype(np.int64), data["dst"].astype(np.int64)
    assert not (src == dst).any()
    fwd, back = src << 32 | dst, dst << 32 | src
    assert len(np.unique(fwd)) == 72000
    assert (np.sort(fwd) == np.sort(back)).all()       # its own reverse
    deg = np.asarray(data["row_len"])
    assert deg.sum() == 72000 and deg.max() == 122
    assert [int(np.percentile(deg, q)) for q in (5, 50, 95, 99)] == \
        [3, 14, 49, 73]
    s, d = gen._structure(PARAMS, 75)
    assert (np.diff(s.astype(np.int64) << 32 | d) > 0).all()
    comm = np.random.default_rng(75).integers(0, 63, 4000)
    assert 0.70 < (comm[s] == comm[d]).mean() < 0.80


def test_the_generator_is_bit_for_bit(data):
    again = gen.generate(PARAMS, seed=2147483900)
    assert set(again) == set(data)
    for k in data:
        assert np.array_equal(again[k], data[k]), k


def test_the_seed_names_the_persons_and_draws_the_properties(data):
    other = gen.generate(PARAMS, seed=5)
    assert gen.sizes(other) == gen.sizes(data)
    assert (np.sort(other["row_len"]) == np.sort(data["row_len"])).all()
    assert (other["src"] != data["src"]).any()
    # the same structural edge list under other names
    back = np.argsort(other["node_of_structure"])
    fwd = data["node_of_structure"]
    assert (fwd[back[other["src"]]] == data["src"]).all()
    assert (fwd[back[other["dst"]]] == data["dst"]).all()
    for prop in ("first_name", "last_name", "city", "birthday_year"):
        assert (other[prop] != data[prop]).any()
    # Zipf(0.8) over the seed's own order: the commonest first name is
    # another string, borne by as many, and so down the whole list; a
    # person of the structure bears a name as common under every seed
    top = [np.bincount(d["first_name"], minlength=96) for d in (data, other)]
    assert top[0].argmax() != top[1].argmax()
    assert 400 < top[0].max() < 600
    assert (np.sort(top[0]) == np.sort(top[1])).all()
    assert (top[1][other["first_name"][other["node_of_structure"]]]
            == top[0][data["first_name"][fwd]]).all()


def test_the_dictionaries_are_distinct_strings():
    words = gen.dictionaries({"first_names": 4096, "last_names": 16384,
                              "cities": 1024})
    assert [len(set(words[k])) for k in ("first_name", "last_name", "city")
            ] == [4096, 16384, 1024]
    assert words["first_name"][:3] == ["Ba", "Da", "Fa"]
    assert words["last_name"][0] == "Bason" and words["city"][0] == "Baville"
    # the order of the strings is not the order of their numbers
    assert sorted(words["last_name"]) != words["last_name"]


def test_a_graph_too_dense_for_its_communities_is_refused():
    with pytest.raises(SystemExit, match="too dense"):
        gen.generate({**PARAMS, "persons": 40, "knows": 790}, seed=1)


# ---------------------------------------------------------------------------
# the loader

def test_array_built_checkpoint_equals_the_mutation_path(tmp_path, data):
    from dgraph_tpu.server.api import Alpha
    build_checkpoint.save_arrays(data, str(tmp_path / "arrays"))
    build_checkpoint.build("ldbc_knows", str(tmp_path / "arrays"),
                           str(tmp_path / "p"))
    ours = Alpha.open(str(tmp_path / "p"))
    theirs = Alpha()
    theirs.alter(gen.SCHEMA)
    words = gen.dictionaries(data)
    lines = []
    for i in range(4000):
        lines += [f'<{i + 1}> <{p}> "{words[p][data[p][i]]}" .'
                  for p in ("first_name", "last_name", "city")]
        lines.append(f'<{i + 1}> <birthday_year> '
                     f'"{data["birthday_year"][i]}"^^<xs:int> .')
    lines += [f"<{s + 1}> <knows> <{d + 1}> ."
              for s, d in zip(data["src"].tolist(), data["dst"].tolist())]
    theirs.mutate(set_nquads="\n".join(lines))
    name = words["first_name"][data["first_name"][0]]
    for q in (
            ic_mix.TEMPLATES["IC1"] % {"p": "0x1", "fn": name},
            '{ q(func: eq(first_name, "%s"), orderasc: last_name, '
            'first: 30) { uid last_name city birthday_year } }' % name,
            "{ q(func: uid(0x2, 0x3)) { uid knows { uid } "
            "~knows { uid } count(knows) } }",
            "{ q(func: ge(birthday_year, 2000), first: 25) "
            "{ uid birthday_year } }",
            '{ q(func: anyofterms(first_name, "%s")) { count(uid) } }'
            % name):
        assert ours.query_raw(q) == theirs.query_raw(q), q


def test_the_loader_asks_the_planner_first():
    """This tree's planner records that IC1's block `q` probes."""
    from dgraph_tpu.store.schema import parse_schema
    from loaders import ldbc_knows as loader
    assert loader.QUERY == ic_mix.TEMPLATES["IC1"] % {"p": "0x1", "fn": "Ba"}
    assert loader.recorded_reads(parse_schema(gen.SCHEMA)) == ["probe"]


STUB_PLANS = {
    # what a planner hands back -> what the loader reads off it
    "no_tree_plan": (None, None),
    # PR 35's program: a TreePlan with no record of the reads
    "a_plan_without_the_record": (
        ("sig", types.SimpleNamespace(stages=[])), []),
    "a_record_that_says_column": (
        ("sig", types.SimpleNamespace(var_reads=(
            types.SimpleNamespace(block=1, stage=0, by="column"),))),
        ["column"]),
    "a_second_reader_beside_the_probe": (
        ("sig", types.SimpleNamespace(var_reads=(
            types.SimpleNamespace(block=1, stage=0, by="probe"),
            types.SimpleNamespace(block=2, stage=0, by="column")))),
        ["probe", "column"]),
}


@pytest.mark.parametrize("stub", list(STUB_PLANS))
def test_the_loader_refuses_a_program_that_would_not_probe(
        data, monkeypatch, stub):
    """Before anything is built: the build child exits with one sentence
    for a program whose plan keeps no record, or records anything but
    `probe` for IC1's reader of `v`."""
    from dgraph_tpu.engine import treebatch
    from dgraph_tpu.store.schema import parse_schema
    from loaders import ldbc_knows as loader
    planned, reads = STUB_PLANS[stub]
    monkeypatch.setattr(treebatch, "plan_tree", lambda store, blocks: planned)
    schema = parse_schema(gen.SCHEMA)
    assert loader.recorded_reads(schema) == reads
    with pytest.raises(SystemExit, match="no deployment of this"):
        loader.build({}, schema)


# ---------------------------------------------------------------------------
# the reference and its control

def brute(data, meta: dict) -> dict:
    """IC1 by Python loops over the edge list."""
    out = {}
    for s, d in zip(data["src"].tolist(), data["dst"].tolist()):
        out.setdefault(s, set()).add(d)
    start = meta["person"] - 1
    seen, frontier = {start}, {start}
    for _ in range(meta["depth"]):
        frontier = {d for s in frontier for d in out.get(s, ())} - seen
        seen |= frontier
    words = gen.dictionaries(data)
    rows = [(words["last_name"][data["last_name"][i]], i + 1,
             words["city"][data["city"][i]]) for i in seen
            if words["first_name"][data["first_name"][i]]
            == meta["first_name"]]
    return {"q": [{"first_name": meta["first_name"], "last_name": last,
                   "city": city}
                  for last, _uid, city in sorted(rows)[:meta["first"]]]}


def some_metas(data, count: int, seed: int = 3) -> list:
    rng = np.random.default_rng(seed)
    words = gen.dictionaries(data)["first_name"]
    return [{"template": "IC1", "person": int(p) + 1,
             "first_name": words[data["first_name"][o]], "depth": 3,
             "first": 20}
            for p, o in zip(rng.choice(4000, count, False),
                            rng.integers(0, 4000, count))]


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_the_reference_is_a_python_loop_s_ic1(data, depth):
    ref = reference.make(data, {})
    metas = [{**m, "depth": depth} for m in some_metas(data, 24)]
    assert [ref.answer(m) for m in metas] == [brute(data, m) for m in metas]
    assert any(len(ref.answer(m)["q"]) == 20 for m in metas) or depth < 3
    # a name of no dictionary, and a page of 3
    assert ref.answer({**metas[0], "first_name": "Nobody"}) == {"q": []}
    short = {**metas[0], "first": 3}
    assert ref.answer(short) == brute(data, short)


def test_the_control_disagrees_on_nearly_every_answer(data):
    """Adjacency rows cut at 8 edges: what a fixed-width device row that
    drops its overflow would reach."""
    ref = reference.make(data, {})
    ctrl = reference.make_control(data, {})
    metas = some_metas(data, 128)
    bad = sum(not ref.check(m, ctrl.answer(m))[0] for m in metas)
    assert bad >= 115
    assert all(ref.check(m, ref.answer(m))[0] for m in metas[:16])
    ok, why = ref.check(metas[0], {"q": []})
    assert not ok and "is right" in why


# ---------------------------------------------------------------------------
# the traffic

def test_the_traffic_is_the_same_places_under_every_seed(data):
    traffic = load(BENCH, "traffic", "ic1-batch.json")
    assert {k: traffic[k] for k in (
        "kind", "endpoint", "loop", "clients", "batch", "depth",
        "recurse_loop", "predicate", "first", "order", "draw_requests",
        "warm_requests")} == {
        "kind": "ic1_persons", "endpoint": "/query/batch", "loop": "closed",
        "clients": 1, "batch": 64, "depth": 3, "recurse_loop": False,
        "predicate": "knows", "first": 20, "order": "last_name",
        "draw_requests": 1, "warm_requests": 4}
    other = gen.generate(PARAMS, seed=5)
    mixes = [ic1_persons.make(d, traffic, s)
             for d, s in ((data, 2147483900), (other, 5))]
    streams = [m.requests(16, stream=100) for m in mixes]
    places = []
    for mix, reqs, d in zip(mixes, streams, (data, other)):
        back = np.argsort(mix.node_of)
        words = gen.dictionaries(d)["first_name"]
        for r in reqs:
            assert r["queries"] == 64 and r["path"] == "/query/batch"
            persons = [m["person"] for m in r["meta"]]
            assert len(set(persons)) == 64
            qs = json.loads(r["body"])["queries"]
            assert qs[0] == ic_mix.TEMPLATES["IC1"] % {
                "p": hex(persons[0]), "fn": r["meta"][0]["first_name"]}
            # a name that is borne
            assert all(m["first_name"] in
                       {words[k] for k in d["first_name"]}
                       for m in r["meta"])
        places.append([sorted(back[[m["person"] - 1 for m in r["meta"]]])
                       for r in reqs])
    # request by request the same places of the structure, other lanes
    assert places[0] == places[1]
    assert [m["person"] for m in streams[0][0]["meta"]] != \
        [m["person"] for m in streams[1][0]["meta"]]
    # requests never sent twice: the warm-up's and two chunks'
    sent = [tuple(p) for p in places[0]]
    assert len(mixes[0].warm_requests()) == 4 and len(set(sent)) == 16
    # a name is asked as often as it is borne: the commonest most often
    asked = [m["first_name"] for r in mixes[0].requests(60, stream=7)
             for m in r["meta"]]
    words = gen.dictionaries(data)["first_name"]
    top = words[np.bincount(data["first_name"]).argmax()]
    assert max(set(asked), key=asked.count) == top


def test_a_draw_the_kind_does_not_know_is_refused(data):
    traffic = load(BENCH, "traffic", "ic1-batch.json")
    with pytest.raises(SystemExit, match="not draws"):
        ic1_persons.make(data, {**traffic, "persons": "zipf"}, 1)


def test_the_cell_s_entries_are_what_the_benchmark_holds():
    bench = load(ROOT, "BENCHMARK.json")
    ent = load(HERE, "data", CELL + ".entries.json")
    holds_entries(bench, ent, CELL)
    # a count a lane is the Graph500 cell's reader, not this one's
    assert "tree_device_count_share.batch" not in ent["also_in"]
    counted = {m["name"]: m for m in bench["per_layer"]}[
        "tree_device_count_share.batch"]
    assert CELL not in counted["workloads"]
    cfg = load(ROOT, ent["configs"][0]["file"])
    assert cfg["source"] == ent["configs"][0]["source"]
    assert len(cfg["source"]) <= 200
    assert cfg["reduced"] == ["entities"] == list(cfg["reduced_why"])
    assert cfg["generator_params"]["persons"] == 633432
    assert cfg["generator_params"]["knows"] == 34185747
    assert " ".join(gen.SCHEMA.split()) == cfg["schema"]
    assert set(cfg["guarantees"]) == {"answers", "isolation", "durability"}
    assert "probing the filter's candidates" in cfg["served"]
    assert ent["workloads"][0]["chips"] == 1
    assert len(ent["workloads"][0]["why"]) <= 200


# ---------------------------------------------------------------------------
# the three metric files

BEFORE = """\
dgraph_tpu_kernel_group_queries_total{family="tree"} 256.0
dgraph_tpu_tree_var_reads_total{by="probe"} 256.0
dgraph_tpu_tree_probe_rows_total 700000.0
"""
AFTER = """\
dgraph_tpu_kernel_group_queries_total{family="tree"} 2304.0
dgraph_tpu_kernel_group_queries_total{family="shortest"} 90.0
dgraph_tpu_tree_var_reads_total{by="probe"} 2304.0
dgraph_tpu_tree_var_reads_total{by="column"} 0.0
dgraph_tpu_tree_probe_rows_total 6844000.0
"""
# the parent of PR 36: every lane's read of `v` is a column, and there is
# no count of probed rows
PARENT = """\
dgraph_tpu_kernel_group_queries_total{family="tree"} 2304.0
dgraph_tpu_tree_var_reads_total{by="column"} 2304.0
"""


def read_metric(name, ctx):
    spec = load(BENCH, "layer_metrics", name + ".json")
    reader = {"prom_ratio": prom_ratio,
              "lane_hop_roofline": lane_hop_roofline}[spec["reader"]]
    return reader.read(ctx, **spec["args"])


@pytest.mark.parametrize("name,want", [
    ("tree_probe_share.batch", 100.0),
    ("probe_rows_per_query.batch", 3000.0),
])
def test_a_counter_metric_reads_the_window_s_delta(name, want):
    ctx = {"prom_before": parse_prom(BEFORE), "prom_after": parse_prom(AFTER)}
    assert read_metric(name, ctx) == pytest.approx(want)


@pytest.mark.parametrize("name", ["tree_probe_share.batch",
                                  "probe_rows_per_query.batch"])
def test_a_program_without_the_counter_reads_nothing(name):
    ctx = {"prom_before": parse_prom(""), "prom_after": parse_prom(PARENT)}
    assert read_metric(name, ctx) is None
    ctx["prom_before"] = parse_prom(PARENT)
    assert read_metric(name, ctx) is None


def test_the_tree_program_s_roofline_share_over_knows():
    """Three turns of the scan a run of `jit_tree`, two runs, 0.9 s: the
    pull's byte model over the v5e's peak, `edges` the stored directed
    `knows`."""
    sizes = {"nodes": 633_432, "knows": 68_371_494}
    ctx = {"trace": {"device_plane": True,
                     "modules": [["jit_tree", 0.9, 2, 6.0]]},
           "root": BENCH, "device": {"kind": "TPU v5 lite"},
           "traffic": {"batch": 64}, "sizes": sizes}
    need = 6 * lane_hop_roofline.hop_bytes(633_432, 68_371_494, 64)
    assert read_metric("knows_tree_roofline.batch", ctx) == pytest.approx(
        100.0 * need / 819e9 / 0.9)
    assert 0 < read_metric("knows_tree_roofline.batch", ctx) < 1.0
    ctx["trace"] = {"device_plane": False, "modules": []}
    assert read_metric("knows_tree_roofline.batch", ctx) is None


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
    assert {"device_ms_per_query.batch", "knows_tree_roofline.batch"} <= (
        names - set(out["metrics"]))
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["tree_queries_per_launch.batch"] == 64
    assert m["tree_probe_share.batch"] == 100
    assert 20 < m["probe_rows_per_query.batch"] < 400
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
