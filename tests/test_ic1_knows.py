"""LDBC SNB complex read IC1 on the level-tree lane kernel: a block rooted
at the var of an @recurse stage that no block renders, under a filter that
index lookups answer, is handed the filter's candidates that the stage's
reachable set holds (one bit test a candidate), never the set itself.

The graph is the benchmark's own generator (benchmark/generators/
ldbc_knows.py) at a small size, loaded by the benchmark's loader; every
batch answer is held to the per-query engine AND, where the query is IC1
itself, to the benchmark's plain reference (benchmark/references/
ldbc_knows.py: numpy only, a breadth-first search, a sort).
"""

import collections
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from generators import ldbc_knows as gen           # noqa: E402
from loaders import ldbc_knows as loader           # noqa: E402
from references import ldbc_knows as reference     # noqa: E402
from traffic_kinds import ic1_persons, ic_mix      # noqa: E402

from dgraph_tpu.dql.parser import parse            # noqa: E402
from dgraph_tpu.engine import Engine               # noqa: E402
from dgraph_tpu.engine.batch import plan_batch_groups, run_batch  # noqa: E402
from dgraph_tpu.engine.treebatch import TreePlan   # noqa: E402
from dgraph_tpu.utils.metrics import METRICS       # noqa: E402

# few last names, so that an answer's order is settled by uid
PARAMS = {"persons": 4000, "knows": 36000, "degree_sigma": 1.0,
          "degree_cap": 200, "local_share": 0.8, "first_names": 96,
          "last_names": 12, "cities": 16, "name_zipf": 0.8,
          "structure_seed": 75}
RECURSE = ("v as var(func: uid(%s)) @recurse(depth: 3, loop: false) "
           "{ knows } ")
READ = "{ first_name last_name city }"


def ic1(person: int, name: str, depth: int = 3) -> str:
    return ic1_persons.QUERY % (hex(person), depth, "false", "knows",
                                "last_name", 20, name)


@pytest.fixture(scope="module")
def knows():
    """(data, store, reference, first names by how many bear them)."""
    from dgraph_tpu.store.schema import parse_schema
    from dgraph_tpu.store.store import Store, build_indexes
    data = gen.generate(PARAMS, seed=11)
    schema = parse_schema(gen.SCHEMA)
    uids, preds = loader.build(data, schema)
    build_indexes(preds)
    words = gen.dictionaries(data)["first_name"]
    borne = np.bincount(data["first_name"], minlength=len(words))
    by_count = [words[i] for i in np.argsort(-borne, kind="stable")]
    return (data, Store(uids=uids, schema=schema, preds=preds),
            reference.make(data, {}), by_count)


def reads() -> dict:
    return {by: METRICS.get("tree_var_reads_total", by=by)
            for by in ("probe", "column", "count", "edge_walk")}


def serve(store, qs, **per_lane):
    """The answers of one batch through plan_batch_groups -> run_batch:
    every query rides one tree launch whose @recurse stage keeps no hop
    masks; the plan's record of how each reader of the stage's var will
    be answered is `per_lane` (readers by label) BEFORE anything runs;
    `tree_var_reads_total` then rises by the record's count a lane under
    each label and under no other; every answer is the per-query
    engine's."""
    plans, leftover = plan_batch_groups(store, [parse(q) for q in qs])
    assert len(plans) == 1 and not leftover
    plan, idxs = plans[0]
    assert isinstance(plan, TreePlan) and idxs == list(range(len(qs)))
    assert [s.keep_hops for s in plan.stages] == [False]
    assert collections.Counter(r.by for r in plan.var_reads) == per_lane
    assert all(r.stage == 0 for r in plan.var_reads)
    before = reads()
    rows = METRICS.get("tree_probe_rows_total")
    got = run_batch(store, plan, 10**9)
    assert got is not None
    after = reads()
    assert {by: after[by] - before[by] for by in after} == {
        "probe": 0, "column": 0, "count": 0, "edge_walk": 0,
        **{by: k * len(qs) for by, k in per_lane.items()}}
    if not per_lane.get("probe"):
        assert METRICS.get("tree_probe_rows_total") == rows
    eng = Engine(store, device_threshold=10**9)
    assert got == [eng.query(q) for q in qs]
    return got


def metas(persons, names):
    return [{"person": int(p), "first_name": w, "depth": 3, "first": 20}
            for p, w in zip(persons, names)]


def test_the_template_is_the_ic_mix_s():
    assert ic1(0x2a, "Ba") == ic_mix.TEMPLATES["IC1"] % {
        "p": hex(0x2a), "fn": "Ba"}


@pytest.mark.parametrize("lanes", [8, 32, 64])
def test_ic1_equals_engine_and_reference(knows, lanes):
    """Start persons that all differ, each asking for the first name of
    another person: the traffic kind's draw."""
    data, store, ref, _names = knows
    rng = np.random.default_rng(lanes)
    n = int(data["n_nodes"])
    words = gen.dictionaries(data)["first_name"]
    ms = metas(rng.choice(n, lanes, replace=False) + 1,
               [words[data["first_name"][i]]
                for i in rng.integers(0, n, lanes)])
    rows = METRICS.get("tree_probe_rows_total")
    got = serve(store, [ic1(m["person"], m["first_name"]) for m in ms],
                probe=1)
    assert got == [ref.answer(m) for m in ms]
    assert any(len(a["q"]) == 20 for a in got)
    # a candidate is a bearer of the name, reached or not
    borne = np.bincount(data["first_name"], minlength=len(words))
    assert METRICS.get("tree_probe_rows_total") - rows == sum(
        borne[words.index(m["first_name"])] for m in ms)


def test_the_mix_s_own_requests(knows):
    """What `traffic_kinds/ic1_persons.py` sends is what is held equal
    here: a request of the cell's parameters at batch 16."""
    data, store, ref, _names = knows
    params = {"endpoint": "/query/batch", "batch": 16, "depth": 3,
              "recurse_loop": False, "predicate": "knows", "first": 20,
              "order": "last_name", "persons": "uniform-distinct",
              "names": "of-a-uniform-person", "draw_requests": 1,
              "warm_requests": 1, "schedule_seed": 5}
    req, = ic1_persons.make(data, params, seed=11).requests(1)
    qs = json.loads(req["body"])["queries"]
    assert len({m["person"] for m in req["meta"]}) == 16
    got = serve(store, qs, probe=1)
    assert got == [ref.answer(m) for m in req["meta"]]


CASES = {
    # the name asked of each lane, from the names by how many bear them
    "a_name_nobody_bears": lambda names: "Nobody",
    "fewer_than_twenty_matches": lambda names: names[60],
    "ties_on_last_name_broken_by_uid": lambda names: names[0],
}


@pytest.mark.parametrize("case", list(CASES))
def test_ic1_answer_shapes(knows, case):
    data, store, ref, names = knows
    name = CASES[case](names)
    ms = metas(np.arange(1, 13) * 97, [name] * 12)
    got = serve(store, [ic1(m["person"], name) for m in ms], probe=1)
    assert got == [ref.answer(m) for m in ms]
    sizes = [len(a["q"]) for a in got]
    if case == "a_name_nobody_bears":
        assert max(sizes) == 0
    elif case == "fewer_than_twenty_matches":
        assert 0 < max(sizes) < 20
    else:
        # 12 last names among 20 rows: equal keys stand side by side,
        # in the order of their uids (the reference sorts by both)
        assert min(sizes) == 20
        lasts = [r["last_name"] for r in got[0]["q"]]
        assert lasts == sorted(lasts) and len(set(lasts)) < 20


def test_the_start_person_bears_the_name(knows):
    """`v` holds the block's root: a start person of the name asked is
    among its own answers where the page reaches it."""
    data, store, ref, _names = knows
    words = gen.dictionaries(data)
    rare = np.bincount(data["first_name"], minlength=96)
    starts = np.nonzero(rare[data["first_name"]] <= 12)[0][:8]
    ms = metas(starts + 1, [words["first_name"][data["first_name"][i]]
                            for i in starts])
    got = serve(store, [ic1(m["person"], m["first_name"]) for m in ms],
                probe=1)
    assert got == [ref.answer(m) for m in ms]
    for i, a in zip(starts, got):
        own = {"first_name": words["first_name"][data["first_name"][i]],
               "last_name": words["last_name"][data["last_name"][i]],
               "city": words["city"][data["city"][i]]}
        assert own in a["q"]


OTHER_READERS = {
    # the reader of v -> how each lane's read of v is counted
    "eq_or_eq":
        ("q(func: uid(v), orderasc: last_name, first: 20) "
         '@filter(eq(first_name, "%(a)s") OR eq(first_name, "%(b)s")) '
         + READ, {"probe": 1}),
    "eq_and_city":
        ("q(func: uid(v), orderasc: last_name, first: 20) "
         '@filter(eq(first_name, "%(a)s") AND eq(city, "%(c)s")) '
         + READ, {"probe": 1}),
    "not_filter":
        ("q(func: uid(v), orderasc: last_name, first: 20) "
         '@filter(not eq(first_name, "%(a)s")) ' + READ, {"column": 1}),
    "filterless":
        ("q(func: uid(v), orderasc: last_name, first: 20) " + READ,
         {"column": 1}),
    "uid_in_the_filter":
        ("q(func: uid(v), orderasc: last_name, first: 20) "
         '@filter(eq(first_name, "%(a)s") AND uid(v)) ' + READ,
         {"column": 1}),
    "one_block_probes_and_one_counts":
        ("q(func: uid(v), orderasc: last_name, first: 20) "
         '@filter(eq(first_name, "%(a)s")) ' + READ
         + " c(func: uid(v)) { count(uid) }", {"probe": 1, "count": 1}),
    "one_block_probes_and_one_reads_v_whole":
        ("q(func: uid(v), orderasc: last_name, first: 20) "
         '@filter(eq(first_name, "%(a)s")) ' + READ
         + " w(func: uid(v), first: 3) { uid }", {"probe": 1, "column": 1}),
    "v_read_whole_and_then_probed":
        ("w(func: uid(v), first: 3) { uid } "
         "q(func: uid(v), orderasc: last_name, first: 20) "
         '@filter(eq(first_name, "%(a)s")) ' + READ,
         {"probe": 1, "column": 1}),
}


@pytest.mark.parametrize("reader", list(OTHER_READERS))
def test_what_each_reader_of_v_is_handed(knows, reader):
    """The probe is for a filter that is evaluable before the launch
    (`_filter_ok`); any other reader keeps the column, a count the
    device's count, and every answer is the per-query engine's."""
    data, store, _ref, names = knows
    block, per_lane = OTHER_READERS[reader]
    city = gen.dictionaries(data)["city"][int(data["city"][0])]
    qs = ["{ " + RECURSE % hex(int(p))
          + block % {"a": names[k % 7], "b": names[7 + k % 5], "c": city}
          + " }" for k, p in enumerate(np.arange(1, 11) * 131)]
    got = serve(store, qs, **per_lane)
    assert any(a["q"] for a in got)


def test_a_value_var_on_a_leaf_of_the_stage_lists_the_set(knows):
    """A leaf of the stage's own block that binds a value var binds it
    over every visited node: the stage's block is itself a `column`
    reader on the record, beside the block that probes."""
    data, store, _ref, names = knows
    qs = ["{ v as var(func: uid(%s)) @recurse(depth: 3, loop: false) "
          "{ knows born as birthday_year } "
          "q(func: uid(v), orderasc: val(born), first: 5) "
          '@filter(eq(first_name, "%s")) { first_name birthday_year } }'
          % (hex(int(p)), names[k % 5])
          for k, p in enumerate(np.arange(1, 11) * 113)]
    got = serve(store, qs, probe=1, column=1)
    assert any(a["q"] for a in got)


def test_a_name_bound_anew_is_recorded_column(knows):
    """Which set `v` stands for when `q` reads it only the run knows
    where a second block binds the name anew: the record says `column`
    (so `seen` is there if the run wants it), the run reads whichever
    binding stands, and the answers are the per-query engine's."""
    data, store, _ref, names = knows
    qs = ["{ " + RECURSE % hex(int(p))
          + "v as var(func: uid(%s)) { knows } " % hex(int(p) + 1)
          + "q(func: uid(v), orderasc: last_name, first: 20) "
          '@filter(eq(first_name, "%s")) ' % names[k % 5] + READ + " }"
          for k, p in enumerate(np.arange(1, 11) * 151)]
    plans, leftover = plan_batch_groups(store, [parse(q) for q in qs])
    assert len(plans) == 1 and not leftover
    plan = plans[0][0]
    assert [r.by for r in plan.var_reads] == ["column"]
    before = reads()
    got = run_batch(store, plan, 10**9)
    assert reads()["probe"] == before["probe"]
    eng = Engine(store, device_threshold=10**9)
    assert got == [eng.query(q) for q in qs]


def test_the_record_splits_the_group_and_not_the_device_program(knows):
    """A count and a probe over one stage are two groups of a batch (the
    host answers them differently) and one compiled program (the device
    runs the same stages)."""
    data, store, _ref, names = knows
    qs = ([ic1(p, names[0]) for p in range(1, 9)]
          + ["{ " + RECURSE % hex(p) + "q(func: uid(v)) { count(uid) } }"
             for p in range(9, 17)])
    plans, leftover = plan_batch_groups(store, [parse(q) for q in qs])
    assert not leftover and [idxs for _p, idxs in plans] == [
        list(range(8)), list(range(8, 16))]
    probing, counting = (p for p, _idxs in plans)
    assert probing.sig != counting.sig
    assert probing.program_sig == counting.program_sig
    compiles = METRICS.get("jit_compile_total", kernel="treebatch.tree_kernel")
    hits = METRICS.get("jit_cache_hits_total", kernel="treebatch.tree_kernel")
    eng = Engine(store, device_threshold=10**9)
    for plan, idxs in plans:
        assert run_batch(store, plan, 10**9) == [
            eng.query(qs[i]) for i in idxs]
    assert METRICS.get("jit_compile_total",
                       kernel="treebatch.tree_kernel") - compiles <= 1
    assert METRICS.get("jit_cache_hits_total",
                       kernel="treebatch.tree_kernel") - hits >= 1


def test_seen_comes_back_with_the_launch_or_not_at_all(knows):
    """`seen` is copied inside phase `batch.fetch`, once a request, where
    a block reads its members; a batch of counts copies nothing; the
    probe runs under `batch.probe` inside `batch.render`."""
    from dgraph_tpu.utils import tracing
    data, store, _ref, names = knows
    spans = []
    sink = spans.append
    tracing.add_sink(sink)
    try:
        serve(store, [ic1(p, names[0]) for p in range(1, 9)], probe=1)
        probing = list(spans)
        del spans[:]
        serve(store, ["{ " + RECURSE % hex(p)
                      + "q(func: uid(v)) { count(uid) } }"
                      for p in range(1, 9)], count=1)
    finally:
        tracing.remove_sink(sink)
    n = store.n_nodes
    fetch = [s for s in probing if s.name == "batch.fetch"]
    assert [s.attrs["bytes"] for s in fetch] == [4 * (n + 1)]
    render, = [s for s in probing if s.name == "batch.render"]
    probes = [s for s in probing if s.name == "batch.probe"]
    assert len(probes) == 8
    assert {s.parent_id for s in probes} <= {
        s.span_id for s in probing if s.name == "engine.block"
        and s.parent_id == render.span_id}
    assert not [s for s in probing + spans
                if s.name == "batch.fetch_column"]
    assert [s.attrs["bytes"] for s in spans
            if s.name == "batch.fetch"] == [0]


def _hop_frontiers(rel, ranks, depth):
    """The rows a launch's hops expand: for each hop, the union over the
    lanes of the rows a lane first reached the hop before (a search a
    lane from its start person, in numpy sets)."""
    fresh = [{int(r)} for r in ranks]
    seen = [set(f) for f in fresh]
    out = []
    for _ in range(depth):
        out.append(np.array(sorted(set().union(*fresh)), np.int64))
        for q, f in enumerate(fresh):
            fresh[q] = {int(v) for u in f for v in rel.row(u)} - seen[q]
            seen[q] |= fresh[q]
    return out


@pytest.mark.parametrize("batch", ["a_few_persons", "sixty_four"])
def test_hop_two_is_pushed_where_a_push_is_the_cheaper_way(knows, batch):
    """The caps of a pushed hop are what a pull of `knows` costs
    (ops/bfs.py push_caps), not a 128th of its edges: a batch of as few
    start persons as put hop 2's frontier over the old cap and under the
    new runs hops 1 and 2 pushed and hop 3 pulled, `kernel_push_slots_
    total` rises by the two frontiers' out-edges, and a batch of 64,
    whose second frontier is over the new caps too, pushes hop 1 alone."""
    from dgraph_tpu.engine.batch import MIN_BATCH, _ell_for
    from dgraph_tpu.ops.bfs import push_caps
    data, store, ref, names = knows
    rel = store.rel("knows", False)
    deg = np.diff(rel.indptr)
    f_cap, e_cap, chunk = push_caps(_ell_for(store, "knows", False))
    old_cap = len(rel.indices) // 128
    assert (len(rel.indices), old_cap) == (72000, 562) and \
        e_cap > 4 * old_cap

    def slots_pushed(frontier):
        """A hop's slots where the caps hold its frontier, else None."""
        rows = frontier[deg[frontier] > 0]
        fits = len(rows) <= f_cap and deg[rows].sum() <= e_cap \
            and deg[rows].max(initial=0) <= chunk
        return int(deg[rows].sum()) if fits else None

    order = np.random.default_rng(44).permutation(int(data["n_nodes"]))
    if batch == "sixty_four":
        persons = order[:64]
    else:
        # the fewest persons (a group is MIN_BATCH queries at least)
        # whose friends' out-edges pass the old cap
        persons = next(
            order[:k] for k in range(MIN_BATCH, 64) if deg[_hop_frontiers(
                rel, store.rank_of(order[:k] + 1), 2)[1]].sum() > old_cap)
    hops = [slots_pushed(f) for f in _hop_frontiers(
        rel, store.rank_of(persons + 1), 3)]
    if batch == "sixty_four":
        assert hops[0] is not None and hops[1:] == [None, None]
    else:
        assert len(persons) < 8 and hops[2] is None
        assert hops[0] < old_cap < hops[1] <= e_cap
    ms = metas(persons + 1, [names[k % 5] for k in range(len(persons))])

    def counters():
        return [METRICS.get(f"kernel_{k}_total", family="tree")
                for k in ("hops_run", "hops_push", "push_slots")]

    before = counters()
    got = serve(store, [ic1(m["person"], m["first_name"]) for m in ms],
                probe=1)
    assert got == [ref.answer(m) for m in ms]
    pushed = [h for h in hops if h is not None]
    assert [b - a for a, b in zip(before, counters())] == [
        3, len(pushed), sum(pushed)]
    assert len(pushed) == (1 if batch == "sixty_four" else 2)


@pytest.mark.parametrize("program", ["this_one", "the_parents"])
def test_the_benchmark_reads_the_slots_pushed_a_query(knows, program):
    """`benchmark/layer_metrics/push_slots_per_query.batch.json` through
    the benchmark's own reader, over the registry's exposition before and
    after a batch of eight: the slots hop 1 pushed (the start persons'
    out-edges; eight persons' friends are over the row cap) over the
    eight queries; and nothing, not 0, from an exposition without the
    series, which is what the parent's program gives the same file."""
    from harness.server import parse_prom
    from readers import prom_ratio
    data, store, _ref, names = knows
    with open(os.path.join(BENCH, "layer_metrics",
                           "push_slots_per_query.batch.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "prom_ratio"
    persons = np.arange(1, 9) * 211
    before = parse_prom(METRICS.render())
    serve(store, [ic1(int(p), names[0]) for p in persons], probe=1)
    after = parse_prom(METRICS.render())
    if program == "the_parents":
        before, after = ([x for x in series if not x[0].startswith(
            "dgraph_tpu_kernel_push_slots")] for series in (before, after))
    got = prom_ratio.read({"prom_before": before, "prom_after": after},
                          **spec["args"])
    deg = np.diff(store.rel("knows", False).indptr)
    assert got == (None if program == "the_parents" else
                   deg[store.rank_of(persons)].sum() / 8)


# ---------------------------------------------------------------------------
# an order's keys by whole arrays

COLUMNS = {
    # kind -> the column's values, some ranks left without one
    "str": ["pear", "apple", "fig", "apple", "Zoe", "fig", "kiwi", "a"],
    "int": [5, -3, 12, 5, 0, 7, -3, 99],
    "float": [0.5, -1.25, 3.0, 0.5, 2.5, -7.0, 1e9, 0.0],
    "datetime": ["2020-01-02", "1999-12-31", "2020-01-02", "2010-06-01",
                 "2001-01-01", "1970-01-01", "2030-03-03", "2010-06-01"],
    "bool": [True, False, True, True, False, False, True, False],
    # values of two kinds: only the value-by-value path settles them
    "str_and_int": ["b", 3, "a", 1, "c", 2, "a", 0],
}


@pytest.mark.parametrize("desc", [False, True], ids=["asc", "desc"])
@pytest.mark.parametrize("kind", list(COLUMNS))
def test_an_order_s_keys_by_whole_arrays_order_as_the_values_do(kind, desc):
    """`Executor.order_ranks` over a typed column (keys by whole arrays,
    a column of `str` by its cached order codes) gives the order a plain
    sort of the values gives: missing values last, ties by rank."""
    from dgraph_tpu.engine.execute import Executor, _column_keys
    from dgraph_tpu.engine.ir import Order
    from dgraph_tpu.store.schema import parse_schema
    from dgraph_tpu.store.store import PredicateData, Store, ValueColumn
    raw = COLUMNS[kind]
    n = 12
    subj = np.array([0, 1, 3, 4, 6, 7, 9, 11], np.int32)   # 4 ranks bare
    if kind == "datetime":
        vals = np.array(raw, "datetime64[D]")
    elif kind in ("str", "str_and_int"):
        vals = np.empty(len(raw), object)
        vals[:] = raw
    else:
        vals = np.array(raw)
    schema = parse_schema("p: string .")
    pd = PredicateData(schema=schema.get("p"))
    pd.vals[""] = col = ValueColumn(subj=subj, vals=vals)
    store = Store(uids=np.arange(1, n + 1, dtype=np.int64), schema=schema,
                  preds={"p": pd})
    ranks = np.arange(n, dtype=np.int32)
    hit = np.isin(ranks, subj)
    idx = np.minimum(np.searchsorted(subj, ranks), len(subj) - 1)
    assert (_column_keys(col, idx, hit) is None) == (kind == "str_and_int")
    got = ranks[Executor(store).order_ranks(
        ranks, [Order(attr="p", desc=desc)])].tolist()
    if kind == "str_and_int":
        return          # no plain order to hold it to; the path is the old one
    value = dict(zip(subj.tolist(), raw))
    have = sorted(value, key=lambda r: value[r], reverse=desc)
    # ties keep ascending rank, in either direction
    want = []
    for v in dict.fromkeys(value[r] for r in have):
        want += sorted(r for r in have if value[r] == v)
    assert got == want + [r for r in ranks.tolist() if r not in value]
