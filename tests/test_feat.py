"""Feature-bearing traversal (ISSUE 19): `@msgpass` message passing.

The contract under test: every route — host numpy (the reference),
single-device jit, mesh shard_map, the fused featprop stage, and the
OOM-degraded fallback — binds the same `[k, d]` f32 aggregate, bit for
bit. Fixtures use small-integer-valued f32 components so sums are
exactly representable (order-independent) and the identity claims are
exact, not approximate. Aggregation is per-EDGE: duplicates count
twice, an edge participates iff its neighbour has a tablet row, and
`mean` is one IEEE f32 division of the exact sum by the participant
count.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from dgraph_tpu.engine import Engine, fused
from dgraph_tpu.engine import feat as efeat
from dgraph_tpu.ops import feat as ofeat
from dgraph_tpu.server.api import Alpha
from dgraph_tpu.store import vec
from dgraph_tpu.store.schema import parse_schema
from dgraph_tpu.store.store import StoreBuilder
from dgraph_tpu.utils import costprior, costprofile, memgov
from dgraph_tpu.utils.metrics import METRICS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIM = 4
AGGS = ("sum", "mean", "max")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_FUSED", "1")
    fused.reset()
    costprior.reset()
    costprofile.reset()
    memgov.set_alloc_fault(None)
    memgov.GOVERNOR.reset()
    yield
    fused.reset()
    costprior.reset()
    costprofile.reset()
    memgov.set_alloc_fault(None)
    memgov.GOVERNOR.reset()


def _feat_store(n=24, seed=3, skip_emb=()):
    """Zipfian friend graph where every node (minus `skip_emb`) carries
    a small-integer `emb` row — the test_vec.py fixture plus holes for
    the participation-mask claims."""
    rng = np.random.default_rng(seed)
    b = StoreBuilder(parse_schema(
        "emb: float32vector @dim(%d) .\n"
        "friend: [uid] @reverse .\n"
        "name: string @index(exact) ." % DIM))
    for i in range(1, n + 1):
        if i not in skip_emb:
            b.add_value(i, "emb",
                        [int(x) for x in rng.integers(0, 5, DIM)])
        b.add_value(i, "name", f"p{i % 7}")
        for j in rng.integers(1, n + 1, 3):
            if i != int(j):
                b.add_edge(i, "friend", int(j))
    return b.finalize()


# ---------------------------------------------------------------------------
# kernel semantics: independent python oracle, four graph shapes

def _oracle(subj, vecs, nbrs, seg, n_seg, agg):
    """Per-edge aggregation spelled as a python loop — independent of
    both the numpy reference and the jax kernel."""
    row = {int(s): vecs[i] for i, s in enumerate(subj)}
    bags = [[] for _ in range(n_seg)]
    ecnt = np.zeros(n_seg, np.int32)
    for nb, sg in zip(nbrs.tolist(), seg.tolist()):
        ecnt[sg] += 1
        if nb in row:
            bags[sg].append(row[nb])
    out = np.zeros((n_seg, vecs.shape[1]), np.float32)
    cnt = np.zeros(n_seg, np.int32)
    for i, bag in enumerate(bags):
        cnt[i] = len(bag)
        if not bag:
            continue
        m = np.stack(bag).astype(np.float32)
        if agg == "sum":
            out[i] = m.sum(0)
        elif agg == "mean":
            out[i] = m.sum(0) / np.float32(len(bag))
        else:
            out[i] = m.max(0)
    return out, cnt, ecnt


def _graphs():
    """(nbrs, seg, n_seg) edge sets: powerlaw dups, star hub, chain,
    and a degree-gap set with an empty segment and a segment whose
    every neighbour lacks a tablet row. The tablet holds EVEN ranks
    only, so odd neighbours exercise the participation mask."""
    rng = np.random.default_rng(7)
    subj = np.arange(0, 40, 2, dtype=np.int32)
    vecs = rng.integers(0, 5, (len(subj), DIM)).astype(np.float32)
    graphs = {
        "powerlaw": (np.minimum(rng.zipf(1.3, 200), 39).astype(np.int32),
                     rng.integers(0, 12, 200).astype(np.int32), 12),
        "star": (np.arange(40, dtype=np.int32),
                 np.where(np.arange(40) < 36, 0, 5).astype(np.int32), 8),
        "chain": (np.arange(1, 21, dtype=np.int32),
                  np.arange(20, dtype=np.int32), 20),
        "degree_gap": (
            np.concatenate([[2], rng.integers(0, 40, 60),
                            [1, 3, 5]]).astype(np.int32),
            np.concatenate([[0], np.full(60, 1),
                            np.full(3, 3)]).astype(np.int32), 4),
    }
    return subj, vecs, graphs


def test_host_combine_matches_python_oracle_every_graph_and_agg():
    subj, vecs, graphs = _graphs()
    for name, (nbrs, seg, n_seg) in graphs.items():
        for agg in AGGS:
            w_out, w_cnt, w_ecnt = _oracle(subj, vecs, nbrs, seg,
                                           n_seg, agg)
            out, cnt, ecnt = efeat.host_combine(subj, vecs, nbrs, seg,
                                                n_seg, agg)
            assert out.tobytes() == w_out.tobytes(), (name, agg)
            assert cnt.tolist() == w_cnt.tolist(), (name, agg)
            assert ecnt.tolist() == w_ecnt.tolist(), (name, agg)


def test_device_kernel_bit_identical_to_host_reference():
    subj, vecs, graphs = _graphs()
    for name, (nbrs, seg, n_seg) in graphs.items():
        for agg in AGGS:
            want = efeat.host_combine(subj, vecs, nbrs, seg, n_seg, agg)
            got = ofeat.combine_edges(subj, vecs, nbrs, seg,
                                      np.int32(len(nbrs)), n_seg, agg)
            assert np.asarray(got[0], np.float32).tobytes() \
                == want[0].tobytes(), (name, agg)
            assert np.asarray(got[1]).tolist() == want[1].tolist()
            assert np.asarray(got[2]).tolist() == want[2].tolist()


def test_empty_and_nonparticipating_segments_are_zero_not_nan():
    """degree_gap pins the two zero cases: segment 2 has no edges at
    all (ecnt 0) and segment 3's neighbours all lack rows (cnt 0,
    ecnt 3) — both aggregate to the zero vector, never inf/nan."""
    subj, vecs, graphs = _graphs()
    nbrs, seg, n_seg = graphs["degree_gap"]
    for agg in AGGS:
        out, cnt, ecnt = efeat.host_combine(subj, vecs, nbrs, seg,
                                            n_seg, agg)
        assert cnt[2] == 0 and ecnt[2] == 0
        assert cnt[3] == 0 and ecnt[3] == 3
        assert out[2].tolist() == [0.0] * DIM
        assert out[3].tolist() == [0.0] * DIM
        assert np.isfinite(out).all()


def test_duplicate_edges_count_twice():
    subj = np.array([1, 2], np.int32)
    vecs = np.array([[1, 0, 0, 0], [0, 1, 0, 0]], np.float32)
    nbrs = np.array([1, 1, 2], np.int32)
    seg = np.zeros(3, np.int32)
    out, cnt, _ = efeat.host_combine(subj, vecs, nbrs, seg, 1, "sum")
    assert out[0].tolist() == [2.0, 1.0, 0.0, 0.0]
    assert cnt[0] == 3
    out, _, _ = efeat.host_combine(subj, vecs, nbrs, seg, 1, "mean")
    # the one IEEE f32 division: sum / count, both f32
    assert out[0].tolist() == [float(np.float32(2) / np.float32(3)),
                               float(np.float32(1) / np.float32(3)),
                               0.0, 0.0]


# ---------------------------------------------------------------------------
# parser: the @msgpass grammar and its refusals

def test_parser_accepts_msgpass_and_defaults_agg_to_mean():
    from dgraph_tpu.dql import parse
    q = parse('{ q(func: uid(1)) @msgpass(pred: emb) { uid friend } }')
    mp = q[0].msgpass
    assert mp is not None and mp.pred == "emb" and mp.agg == "mean"


@pytest.mark.parametrize("bad", [
    '{ q(func: uid(1)) @msgpass(pred: emb, agg: median) { uid } }',
    '{ q(func: uid(1)) @msgpass(agg: sum) { uid } }',
    '{ q(func: uid(1)) @msgpass(pred: emb, depth: 2) { uid } }',
])
def test_parser_rejects_malformed_msgpass(bad):
    from dgraph_tpu.dql import ParseError, parse
    with pytest.raises(ParseError):
        parse(bad)


def test_msgpass_with_loop_recurse_is_a_typed_refusal():
    st = _feat_store()
    q = ('{ q(func: uid(1)) @recurse(depth: 3, loop: true) '
         '@msgpass(pred: emb, agg: sum) { uid friend } }')
    with pytest.raises(ValueError, match="loop"):
        Engine(st, device_threshold=10**9).query(q)


def test_msgpass_on_non_vector_predicate_is_a_typed_refusal():
    st = _feat_store()
    q = ('{ q(func: uid(1)) @msgpass(pred: name, agg: sum) '
         '{ uid friend } }')
    with pytest.raises(ValueError, match="float32vector"):
        Engine(st, device_threshold=10**9).query(q)


# ---------------------------------------------------------------------------
# engine routes: staged host == device, rendering discipline

_QUERIES = [
    '{ q(func: uid(1, 2, 3)) @msgpass(pred: emb, agg: sum) '
    '{ uid friend { uid } } }',
    '{ q(func: uid(2)) @recurse(depth: 3) '
    '@msgpass(pred: emb, agg: mean) { uid friend } }',
    '{ q(func: similar_to(emb, 4, "[1, 1, 2, 0]")) '
    '@recurse(depth: 2) @msgpass(pred: emb, agg: max) { uid friend } }',
]


def test_staged_device_route_bit_identical_to_host(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_FUSED", "0")
    st = _feat_store(n=48, seed=5)
    host = Engine(st, device_threshold=10**9)
    dev = Engine(st, device_threshold=0)
    for q in _QUERIES:
        assert json.dumps(host.query(q)) == json.dumps(dev.query(q)), q
    assert METRICS.get("feat_route_total", route="host") >= 3
    assert METRICS.get("feat_route_total", route="device") >= 3
    assert METRICS.get("feat_bytes_total") > 0


def test_msgpass_renders_count_leaf_style_keys(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_FUSED", "0")
    st = _feat_store(n=24)
    out = Engine(st, device_threshold=10**9).query(_QUERIES[0])
    keyed = [o for o in out["q"] if "sum(emb)" in o]
    assert keyed, out
    for o in keyed:
        v = o["sum(emb)"]
        assert isinstance(v, list) and len(v) == DIM
        assert all(isinstance(x, float) for x in v)


def test_nodes_without_kept_edges_carry_no_feat_key(monkeypatch):
    """Membership is structural (ecnt): a frontier node with zero kept
    edges gets NO entry — not a zero vector."""
    monkeypatch.setenv("DGRAPH_TPU_FUSED", "0")
    b = StoreBuilder(parse_schema(
        "emb: float32vector @dim(%d) .\nfriend: [uid] @reverse ." % DIM))
    for i in (1, 2, 3):
        b.add_value(i, "emb", [i, 0, 0, 0])
    b.add_edge(1, "friend", 2)  # node 3 has no out-edges
    st = b.finalize()
    out = Engine(st, device_threshold=10**9).query(
        '{ q(func: uid(1, 3)) @msgpass(pred: emb, agg: sum) '
        '{ uid friend { uid } } }')
    by_uid = {o["uid"]: o for o in out["q"]}
    assert "sum(emb)" in by_uid["0x1"]
    assert by_uid["0x1"]["sum(emb)"] == [2.0, 0.0, 0.0, 0.0]
    assert "sum(emb)" not in by_uid["0x3"]


# ---------------------------------------------------------------------------
# fused featprop: one launch, digests identical to staged

def test_fused_featprop_matches_staged_for_every_agg(monkeypatch):
    st = _feat_store(n=64, seed=9)
    monkeypatch.setenv("DGRAPH_TPU_FUSED", "0")
    staged = Engine(st, device_threshold=10**9)
    want = [json.dumps(staged.query(q)) for q in _QUERIES]
    monkeypatch.setenv("DGRAPH_TPU_FUSED", "1")
    fused.reset()
    dev = Engine(st, device_threshold=0)
    for q, w in zip(_QUERIES, want):
        assert json.dumps(dev.query(q)) == w, q
    assert METRICS.get("feat_route_total", route="fused") >= 1
    assert not [s for s, e in fused.status()["shapes"].items()
                if e.get("disabled")]


def test_fused_featprop_collapses_to_one_launch_digest_equal():
    """The tentpole headline: similar_to → @recurse+@msgpass → render
    compiles to ONE XLA program, byte-identical to the staged serve."""
    st = _feat_store(n=64, seed=9)
    q = ('{ q(func: similar_to(emb, 5, "[2, 0, 1, 3]")) '
         '@recurse(depth: 2) @msgpass(pred: emb, agg: mean) '
         '{ uid friend } }')
    a = Alpha(base=st, device_threshold=0)
    os.environ["DGRAPH_TPU_FUSED"] = "0"
    try:
        staged_raw = a.query_raw(q)
        a.query_raw(q)
        staged_launches = costprofile.recent(1)[0]["kernel_launches"]
    finally:
        os.environ["DGRAPH_TPU_FUSED"] = "1"
    fused.reset()
    a.query_raw(q)  # warm: compile outside the measured serve
    fused_raw = a.query_raw(q)
    rec = costprofile.recent(1)[0]
    assert fused_raw == staged_raw
    assert staged_launches > 1
    assert rec["kernel_launches"] == 1, rec
    assert "fused" in rec["shape"]


# ---------------------------------------------------------------------------
# satellite 1: similar_to structural-empty + typed refusals, non-sticky

def test_similar_to_uid_without_embedding_row_serves_empty():
    st = _feat_store(n=24, skip_emb=(7,))
    dev = Engine(st, device_threshold=0)
    host = Engine(st, device_threshold=10**9)
    q = '{ q(func: similar_to(emb, 3, 7)) { uid friend { uid } } }'
    assert dev.query(q) == host.query(q) == {"q": []}
    # the empty is structural, not an error: no fused shape tripped
    assert not [s for s, e in fused.status()["shapes"].items()
                if e.get("disabled")]
    # and the same shape with a seeded uid still serves fused
    good = '{ q(func: similar_to(emb, 3, 5)) { uid friend { uid } } }'
    want_good = host.query(good)
    dev.query(good)
    f0 = METRICS.get("fused_route_total", route="fused")
    assert dev.query(good) == want_good
    assert METRICS.get("fused_route_total", route="fused") == f0 + 1


def test_malformed_similar_to_raises_typed_error_without_sticky():
    st = _feat_store(n=24)
    dev = Engine(st, device_threshold=0)
    good = '{ q(func: similar_to(emb, 3, 5)) { uid } }'
    dev.query(good)
    assert issubclass(vec.VecQueryError, ValueError)
    for bad in [
        '{ q(func: similar_to(emb, 0, 5)) { uid } }',
        '{ q(func: similar_to(emb, 3, "nonsense")) { uid } }',
        '{ q(func: similar_to(emb, 3, "[1, 2]")) { uid } }',
    ]:
        with pytest.raises(vec.VecQueryError):
            dev.query(bad)
    # user errors never disable the shape: the good query still fuses
    assert not [s for s, e in fused.status()["shapes"].items()
                if e.get("disabled")]
    f0 = METRICS.get("fused_route_total", route="fused")
    dev.query(good)
    assert METRICS.get("fused_route_total", route="fused") == f0 + 1


# ---------------------------------------------------------------------------
# memory governance: feat.agg OOM lifecycle, vec re-placement meter

def test_alloc_fault_at_feat_agg_absorbed_by_evict_retry(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_FUSED", "0")
    st = _feat_store(n=48, seed=5)
    q = _QUERIES[1]
    want = json.dumps(Engine(st, device_threshold=10**9).query(q))
    armed = [True]

    def hook(site):
        if armed[0] and site == "feat.agg":
            armed[0] = False
            return True
        return False

    memgov.set_alloc_fault(hook)
    assert json.dumps(Engine(st, device_threshold=0).query(q)) == want
    assert not armed[0], "the injected alloc fault never fired"
    stats = memgov.GOVERNOR.oom_stats()
    assert stats["events"] >= 1 and stats["retries"] >= 1


def test_persistent_feat_fault_degrades_to_host_and_sticks(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_FUSED", "0")
    st = _feat_store(n=48, seed=5)
    q = _QUERIES[1]
    want = json.dumps(Engine(st, device_threshold=10**9).query(q))
    host0 = METRICS.get("feat_route_total", route="host")
    memgov.set_alloc_fault(lambda site: site == "feat.agg")
    deg = Engine(st, device_threshold=0)
    assert json.dumps(deg.query(q)) == want
    assert METRICS.get("feat_route_total", route="host") == host0 + 1
    assert memgov.GOVERNOR.oom_stats()["degraded"] >= 1
    # sticky: hook gone, the shape keeps the host route — identically
    memgov.set_alloc_fault(None)
    assert json.dumps(deg.query(q)) == want
    assert METRICS.get("feat_route_total", route="host") == host0 + 2


def test_vec_replacement_meter_and_memory_detail(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_FUSED", "0")
    st = _feat_store(n=48)
    dev = Engine(st, device_threshold=0)
    dev.query(_QUERIES[1])  # places the emb stack on device
    assert st._vec_dev
    detail = memgov.GOVERNOR.status()["caches"]["store.vec"]["detail"]
    emb = [d for d in detail if d["pred"] == "emb"]
    assert emb and emb[0]["dim"] == DIM and emb[0]["rows"] == 48
    assert emb[0]["placement"] == "device"
    r0 = METRICS.get("vec_replacements_total", kind="device")
    memgov.GOVERNOR.set_budgets(device_bytes=1)
    try:
        memgov.GOVERNOR.evict_to_low("device")
    finally:
        memgov.GOVERNOR.set_budgets()
    assert not st._vec_dev
    dev.query(_QUERIES[1])  # re-placement — the metered event
    assert st._vec_dev
    assert METRICS.get("vec_replacements_total", kind="device") == r0 + 1


# ---------------------------------------------------------------------------
# mesh route: 4 virtual devices, own subprocess

_CHILD = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["DGRAPH_TPU_FUSED"] = "0"  # exercise the mesh feat route

    import json
    import numpy as np
    import jax
    assert len(jax.devices()) == 4, jax.devices()

    from dgraph_tpu.engine import Engine
    from dgraph_tpu.parallel.mesh import make_mesh, reshard_count
    from dgraph_tpu.store.schema import parse_schema
    from dgraph_tpu.store.store import StoreBuilder
    from dgraph_tpu.utils.metrics import METRICS

    rng = np.random.default_rng(3)
    b = StoreBuilder(parse_schema(
        "emb: float32vector @dim(4) .\\nfriend: [uid] @reverse ."))
    for i in range(1, 51):
        b.add_value(i, "emb", [int(x) for x in rng.integers(0, 5, 4)])
        for j in rng.integers(1, 51, 3):
            if i != int(j):
                b.add_edge(i, "friend", int(j))
    st = b.finalize()

    host = Engine(st, device_threshold=10**9)
    mesh = Engine(st, device_threshold=0, mesh=make_mesh(4))
    for q in [
        '{ q(func: uid(1, 2, 3)) @msgpass(pred: emb, agg: sum) '
        '{ uid friend { uid } } }',
        '{ q(func: uid(2)) @recurse(depth: 3) '
        '@msgpass(pred: emb, agg: mean) { uid friend } }',
        '{ q(func: similar_to(emb, 4, "[1, 1, 2, 0]")) '
        '@recurse(depth: 2) @msgpass(pred: emb, agg: max) '
        '{ uid friend } }',
    ]:
        a, b_ = host.query(q), mesh.query(q)
        assert json.dumps(a) == json.dumps(b_), (q, a, b_)
    assert METRICS.get("feat_route_total", route="mesh") >= 3
    assert reshard_count() == 0, reshard_count()
    print("PASS 4dev msgpass bit-identity reshard-free", flush=True)
""")


def test_mesh_msgpass_bit_identical_on_4_virtual_devices(tmp_path):
    script = tmp_path / "feat_mesh_child.py"
    script.write_text(_CHILD)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT)
    proc = subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True,
                          cwd=str(ROOT), env=env, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS 4dev msgpass bit-identity reshard-free" in proc.stdout


# ---------------------------------------------------------------------------
# inventory satellite

def test_fused_inventory_carries_five_stage_kinds():
    from dgraph_tpu.engine.fused import _STAGE_EMITTERS, STAGE_KINDS
    assert len(STAGE_KINDS) == 5
    assert "featprop" in STAGE_KINDS
    # both-ways pin mirrors test_lint's facts discipline
    assert set(STAGE_KINDS) == set(_STAGE_EMITTERS)
