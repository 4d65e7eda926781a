"""Mesh-sharded serving (ISSUE 10): the 4-virtual-device subprocess
fixture (sharded multi-hop bit-identical to the single-device engine
with ZERO steady-path reshards — the acceptance contract), chain-hop
@recurse vs the host loop, the reshard guard's
detection of mis-sharded hop inputs, tablet residency gauges + fold
carry, learned route promotion, and the cost-prior plumbing: mesh
expansions record shard-keyed costs that /debug/scheduler surfaces
(the PR-9 "feed the MESH layer" follow-on, closed).

Runs on CPU: conftest fakes 8 host devices in-process
(`--xla_force_host_platform_device_count`), and the subprocess fixture
launches its own 4-device child, so none of this needs a TPU.
"""

import json
import subprocess
import sys
import textwrap
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from dgraph_tpu.engine import Engine
from dgraph_tpu.models.synthetic import powerlaw_rel
from dgraph_tpu.parallel.mesh import (
    make_mesh, replicated, hop_input, reshard_count, reshard_guard)
from dgraph_tpu.store.schema import parse_schema
from dgraph_tpu.store.store import StoreBuilder
from dgraph_tpu.utils import costprior, costprofile
from dgraph_tpu.utils.metrics import METRICS

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _clean():
    costprior.reset()
    costprofile.reset()
    yield
    costprior.reset()
    costprofile.reset()


def _powerlaw_store(n=400, deg=4.0, seed=7):
    rel = powerlaw_rel(n, deg, seed=seed)
    b = StoreBuilder(parse_schema(
        "friend: [uid] @reverse .\nname: string @index(exact) ."))
    for s in range(rel.indptr.shape[0] - 1):
        b.add_value(s + 1, "name", f"p{s}")
        for o in rel.row(s):
            b.add_edge(s + 1, "friend", int(o) + 1)
    return b.finalize()


# ---------------------------------------------------------------------------
# the ISSUE acceptance fixture: 4 virtual devices, own subprocess

_CHILD = textwrap.dedent("""\
    import os
    # the flag must bind BEFORE jax initializes — that is the entire
    # point of running this in a subprocess (conftest's in-process
    # virtual mesh is 8-wide; the acceptance fixture pins 4)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    assert len(jax.devices()) == 4, jax.devices()

    from dgraph_tpu.engine import Engine
    from dgraph_tpu.models.synthetic import powerlaw_rel
    from dgraph_tpu.parallel.mesh import make_mesh, reshard_count
    from dgraph_tpu.store.schema import parse_schema
    from dgraph_tpu.store.store import StoreBuilder

    rel = powerlaw_rel(400, 4.0, seed=7)
    b = StoreBuilder(parse_schema(
        "friend: [uid] @reverse .\\nname: string @index(exact) ."))
    for s in range(rel.indptr.shape[0] - 1):
        b.add_value(s + 1, "name", f"p{s}")
        for o in rel.row(s):
            b.add_edge(s + 1, "friend", int(o) + 1)
    st = b.finalize()

    host = Engine(st, device_threshold=10**9)
    mesh = Engine(st, device_threshold=0, mesh=make_mesh(4))
    for q in [
        '{ q(func: uid(0x1, 0x5, 0x9)) { uid friend { uid } } }',
        '{ q(func: eq(name, "p7")) { name friend { name '
        '  friend { name } } } }',
        '{ r(func: uid(0x2)) @recurse(depth: 4) { uid friend } }',
        '{ q(func: uid(0x3)) { friend { friend { uid } } '
        '  ~friend { uid } } }',
    ]:
        a, b_ = host.query(q), mesh.query(q)
        assert a == b_, (q, a, b_)
    # the steady-path contract: across every hop of every query above,
    # no frontier re-crossed the mesh with the wrong sharding
    assert reshard_count() == 0, reshard_count()
    print("PASS 4dev bit-identity reshard-free", flush=True)
""")


def test_sharded_hops_bit_identical_on_4_virtual_devices(tmp_path):
    """ISSUE 10 acceptance: sharded multi-hop expansion is
    bit-identical to the single-device engine path on a 4-virtual-
    device fixture, reshard counter at zero — no TPU required."""
    script = tmp_path / "mesh_child.py"
    script.write_text(_CHILD)
    import os
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT)
    proc = subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True,
                          cwd=str(ROOT), env=env, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS 4dev bit-identity reshard-free" in proc.stdout


# ---------------------------------------------------------------------------
# chain hops vs the host loop (in-process mesh)

def test_chain_recurse_matches_host():
    """The reshard-free chained-hop @recurse (the mesh route) agrees with
    the host loop — and the chain's hop loop, armed with reshard_guard by
    the engine, stays copy-free."""
    st = _powerlaw_store()
    host = Engine(st, device_threshold=10**9)
    mesh = Engine(st, device_threshold=0, mesh=make_mesh(8))
    q = "{ r(func: uid(0x2, 0x7)) @recurse(depth: 3) { uid friend } }"
    want = host.query(q)

    before = reshard_count()
    routed = METRICS.get("mesh_route_total", route="chain")
    assert mesh.query(q) == want
    assert reshard_count() == before  # guard armed inside the loop too
    assert METRICS.get("mesh_route_total", route="chain") == routed + 1


def test_hop_input_counts_mismatched_sharding():
    """A committed device array entering a hop with a sharding other
    than the launch's in_specs is exactly the silent cross-device copy
    the counter exists to catch; host numpy (the chain's seed upload)
    and correctly-sharded arrays don't count."""
    import jax

    mesh = make_mesh(4)
    before = reshard_count()
    hop_input(np.arange(8, dtype=np.int32), mesh)          # host seed
    hop_input(jax.device_put(np.arange(8, dtype=np.int32),
                             replicated(mesh)), mesh)      # chained
    assert reshard_count() == before
    # a single-device array is NOT replicated over the 4-device mesh
    stray = jax.device_put(np.arange(8, dtype=np.int32))
    with pytest.raises(AssertionError, match="reshard"):
        with reshard_guard():
            hop_input(stray, mesh)
    assert reshard_count() == before + 1


# ---------------------------------------------------------------------------
# residency: gauges on placement, carry across folds

def test_sharded_residency_gauges_and_cache():
    st = _powerlaw_store()
    mesh = make_mesh(8)
    srel = st.sharded_rel("friend", False, mesh)
    assert st.sharded_rel("friend", False, mesh) is srel  # cached
    gauges = METRICS.snapshot()["gauges"]
    for s in range(8):
        assert gauges[f'mesh_shard_bytes{{shard="{s}"}}'] > 0
    assert gauges["mesh_shard_balance"] >= 1.0


def test_mesh_residency_carries_across_fold():
    """A fold that didn't touch a predicate keeps its placed shard
    stack — the serving path never re-uploads a resident tablet
    because of an unrelated fold."""
    from dgraph_tpu.engine.batch import carry_mesh_residency

    mesh = make_mesh(8)
    old = _powerlaw_store()
    srel = old.sharded_rel("friend", False, mesh)
    old.sharded_rel("friend", True, mesh)

    new = _powerlaw_store()
    before = METRICS.get("mesh_resident_carried_total")
    assert carry_mesh_residency(old, new, touched={"friend"}) == 0

    new2 = _powerlaw_store()
    assert carry_mesh_residency(old, new2, touched={"other"}) == 2
    assert METRICS.get("mesh_resident_carried_total") == before + 2
    assert new2.sharded_rel("friend", False, mesh) is srel  # no rebuild


# ---------------------------------------------------------------------------
# route selection: learned promotion + cost-prior plumbing

def test_route_promotion_follows_learned_costs():
    """Below device_threshold the mesh route is promoted only once the
    learned per-edge cost EMAs say it's cheaper than the host walk —
    and never below the dispatch-overhead floor or with priors off."""
    from dgraph_tpu.engine.execute import Executor

    st = _powerlaw_store()
    ex = Executor(st, device_threshold=512, mesh=make_mesh(8))
    assert not ex._mesh_promoted(100)        # no data yet
    costprior.PRIORS.learn_route("mesh", 5.0)
    costprior.PRIORS.learn_route("numpy", 50.0)
    assert ex._mesh_promoted(100)
    assert not ex._mesh_promoted(ex.mesh_floor - 1)   # overhead floor
    costprior.set_enabled(False)
    try:
        assert not ex._mesh_promoted(100)
    finally:
        costprior.set_enabled(True)
    # the slower-mesh case stays on the host walk
    costprior.PRIORS.learn_route("numpy", 0.1)
    for _ in range(200):  # drive the EMA well below the mesh cost
        costprior.PRIORS.learn_route("numpy", 0.1)
    assert not ex._mesh_promoted(100)
    # route EMAs persist with the model state
    m2 = costprior.CostPriorModel()
    m2.merge_state(costprior.PRIORS.to_state())
    assert m2.route_cost("mesh") == costprior.PRIORS.route_cost("mesh")


def test_mesh_expansion_records_shard_costs():
    st = _powerlaw_store()
    mesh = Engine(st, device_threshold=0, mesh=make_mesh(8))
    mesh.query('{ q(func: uid(0x1, 0x5, 0x9)) { uid friend '
               '{ uid friend { uid } } } }')
    costs = costprofile.shard_costs()
    assert costs and sum(costs.values()) > 0
    # the selector counted every expansion while a mesh was configured
    # (child uid hops ride the fused level program: route="fused")
    routed = {k: v for k, v in METRICS.snapshot()["counters"].items()
              if k.startswith("mesh_route_total")}
    assert routed and sum(routed.values()) >= 1


def test_debug_scheduler_surfaces_mesh_shard_costs():
    """ISSUE 10 satellite (the PR-9 follow-on, pinned closed):
    mesh-routed requests record shard-keyed costs, the request record
    carries the mesh_shards feature, and /debug/scheduler reflects the
    per-shard sums."""
    from dgraph_tpu.server.api import Alpha
    from dgraph_tpu.server.http import make_http_server, serve_background

    a = Alpha(device_threshold=0, mesh=make_mesh(4))
    a.alter("friend: [uid] .\nname: string @index(exact) .")
    a.mutate(set_nquads='_:a <name> "x" .\n'
                        '_:a <friend> _:b .\n'
                        '_:b <friend> _:c .\n'
                        '_:b <name> "y" .\n'
                        '_:c <name> "z" .')
    a.query('{ q(func: eq(name, "x")) { name friend '
            '{ name friend { name } } } }')
    rec = costprofile.recent(1)[0]
    assert rec["mesh_shards"] >= 1
    srv = make_http_server(a, port=0)
    serve_background(srv)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.server_address[1]}"
                f"/debug/scheduler") as r:
            doc = json.loads(r.read())
        assert doc["mesh"]["shard_cost_us"]
        assert sum(doc["mesh"]["shard_cost_us"].values()) > 0
    finally:
        srv.shutdown()
