"""graftlint acceptance: the analyzer itself, and the package under it.

Reference parity: the reference wires `go vet` + custom analyzers into
CI so invariant drift fails the build. Tier-1 here runs graftlint
(dgraph_tpu/analysis) over the WHOLE package: any unwaived finding —
a hot loop that dropped its deadline checkpoint, a bare gRPC channel, a
wall-clock deadline, a retry loop that re-spends expired budgets, an
undocumented metric, an impure jit function — fails this file. The
synthetic-fixture tests pin each rule's detection and the waiver
grammar so a refactor of the analyzer can't silently blind a rule.
"""

import ast
import functools
import json
import pathlib
import subprocess
import sys

from dgraph_tpu.analysis import Analyzer
from dgraph_tpu.analysis import run as _run
from dgraph_tpu.analysis.rules import default_rules

ROOT = pathlib.Path(__file__).resolve().parents[1]


@functools.lru_cache(maxsize=1)
def _package_run():
    return _run(ROOT)


def run(_root=None):  # one shared scan for the whole module
    return _package_run()


def scan(rel: str, source: str, readme: str = "") -> Analyzer:
    """Run the full rule set over one in-memory file."""
    a = Analyzer(rules=default_rules(), repo_root=ROOT,
                 readme_text=readme)
    a.add_source(rel, source)
    a.finish()
    return a


def rules_of(a: Analyzer, waived: bool = False) -> set[str]:
    return {f.rule for f in a.findings if f.waived == waived}


# ---------------------------------------------------------------------------
# the acceptance gate: the real package is clean

def test_package_has_zero_unwaived_findings():
    """THE build gate: `python -m dgraph_tpu.analysis` over the whole
    package must be clean. Fix the finding or waive it with
    `# graftlint: allow(<rule>): <reason>` — the failure message below
    is exactly the analyzer's own report."""
    a = run(ROOT)
    bad = a.unwaived()
    assert not bad, "graftlint findings:\n" + "\n".join(
        f.format() for f in bad)


def test_every_waiver_carries_a_reason():
    """A waiver without a reason is itself a finding (waiver-syntax),
    so this is implied by the gate above — asserted separately so the
    contract survives a refactor of the gate test."""
    a = run(ROOT)
    naked = [f for f in a.findings if f.rule == "waiver-syntax"]
    assert not naked, "\n".join(f.format() for f in naked)
    # and the waivers that do exist were actually consumed with reasons
    waived = [f for f in a.findings if f.waived]
    assert all(f.reason for f in waived)
    assert waived, "expected the package's documented waivers to exist"


def test_metric_scan_not_blind():
    """Migrated from test_metrics.py's doc-lint: the R5 name scan must
    keep seeing the registry traffic — a refactor that breaks the AST
    match would silently pass an empty README check."""
    a = run(ROOT)
    names = {m["name"] for m in a.facts["metric_sites"]}
    assert len(names) > 30, "metric scan went blind — check the rule"


def test_facts_inventory_shapes():
    """The cost-model feedstock: kernels with their static (retrace)
    axes, launch sites, span vocabulary, lock order classes."""
    a = run(ROOT)
    t = a.facts["totals"]
    assert t["kernels"] >= 10
    assert t["span_names"] >= 15
    assert t["lock_classes"] >= 15
    names = {k["name"] for k in a.facts["kernels"]}
    assert {"gather_edges", "step"} <= names  # masked_hop's gather, the lane step
    ladder = {x["name"] for x in a.facts["lock_classes"]}
    assert {"metrics.registry", "mvcc.store", "wal.write"} <= ladder


def _entry_names(tree, line: int) -> set[str]:
    """The names a caller could reach the jitted function at `line` by:
    the top-level def that holds it, and every top-level def of the same
    file that (transitively) names one of those."""
    tops = [n for n in tree.body
            if isinstance(n, (ast.FunctionDef, ast.ClassDef))]
    names = {next(n.name for n in tops if n.lineno <= line <= n.end_lineno)}
    while True:
        more = {n.name for n in tops if n.name not in names and any(
            isinstance(x, ast.Name) and x.id in names for x in ast.walk(n))}
        if not more:
            return names
        names |= more


def _names_used(tree) -> set[str]:
    """Every identifier a file's code names (imports, calls, attributes);
    docstrings and comments name nothing."""
    out = set()
    for x in ast.walk(tree):
        if isinstance(x, ast.Name):
            out.add(x.id)
        elif isinstance(x, ast.Attribute):
            out.add(x.attr)
        elif isinstance(x, ast.alias):
            out.add(x.name.rsplit(".", 1)[-1])
    return out


def test_every_kernel_has_a_caller():
    """A device program is in the tree because a served route launches
    it: every jitted function the facts inventory finds under ops/ or
    parallel/ is named by the code of some file of the package other than
    its own. PR 31 deleted the eight this named (kernels whose only
    callers were their own tests, the driver's dry run or a pre-chip
    benchmark)."""
    a = run(ROOT)
    trees = {c.rel: c.tree for c in a.contexts
             if c.rel.startswith("dgraph_tpu/")}
    used = {rel: _names_used(t) for rel, t in trees.items()}
    kernels = [k for k in a.facts["kernels"] if k["file"].startswith(
        ("dgraph_tpu/ops/", "dgraph_tpu/parallel/"))]
    assert len(kernels) >= 15
    orphans = []
    for k in kernels:
        names = _entry_names(trees[k["file"]], k["line"])
        if not any(names & u for rel, u in used.items() if rel != k["file"]):
            orphans.append(f"{k['file']}:{k['line']} {k['name']} "
                           f"(reached as {sorted(names)})")
    assert not orphans, "kernels no route launches:\n" + "\n".join(orphans)


def test_cost_record_schema_shares_the_facts_vocabulary():
    """ISSUE-8 satellite: the static facts inventory and the runtime
    cost-record schema are ONE vocabulary — facts re-export
    utils/costprofile.FIELDS verbatim, and a runtime record's keys are
    exactly that field set (the join key for the future cost model).
    Any drift between the two fails here."""
    from dgraph_tpu.utils import costprofile
    a = run(ROOT)
    facts_fields = {f["name"]: f["kind"]
                    for f in a.facts["cost_record_fields"]}
    assert facts_fields == {n: d["kind"]
                            for n, d in costprofile.FIELDS.items()}
    assert a.facts["totals"]["cost_record_fields"] \
        == len(costprofile.FIELDS)
    # a runtime record speaks exactly the shared vocabulary
    rec = costprofile.Recorder("read").finish("ok")
    assert set(rec) == set(costprofile.FIELDS)
    # the digest/feature split covers every non-meta field
    assert {d["kind"] for d in costprofile.FIELDS.values()} \
        == {"meta", "cost", "feature"}
    assert set(costprofile.DIGEST_FIELDS) | set(
        costprofile.FEATURE_FIELDS) \
        == {n for n, d in costprofile.FIELDS.items()
            if d["kind"] != "meta"}


def test_cost_prior_features_pinned_to_cost_fields():
    """ISSUE-9 satellite: the prior model's regressor vocabulary
    (utils/costprior.FEATURES) is lint-pinned to costprofile.FIELDS in
    BOTH directions, like cost_record_fields — the facts inventory
    re-exports it verbatim, every prior feature is a real `feature`
    field of the record schema, and every feature field is reachable
    by the model."""
    from dgraph_tpu.utils import costprior, costprofile
    a = run(ROOT)
    facts_feats = [f["name"] for f in a.facts["cost_prior_features"]]
    # direction 1: facts == the model's vocabulary, order included
    assert facts_feats == list(costprior.FEATURES)
    assert a.facts["totals"]["cost_prior_features"] \
        == len(costprior.FEATURES)
    # direction 2: every prior feature is a `feature`-kind record
    # field, and every feature-kind field is in the model's reach
    for f in a.facts["cost_prior_features"]:
        assert costprofile.FIELDS[f["name"]]["kind"] == "feature"
        assert f["kind"] == "feature"
    assert set(costprior.FEATURES) == set(costprofile.FEATURE_FIELDS)


def test_debug_endpoint_inventory_pinned_both_ways():
    """ISSUE-13 satellite (the cost_record_fields pattern applied to
    the debug surface): the static endpoint inventory
    (server/debug_routes.DEBUG_ENDPOINTS, re-exported by facts) and
    the RUNTIME route table (server/http._DEBUG_GET/_DEBUG_POST) are
    pinned to each other in both directions — a new debug endpoint
    that isn't inventoried, or an inventoried path no handler serves,
    fails tier-1. GET /debug renders this inventory."""
    from dgraph_tpu.server import http
    from dgraph_tpu.server.api import Alpha
    from dgraph_tpu.server.debug_routes import DEBUG_ENDPOINTS
    a = run(ROOT)
    facts_eps = {e["path"]: e["doc"] for e in a.facts["debug_endpoints"]}
    assert facts_eps == DEBUG_ENDPOINTS
    assert a.facts["totals"]["debug_endpoints"] == len(DEBUG_ENDPOINTS)
    # runtime GET table ↔ inventory, both directions; POST routes are
    # a subset (profile + flightrecorder have POST verbs)
    assert set(http._DEBUG_GET) == set(DEBUG_ENDPOINTS)
    assert set(http._DEBUG_POST) <= set(DEBUG_ENDPOINTS)
    # ISSUE-14: the fleet + flight-pull routes are inventoried (and,
    # via the set equality above, routed) — neither surface can drift
    assert "/debug/fleet" in DEBUG_ENDPOINTS
    assert "/debug/fleet/flight" in DEBUG_ENDPOINTS
    # every routed handler resolves to a real method on the runtime
    # Handler class (the dispatch table cannot point into the void)
    srv = http.make_http_server(Alpha(device_threshold=10**9))
    try:
        handler_cls = srv.RequestHandlerClass
        for table in (http._DEBUG_GET, http._DEBUG_POST):
            for route, meth in table.items():
                assert callable(getattr(handler_cls, meth, None)), \
                    (route, meth)
    finally:
        srv.server_close()


def test_cli_json_runs_clean():
    out = subprocess.run(
        [sys.executable, "-m", "dgraph_tpu.analysis", "--format=json"],
        capture_output=True, text=True, cwd=ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    doc = json.loads(out.stdout)
    assert doc["findings"] == []
    assert sum(doc["counts"]["waived"].values()) >= 10
    assert doc["facts"]["totals"]["kernels"] >= 10


# ---------------------------------------------------------------------------
# R1 hot-loop-checkpoint

R1_HOT = """\
def pump(frontier):
    while frontier:
        frontier = expand(frontier)
"""

R1_OK = """\
from dgraph_tpu.utils import deadline
def pump(frontier):
    while frontier:
        deadline.checkpoint("hop")
        frontier = expand(frontier)
"""


def test_r1_fires_on_uncheckpointed_while_in_engine():
    a = scan("dgraph_tpu/engine/fake.py", R1_HOT)
    assert "hot-loop-checkpoint" in rules_of(a)


def test_r1_satisfied_by_checkpoint_call():
    a = scan("dgraph_tpu/engine/fake.py", R1_OK)
    assert "hot-loop-checkpoint" not in rules_of(a)


def test_r1_scoped_to_hot_dirs():
    a = scan("dgraph_tpu/store/fake.py", R1_HOT)
    assert "hot-loop-checkpoint" not in rules_of(a)


def test_r1_waiver_suppresses_and_is_reported_waived():
    src = ("def pump(f):\n"
           "    # graftlint: allow(hot-loop-checkpoint): bounded by f\n"
           "    while f:\n"
           "        f = step(f)\n")
    a = scan("dgraph_tpu/ops/fake.py", src)
    assert "hot-loop-checkpoint" not in rules_of(a)
    assert "hot-loop-checkpoint" in rules_of(a, waived=True)
    (w,) = [f for f in a.findings if f.waived]
    assert w.reason == "bounded by f"


def test_reasonless_waiver_is_a_finding_and_does_not_waive():
    src = ("def pump(f):\n"
           "    while f:  # graftlint: allow(hot-loop-checkpoint)\n"
           "        f = step(f)\n")
    a = scan("dgraph_tpu/engine/fake.py", src)
    assert "hot-loop-checkpoint" in rules_of(a)       # NOT waived
    assert "waiver-syntax" in rules_of(a)             # and flagged


# ---------------------------------------------------------------------------
# R2 direct-io

def test_r2_flags_bare_channel_and_socket():
    src = ("import grpc, socket\n"
           "ch = grpc.insecure_channel('h:1')\n"
           "s = socket.create_connection(('h', 1))\n")
    a = scan("dgraph_tpu/cluster/fake.py", src)
    assert sum(1 for f in a.findings
               if f.rule == "direct-io" and not f.waived) == 2


def test_r2_allows_the_wrapper_module():
    src = "import grpc\nch = grpc.insecure_channel('h:1')\n"
    a = scan("dgraph_tpu/server/task.py", src)
    assert "direct-io" not in rules_of(a)


# ---------------------------------------------------------------------------
# R3 wall-clock

def test_r3_flags_time_time_and_waiver_reaches_multiline_stmt():
    src = ("import time\n"
           "def exp():\n"
           "    return time.time() + 60\n")
    a = scan("dgraph_tpu/server/fake.py", src)
    assert "wall-clock" in rules_of(a)
    src_waived = ("import time\n"
                  "def exp():\n"
                  "    # graftlint: allow(wall-clock): crosses procs\n"
                  "    return dict(a=1,\n"
                  "                b=time.time() + 60)\n")
    a = scan("dgraph_tpu/server/fake.py", src_waived)
    assert "wall-clock" not in rules_of(a)
    assert "wall-clock" in rules_of(a, waived=True)


def test_r3_does_not_flag_monotonic():
    src = "import time\nt0 = time.monotonic()\n"
    a = scan("dgraph_tpu/server/fake.py", src)
    assert "wall-clock" not in rules_of(a)


# ---------------------------------------------------------------------------
# R4 retry-deadline

R4_BAD = """\
import time, grpc
def call(fn):
    for i in range(3):
        try:
            return fn()
        except grpc.RpcError:
            time.sleep(0.1)
"""

R4_GOOD = """\
import time, grpc
def call(fn):
    for i in range(3):
        try:
            return fn()
        except grpc.RpcError as e:
            if e.code() == grpc.StatusCode.DEADLINE_EXCEEDED:
                raise
            time.sleep(0.1)
"""

R4_SPECIFIC = """\
import time
def call(fn):
    for i in range(3):
        try:
            return fn()
        except TxnAborted:
            time.sleep(0.1)
"""


def test_r4_flags_broad_retry_without_deadline_exclusion():
    a = scan("dgraph_tpu/cluster/fake.py", R4_BAD)
    assert "retry-deadline" in rules_of(a)


def test_r4_passes_with_deadline_exclusion():
    a = scan("dgraph_tpu/cluster/fake.py", R4_GOOD)
    assert "retry-deadline" not in rules_of(a)


def test_r4_ignores_specific_exception_retries():
    a = scan("dgraph_tpu/cluster/fake.py", R4_SPECIFIC)
    assert "retry-deadline" not in rules_of(a)


# ---------------------------------------------------------------------------
# R5 metric-docs (the migrated doc-lint)

def test_r5_requires_readme_row_with_original_message():
    src = 'METRICS.inc("brand_new_total", lane="read")\n'
    a = scan("dgraph_tpu/server/fake.py", src, readme="nothing here")
    (f,) = [x for x in a.findings if x.rule == "metric-docs"
            and x.path == "README.md"]
    # the PR-4 doc-lint's exact message shape, preserved
    assert "emitted but undocumented in README" in f.msg
    assert "brand_new_total" in f.msg


def test_r5_satisfied_by_backticked_row():
    src = 'METRICS.inc("brand_new_total")\n'
    readme = ("| `brand_new_total` | counts new things |\n"
              "| `metrics_series_dropped_total` | overflow |\n")
    a = scan("dgraph_tpu/server/fake.py", src, readme=readme)
    assert not [x for x in a.findings if x.path == "README.md"]


def test_r5_flags_dynamic_name_and_label_splat():
    src = ('name = "x_total"\n'
           'METRICS.inc(name)\n'
           'METRICS.observe("lat_us", 1.0, **labels)\n')
    a = scan("dgraph_tpu/server/fake.py", src,
             readme="`lat_us` `metrics_series_dropped_total`")
    msgs = [f.msg for f in a.findings if f.rule == "metric-docs"]
    assert any("string literal" in m for m in msgs)
    assert any("**label" in m for m in msgs)


# ---------------------------------------------------------------------------
# R6 jit-purity

def test_r6_flags_item_and_numpy_in_decorated_jit():
    src = ("import jax, numpy as np\n"
           "@jax.jit\n"
           "def k(x):\n"
           "    n = x.sum().item()\n"
           "    return np.asarray(x) + n\n")
    a = scan("dgraph_tpu/ops/fake.py", src)
    msgs = [f.msg for f in a.findings if f.rule == "jit-purity"]
    assert any(".item()" in m for m in msgs)
    assert any("np.asarray" in m for m in msgs)


def test_r6_flags_branch_on_tracer_but_not_static_or_none():
    src = ("import functools, jax\n"
           "@functools.partial(jax.jit, static_argnames=('depth',))\n"
           "def k(x, depth, mask=None):\n"
           "    if depth > 2:\n"
           "        x = x + 1\n"
           "    if mask is None:\n"
           "        mask = x\n"
           "    if x > 0:\n"
           "        return mask\n"
           "    return x\n")
    a = scan("dgraph_tpu/ops/fake.py", src)
    finds = [f for f in a.findings if f.rule == "jit-purity"]
    assert len(finds) == 1 and "'x'" in finds[0].msg


def test_r6_covers_closure_passed_to_jax_jit():
    src = ("import jax\n"
           "def build(cap):\n"
           "    def fn(x):\n"
           "        return x.tolist()\n"
           "    return jax.jit(fn)\n")
    a = scan("dgraph_tpu/parallel/fake.py", src)
    assert any(".tolist()" in f.msg for f in a.findings
               if f.rule == "jit-purity")


def test_r6_shape_and_len_branches_are_static():
    src = ("import jax\n"
           "@jax.jit\n"
           "def k(x):\n"
           "    if x.shape[0] > 4 and len(x) > 4:\n"
           "        return x + 1\n"
           "    return x\n")
    a = scan("dgraph_tpu/ops/fake.py", src)
    assert "jit-purity" not in rules_of(a)


# ---------------------------------------------------------------------------
# R13 fused-host-callback (ISSUE 15 — jit purity for the fused layer)

R13_BAD = """\
import jax
from dgraph_tpu.utils import costprofile
from dgraph_tpu.utils.metrics import METRICS
@jax.jit
def stage(x):
    costprofile.add("edges_traversed", 1)
    METRICS.inc("edges_traversed_total")
    return x + 1
"""

R13_CLOSURE = """\
import jax
from dgraph_tpu.utils.jitcache import jit_call
def build():
    def program(x):
        with jit_call("fused.program", ()):
            return x + 1
    return jax.jit(program)
"""

R13_OK = """\
import jax
from dgraph_tpu.utils import costprofile
@jax.jit
def stage(x):
    return x + 1
def launch(x):
    out = stage(x)
    costprofile.add("edges_traversed", 1)   # around, not inside
    return out
"""


def test_r13_flags_host_accounting_inside_jitted_fused_stage():
    a = scan("dgraph_tpu/engine/fused.py", R13_BAD)
    msgs = [f.msg for f in a.findings
            if f.rule == "fused-host-callback"]
    assert any("costprofile.add" in m for m in msgs)
    assert any("METRICS.inc" in m for m in msgs)


def test_r13_covers_program_closures_and_jit_call():
    a = scan("dgraph_tpu/ops/fake.py", R13_CLOSURE)
    assert any("jit_call" in f.msg for f in a.findings
               if f.rule == "fused-host-callback")


def test_r13_accounting_around_the_dispatch_is_clean():
    a = scan("dgraph_tpu/engine/fused.py", R13_OK)
    assert "fused-host-callback" not in rules_of(a)
    # outside the fused layer the rule does not apply (R6 still does)
    a = scan("dgraph_tpu/server/fake.py", R13_BAD)
    assert "fused-host-callback" not in rules_of(a)


def test_r13_waiver_with_reason():
    src = R13_BAD.replace(
        '    costprofile.add("edges_traversed", 1)\n',
        '    # graftlint: allow(fused-host-callback): trace-time '
        'build counter, once per compile is the intent\n'
        '    costprofile.add("edges_traversed", 1)\n')
    a = scan("dgraph_tpu/engine/fused.py", src)
    assert any("fused-host-callback" in r
               for r in rules_of(a, waived=True))


def test_fused_stage_inventory_pinned_both_ways():
    """ISSUE-15 satellite (the cost_record_fields pattern applied to
    the fused program): the static stage-kind inventory
    (engine/fused.STAGE_KINDS, re-exported by facts) and the RUNTIME
    stage-emitter registry are pinned to each other in both
    directions — a stage the compiler can emit that isn't inventoried,
    or an inventoried kind no emitter serves, fails tier-1."""
    from dgraph_tpu.engine import fused
    a = run(ROOT)
    facts_kinds = {e["kind"]: e["doc"]
                   for e in a.facts["fused_stage_kinds"]}
    assert facts_kinds == fused.STAGE_KINDS
    assert a.facts["totals"]["fused_stage_kinds"] \
        == len(fused.STAGE_KINDS)
    # direction 1: every inventoried kind has a runtime emitter
    assert set(fused.STAGE_KINDS) == set(fused._STAGE_EMITTERS)
    # direction 2: every plan the compiler builds emits only
    # inventoried kinds (the _Stage constructor vocabulary)
    from dgraph_tpu.store.schema import parse_schema
    from dgraph_tpu.store.store import StoreBuilder
    b = StoreBuilder(parse_schema("knows: [uid] @reverse ."))
    b.add_edge(1, "knows", 2)
    st = b.finalize()
    from dgraph_tpu.dql.parser import parse
    blocks = parse('{ q(func: uid(0x1)) @recurse(depth: 2) '
                   '{ uid knows } }')
    plan = fused.plan_block(st, blocks[0])
    assert plan is not None
    assert {s.kind for s in plan.stages} <= set(fused.STAGE_KINDS)
    # and every kind's doc is a real one-liner, not a placeholder
    for doc in fused.STAGE_KINDS.values():
        assert len(doc) > 20


# ---------------------------------------------------------------------------
# R7 shard-map-compat

def test_r7_flags_every_direct_spelling():
    """Both historical spellings, as attribute references and as
    imports, are findings anywhere outside the shim — the exact
    regression that parked the whole parallel/ layer."""
    src = ("import jax\n"
           "fn = jax.shard_map(f, mesh=m, in_specs=s, out_specs=s)\n")
    a = scan("dgraph_tpu/parallel/fake.py", src)
    assert "shard-map-compat" in rules_of(a)

    src = "from jax.experimental.shard_map import shard_map\n"
    a = scan("dgraph_tpu/parallel/fake.py", src)
    assert "shard-map-compat" in rules_of(a)

    src = "from jax import shard_map\n"
    a = scan("dgraph_tpu/engine/fake.py", src)
    assert "shard-map-compat" in rules_of(a)

    src = "import jax.experimental.shard_map as sm\n"
    a = scan("dgraph_tpu/server/fake.py", src)
    assert "shard-map-compat" in rules_of(a)


def test_r7_allows_the_shim_and_the_resolver_import():
    # the shim itself is the one place allowed to touch the raw API
    src = ("import jax\n"
           "impl = getattr(jax, 'shard_map', None)\n"
           "from jax.experimental.shard_map import shard_map\n")
    a = scan("dgraph_tpu/utils/jaxcompat.py", src)
    assert "shard-map-compat" not in rules_of(a)
    # and everyone else importing THROUGH the shim is clean
    src = ("from dgraph_tpu.utils.jaxcompat import shard_map\n"
           "fn = shard_map(f, mesh=m, in_specs=s, out_specs=s)\n")
    a = scan("dgraph_tpu/parallel/fake.py", src)
    assert "shard-map-compat" not in rules_of(a)


def test_r7_one_finding_per_line_not_per_attribute():
    src = ("import jax\n"
           "fn = jax.experimental.shard_map.shard_map(f)\n")
    a = scan("dgraph_tpu/parallel/fake.py", src)
    finds = [f for f in a.findings if f.rule == "shard-map-compat"]
    assert len(finds) == 1


# ---------------------------------------------------------------------------
# R8 atomic-write (ISSUE 11)

R8_BAD = """\
import json
def persist(path, doc):
    with open(path, "w") as f:
        json.dump(doc, f)
"""

R8_ATOMIC = """\
import json, os
def persist(path, doc):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
"""


def test_r8_flags_bare_write_in_store_and_backup():
    for rel in ("dgraph_tpu/store/fake.py",
                "dgraph_tpu/server/backup.py"):
        a = scan(rel, R8_BAD)
        assert "atomic-write" in rules_of(a), rel
    # binary mode and mode= kwarg are caught too
    a = scan("dgraph_tpu/store/fake.py",
             'f = open("x", mode="wb")\nf.close()\n')
    assert "atomic-write" in rules_of(a)


def test_r8_allows_the_atomic_pattern_and_out_of_scope_files():
    # a function that itself fsyncs + replaces IS the helper pattern
    a = scan("dgraph_tpu/store/fake.py", R8_ATOMIC)
    assert "atomic-write" not in rules_of(a)
    # reads and appends are not writes-that-tear
    a = scan("dgraph_tpu/store/fake.py",
             'f = open("x", "ab")\ng = open("y", "r+b")\n')
    assert "atomic-write" not in rules_of(a)
    # outside the persistence layer the rule does not apply
    a = scan("dgraph_tpu/server/fake.py", R8_BAD)
    assert "atomic-write" not in rules_of(a)


def test_r8_waiver_with_reason():
    src = ("def persist(path, doc):\n"
           "    # graftlint: allow(atomic-write): scratch file, "
           "re-generated on boot\n"
           "    with open(path, \"w\") as f:\n"
           "        f.write(doc)\n")
    a = scan("dgraph_tpu/store/fake.py", src)
    assert "atomic-write" not in rules_of(a)
    assert "atomic-write" in rules_of(a, waived=True)


# ---------------------------------------------------------------------------
# R9 guarded-field (ISSUE 12 — graftrace static half)

R9_BAD = """\
from dgraph_tpu.utils import locks
class Counter:
    def __init__(self):
        self._lock = locks.make_lock("c.lock")
        self._n = 0
    def inc(self):
        with self._lock:
            self._n += 1
    def dec(self):
        with self._lock:
            self._n -= 1
    def reset(self):
        with self._lock:
            self._n = 0
    def peek(self):
        return self._n
"""


def test_r9_flags_unguarded_minority_access():
    a = scan("dgraph_tpu/server/fake.py", R9_BAD)
    finds = [f for f in a.findings if f.rule == "guarded-field"]
    assert len(finds) == 1
    assert "peek()" in finds[0].msg and "_n" in finds[0].msg


def test_r9_clean_when_every_access_locked():
    src = R9_BAD.replace(
        "    def peek(self):\n        return self._n\n",
        "    def peek(self):\n        with self._lock:\n"
        "            return self._n\n")
    a = scan("dgraph_tpu/server/fake.py", src)
    assert "guarded-field" not in rules_of(a)


def test_r9_published_pointer_below_belief_bar_not_flagged():
    """The atomic published-pointer pattern: one locked rebind, many
    unlocked reads — the lock serializes WRITERS; readers ride atomic
    reference loads (self.mvcc's real discipline). Below the 3/4
    belief bar the field is not considered lock-guarded."""
    src = ("from dgraph_tpu.utils import locks\n"
           "class Holder:\n"
           "    def __init__(self):\n"
           "        self._lock = locks.make_lock('h.lock')\n"
           "        self.snap = object()\n"
           "    def swap(self, s):\n"
           "        with self._lock:\n"
           "            self.snap = s\n"
           "    def r1(self):\n"
           "        return self.snap\n"
           "    def r2(self):\n"
           "        return self.snap\n"
           "    def r3(self):\n"
           "        return self.snap\n")
    a = scan("dgraph_tpu/server/fake.py", src)
    assert "guarded-field" not in rules_of(a)


def test_r9_init_window_and_lock_context_helpers_exempt():
    """__init__ (and methods reachable only from it) plus helpers
    called only from inside lock scopes inherit the right context."""
    src = ("from dgraph_tpu.utils import locks\n"
           "class S:\n"
           "    def __init__(self):\n"
           "        self._lock = locks.make_lock('s.lock')\n"
           "        self._d = {}\n"
           "        self._boot()\n"
           "    def _boot(self):\n"
           "        self._d['seed'] = 1\n"          # init window
           "    def put(self, k, v):\n"
           "        with self._lock:\n"
           "            self._d[k] = v\n"
           "            self._bump(k)\n"
           "    def drop(self, k):\n"
           "        with self._lock:\n"
           "            self._d.pop(k, None)\n"
           "    def _bump(self, k):\n"
           "        self._d[k] = self._d[k] + 1\n")  # caller holds it
    a = scan("dgraph_tpu/store/fake.py", src)
    assert "guarded-field" not in rules_of(a)


def test_r9_waiver_suppresses_and_disarms_runtime_inventory():
    """A reasoned R9 waiver suppresses the finding AND drops the field
    from the guarded-fields inventory — one review disarms the static
    and dynamic halves together."""
    src = R9_BAD.replace(
        "    def peek(self):\n        return self._n\n",
        "    def peek(self):\n"
        "        # graftlint: allow(guarded-field): monotonic gauge "
        "read, torn value acceptable\n"
        "        return self._n\n")
    a = scan("dgraph_tpu/server/fake.py", src)
    assert "guarded-field" not in rules_of(a)
    assert "guarded-field" in rules_of(a, waived=True)
    inv = [g for g in a.facts["guarded_fields"]
           if g["class"] == "Counter"]
    assert not any("_n" in g["fields"] for g in inv)
    # without the waiver the field IS inventoried
    a2 = scan("dgraph_tpu/server/fake.py", R9_BAD.replace(
        "    def peek(self):\n        return self._n\n", ""))
    (entry,) = [g for g in a2.facts["guarded_fields"]
                if g["class"] == "Counter"]
    assert entry["fields"] == ["_n"] and entry["lock"] == "c.lock"


# ---------------------------------------------------------------------------
# R10 guarded-escape

R10_BAD = """\
from dgraph_tpu.utils import locks
class Buf:
    def __init__(self):
        self._lock = locks.make_lock("b.lock")
        self._items = []
    def add(self, x):
        with self._lock:
            self._items.append(x)
    def worst(self):
        with self._lock:
            return self._items
"""


def test_r10_flags_escaping_container_reference():
    a = scan("dgraph_tpu/server/fake.py", R10_BAD)
    finds = [f for f in a.findings if f.rule == "guarded-escape"]
    assert len(finds) == 1 and "_items" in finds[0].msg


def test_r10_copy_or_snapshot_is_clean():
    for fix in ("return list(self._items)",
                "return self._items[0]",
                "return len(self._items)"):
        src = R10_BAD.replace("return self._items", fix)
        a = scan("dgraph_tpu/server/fake.py", src)
        assert "guarded-escape" not in rules_of(a), fix


def test_r10_scalar_return_under_lock_is_clean():
    src = ("from dgraph_tpu.utils import locks\n"
           "class C:\n"
           "    def __init__(self):\n"
           "        self._lock = locks.make_lock('c.lock')\n"
           "        self._n = 0\n"
           "    def inc(self):\n"
           "        with self._lock:\n"
           "            self._n += 1\n"
           "            return self._n\n")
    a = scan("dgraph_tpu/server/fake.py", src)
    assert "guarded-escape" not in rules_of(a)


# ---------------------------------------------------------------------------
# R11 split-critical-section

R11_BAD = """\
from dgraph_tpu.utils import locks
class Q:
    def __init__(self):
        self._lock = locks.make_lock("q.lock")
        self._level = 0
    def set_level(self, v):
        with self._lock:
            self._level = v
    def bump_if_low(self):
        with self._lock:
            low = self._level < 10
        if low:
            with self._lock:
                self._level = self._level + 1
"""


def test_r11_flags_check_then_act_across_release():
    a = scan("dgraph_tpu/server/fake.py", R11_BAD)
    finds = [f for f in a.findings
             if f.rule == "split-critical-section"]
    assert len(finds) == 1 and "_level" in finds[0].msg


def test_r11_fused_section_is_clean():
    src = ("from dgraph_tpu.utils import locks\n"
           "class Q:\n"
           "    def __init__(self):\n"
           "        self._lock = locks.make_lock('q.lock')\n"
           "        self._level = 0\n"
           "    def set_level(self, v):\n"
           "        with self._lock:\n"
           "            self._level = v\n"
           "    def bump_if_low(self):\n"
           "        with self._lock:\n"
           "            if self._level < 10:\n"
           "                self._level = self._level + 1\n")
    a = scan("dgraph_tpu/server/fake.py", src)
    assert "split-critical-section" not in rules_of(a)


# ---------------------------------------------------------------------------
# R12 untracked-lock

def test_r12_flags_direct_threading_locks_outside_locks_py():
    src = ("import threading\n"
           "from threading import Condition\n"
           "a = threading.Lock()\n"
           "b = threading.RLock()\n"
           "c = Condition()\n")
    a = scan("dgraph_tpu/server/fake.py", src)
    finds = [f for f in a.findings if f.rule == "untracked-lock"]
    assert len(finds) == 3


def test_r12_allows_locks_py_and_events():
    src = "import threading\nx = threading.Lock()\n"
    a = scan("dgraph_tpu/utils/locks.py", src)
    assert "untracked-lock" not in rules_of(a)
    # Event/local are not locks: the sanitizers have nothing to see
    src = ("import threading\n"
           "e = threading.Event()\nt = threading.local()\n")
    a = scan("dgraph_tpu/server/fake.py", src)
    assert "untracked-lock" not in rules_of(a)


# ---------------------------------------------------------------------------
# facts round-trip: static inventory ⟷ runtime guarded() registry

def test_guarded_fields_inventory_shape():
    """The lock-discipline inventory covers the real threaded
    surface: the known lock-owning classes with their guarded
    fields."""
    a = run(ROOT)
    inv = {(g["file"], g["class"]): g
           for g in a.facts["guarded_fields"]}
    assert ("dgraph_tpu/utils/metrics.py", "Registry") in inv
    assert ("dgraph_tpu/store/mvcc.py", "MVCCStore") in inv
    assert ("dgraph_tpu/server/admission.py", "_Lane") in inv
    assert a.facts["totals"]["guarded_classes"] >= 15
    assert a.facts["totals"]["guarded_fields"] >= 60
    reg = inv[("dgraph_tpu/utils/metrics.py", "Registry")]
    assert "_counters" in reg["fields"]
    assert reg["lock"] == "metrics.registry"


def test_guarded_sites_pin_inventory_both_ways():
    """Direction 1: every inventoried class carries a
    `locks.guarded(self, …)` arming call in its file. Direction 2:
    every arming call's class has inventory entries — an arming call
    on a class the inference knows nothing about is drift."""
    a = run(ROOT)
    inv_keys = {(g["file"], g["class"])
                for g in a.facts["guarded_fields"]}
    site_keys = {(s["file"], s["class"])
                 for s in a.facts["guarded_sites"]}
    missing_sites = inv_keys - site_keys
    assert not missing_sites, (
        f"inventoried classes with NO guarded() arming call: "
        f"{sorted(missing_sites)}")
    stray_sites = site_keys - inv_keys
    assert not stray_sites, (
        f"guarded() calls on classes with no inferred discipline: "
        f"{sorted(stray_sites)}")
    # and the declared lock label matches the inventory's
    by_key = {}
    for g in a.facts["guarded_fields"]:
        by_key.setdefault((g["file"], g["class"]), set()).add(g["lock"])
    for s in a.facts["guarded_sites"]:
        assert s["lock"] in by_key[(s["file"], s["class"])], s


def test_runtime_registry_matches_static_inventory():
    """The dynamic half arms EXACTLY the statically-inferred fields:
    construct real subsystem objects, then compare the runtime
    registry (what the shim actually tracks) against facts — the
    cost_record_fields pattern applied to the race sanitizer."""
    from dgraph_tpu.server.admission import AdmissionController
    from dgraph_tpu.utils import locks
    from dgraph_tpu.utils.push import TelemetryPusher

    AdmissionController(max_inflight=1, queue_depth=1)
    TelemetryPusher("http://127.0.0.1:1")
    a = run(ROOT)
    inv: dict = {}
    for g in a.facts["guarded_fields"]:
        inv.setdefault((g["file"], g["class"]), set()).update(
            g["fields"])
    reg = locks.RACES.registered
    for key in [("dgraph_tpu/server/admission.py", "_Lane"),
                ("dgraph_tpu/utils/push.py", "TelemetryPusher"),
                ("dgraph_tpu/utils/metrics.py", "Registry")]:
        assert key in reg, f"{key} never registered at runtime"
        assert set(reg[key]["fields"]) == inv[key], (
            f"{key}: runtime shim tracks {sorted(reg[key]['fields'])} "
            f"but static inference says {sorted(inv[key])}")


# ---------------------------------------------------------------------------
# R14 cache-registration (ISSUE 16)

def test_r14_flags_memo_without_governed_decision():
    src = ("from dgraph_tpu.utils.jitcache import Memo\n"
           "_plans = Memo(\"engine.plans\", capacity=64)\n")
    a = scan("dgraph_tpu/engine/fake.py", src)
    assert "cache-registration" in rules_of(a)


def test_r14_satisfied_by_explicit_governed_kwarg():
    src = ("from dgraph_tpu.utils.jitcache import Memo\n"
           "_plans = Memo(\"batch.plan\", capacity=64,\n"
           "              governed=\"batch.plan\")\n"
           "_raw = Memo(\"raw\", governed=None)\n")
    a = scan("dgraph_tpu/engine/fake.py", src)
    assert "cache-registration" not in rules_of(a)


def test_r14_flags_unregistered_dict_cache_attr():
    src = ("class Host:\n"
           "    def __init__(self):\n"
           "        self._page_cache: dict = {}\n")
    a = scan("dgraph_tpu/store/fake.py", src)
    assert "cache-registration" in rules_of(a)


def test_r14_dict_cache_passes_when_file_registers():
    src = ("from dgraph_tpu.utils import memgov\n"
           "class Host:\n"
           "    def __init__(self):\n"
           "        self._page_cache: dict = {}\n"
           "        memgov.GOVERNOR.register(\n"
           "            \"store.device\", \"device\",\n"
           "            lambda: 0, lambda: 0, owner=self)\n")
    a = scan("dgraph_tpu/store/fake.py", src)
    assert "cache-registration" not in rules_of(a)


def test_r14_waiver_suppresses_with_reason():
    src = ("class Host:\n"
           "    def __init__(self):\n"
           "        # graftlint: allow(cache-registration): bounded at 3 entries\n"
           "        self._page_cache: dict = {}\n")
    a = scan("dgraph_tpu/store/fake.py", src)
    assert "cache-registration" not in rules_of(a)
    assert "cache-registration" in rules_of(a, waived=True)


def test_r14_exempts_the_mechanism_itself():
    src = "_self_cache: dict = {}\n"
    for rel in ("dgraph_tpu/utils/memgov.py",
                "dgraph_tpu/utils/jitcache.py"):
        a = scan(rel, src)
        assert "cache-registration" not in rules_of(a)


def test_governed_cache_inventory_pinned_both_ways():
    """ISSUE-16 satellite (the cost_record_fields pattern applied to
    the memory governor): the static cache inventory
    (utils/memgov.GOVERNED_CACHES, re-exported by facts) and the
    runtime registration surface are pinned to each other in both
    directions — a cache registering under an uninventoried name is a
    hard ValueError at register(), and an inventoried name no
    `GOVERNOR.register("<name>", ...)` site ever uses fails here."""
    import ast as _ast

    from dgraph_tpu.utils import memgov
    a = run(ROOT)
    facts_caches = {e["name"]: e["doc"]
                    for e in a.facts["governed_caches"]}
    assert facts_caches == memgov.GOVERNED_CACHES
    assert a.facts["totals"]["governed_caches"] \
        == len(memgov.GOVERNED_CACHES)
    # direction 1: register() refuses names outside the inventory
    try:
        memgov.GOVERNOR.register("not.a.cache", "host",
                                 lambda: 0, lambda: 0)
    except ValueError:
        pass
    else:
        raise AssertionError(
            "register() accepted a name outside GOVERNED_CACHES")
    # direction 2: every inventoried name is referenced as a string
    # literal somewhere OUTSIDE the inventory module — registration
    # sites pass the name to GOVERNOR.register directly, through
    # Memo(governed=...), or through a file-local registration helper
    # (batch._governed_host_cache, store._register_device_caches);
    # an inventory row nothing mentions is dead vocabulary
    registered_literals = set()
    for ctx in a.contexts:
        if ctx.rel == "dgraph_tpu/utils/memgov.py":
            continue
        for node in _ast.walk(ctx.tree):
            if (isinstance(node, _ast.Constant)
                    and isinstance(node.value, str)):
                registered_literals.add(node.value)
    missing = set(memgov.GOVERNED_CACHES) - registered_literals
    assert not missing, (
        f"inventoried cache name(s) with no registration site: "
        f"{sorted(missing)}")
    # and every doc is a real one-liner, not a placeholder
    for doc in memgov.GOVERNED_CACHES.values():
        assert len(doc) > 20

# ---------------------------------------------------------------------------
# R15 slo-spec

R15_BAD_LABEL = """\
from dgraph_tpu.utils.metrics import METRICS
METRICS.inc("slo_breaches_total", slo="made_up_objective", window="fast")
"""

R15_BAD_LOOKUP = """\
from dgraph_tpu.utils.slo import DEFAULT_TARGETS
target = DEFAULT_TARGETS["typo_latency_p99_us"]
"""

R15_GOOD = """\
from dgraph_tpu.utils.metrics import METRICS
from dgraph_tpu.utils.slo import DEFAULT_TARGETS
METRICS.inc("slo_breaches_total", slo="error_rate", window="slow")
target = DEFAULT_TARGETS["read_latency_p99_us"]
"""

R15_DYNAMIC = """\
from dgraph_tpu.utils.metrics import METRICS
def breach(name):
    METRICS.inc("slo_breaches_total", slo=name, window="fast")
"""

R15_README = "`slo_breaches_total` documented here"


def test_r15_flags_uninventoried_slo_label():
    a = scan("dgraph_tpu/server/x.py", R15_BAD_LABEL,
             readme=R15_README)
    assert "slo-spec" in rules_of(a)


def test_r15_flags_uninventoried_spec_lookup():
    a = scan("dgraph_tpu/server/x.py", R15_BAD_LOOKUP,
             readme=R15_README)
    assert "slo-spec" in rules_of(a)


def test_r15_passes_inventoried_names_and_dynamic_labels():
    for src in (R15_GOOD, R15_DYNAMIC):
        a = scan("dgraph_tpu/server/x.py", src, readme=R15_README)
        assert "slo-spec" not in rules_of(a), src


def test_r15_waiver():
    src = R15_BAD_LABEL.replace(
        'window="fast")',
        'window="fast")  '
        '# graftlint: allow(slo-spec): fixture-only objective')
    a = scan("dgraph_tpu/server/x.py", src, readme=R15_README)
    assert "slo-spec" not in rules_of(a)
    assert "slo-spec" in rules_of(a, waived=True)


def test_slo_spec_inventory_pinned_both_ways():
    """ISSUE-17 satellite (the cost_record_fields pattern applied to
    the SLO engine): the static objective inventory (utils/slo.
    SLO_SPECS, re-exported by facts as `slo_specs`) and the runtime
    evaluator registry are pinned to each other in both directions —
    an evaluator for an un-inventoried name is a hard ValueError at
    registration, and an inventoried objective nothing evaluates
    fails here."""
    from dgraph_tpu.utils import slo
    a = run(ROOT)
    facts_specs = {e["name"]: e["doc"] for e in a.facts["slo_specs"]}
    assert facts_specs == slo.SLO_SPECS
    assert a.facts["totals"]["slo_specs"] == len(slo.SLO_SPECS)
    # runtime registry ↔ inventory, both directions
    assert set(slo._EVALUATORS) == set(slo.SLO_SPECS)
    # registration refuses names outside the inventory...
    try:
        slo._evaluator("not_an_objective")
    except ValueError:
        pass
    else:
        raise AssertionError(
            "_evaluator() accepted a name outside SLO_SPECS")
    # ...and so do target overrides (CLI typos must not silently keep
    # the default budget in force)
    try:
        slo.parse_spec("typo_rate=0.5")
    except ValueError:
        pass
    else:
        raise AssertionError("parse_spec() accepted an unknown SLO")
    try:
        slo.SloEngine({"typo_rate": 0.5})
    except ValueError:
        pass
    else:
        raise AssertionError("SloEngine accepted an unknown target")
    # every target has a default and every doc is a real one-liner
    assert set(slo.DEFAULT_TARGETS) == set(slo.SLO_SPECS)
    for doc in slo.SLO_SPECS.values():
        assert len(doc) > 20
