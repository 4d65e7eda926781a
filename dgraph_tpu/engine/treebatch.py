"""Lane-batched LEVEL-TREE serving: whole nested queries as one kernel.

Reference parity: the reference serves the LDBC IC mix with per-query
goroutines descending SubGraph trees (query/query.go ProcessGraph,
worker/task.go fan-out). The TPU-native equivalent packs B structurally
compatible queries into the bit-lanes of ops/bfs.py make_ell_tree: every
uid-expansion level of every query is ONE stage of one fused XLA program
(ELL pull-gathers + bitmask-AND filters), launched once per batch.

What widens eligibility past engine/batch.py's recurse-only path
(round-4 verdict item 2):
  * multi-level expansion trees (IC2-IC12 shapes), each tree edge a stage
  * @filter on expansion levels — evaluated once per distinct constant
    per batch to a node set, packed per-lane, ANDed on device
  * filtered @recurse blocks (config-3 shape) as in-kernel scans
  * multi-block queries: `var` blocks chain stage-to-stage inside the
    kernel (uid(v) roots), host-processed blocks consume the bound vars
  * per-level ordering / pagination / facet keys — render-side, applied
    during host rebuild exactly as the per-query engine applies them

Division of labor: the device computes every level's NODE SET (the
expansion + filter work, amortised across all lanes). For a level that a
block RENDERS the host rebuilds each query's per-parent edge rows by
intersecting parents' CSR rows with the level masks (bit tests, no set
algebra), then the standard renderer emits JSON. An @recurse stage that no
block renders (a `var` block) rebuilds nothing: the launch keeps no hop
masks, and each consumer of the stage's var is handed what it reads, from
the device: a `count(uid)` over `uid(v)` its lane's population count of
the reachable set; a block rooted at `uid(v)` under a filter that index
lookups answer (the LDBC IC1 shape) the filter's candidates that the set
holds, one bit test a candidate; any other reader the lane's column of
that set by one O(n) bit test. Which it will be is decided once, when the
query is planned, and recorded in the plan (`TreePlan.var_reads`): the
launch copies `seen` back for the readers that need it, the per-query run
answers each reader as recorded, and `tree_var_reads_total{by=}` counts it
under the record's label. Either way batch results are bit-identical to
the per-query engine, asserted by tests/test_treebatch.py against the LDBC
IC goldens, by tests/test_khop.py against a plain breadth-first search and
by tests/test_ic1_knows.py against a plain IC1.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from dgraph_tpu.engine.execute import EMPTY64, Executor, LevelNode, expands
from dgraph_tpu.engine.ir import FilterNode, SubGraph
from dgraph_tpu.engine.varorder import execution_order
from dgraph_tpu.utils.metrics import METRICS

EMPTY = np.zeros(0, np.int32)

MAX_KERNEL_DEPTH = 64      # recurse stages: device buffers scale with it
MAX_STAGES = 12            # one [n+1, W] mask per stage stays resident


# ---------------------------------------------------------------------------
# plan structures

@dataclass
class StageSpec:
    attr: str
    reverse: bool
    kind: str                  # "hop" | "recurse"
    parent: tuple              # ("seed", slot) | ("stage", idx)
    filt_slot: int | None
    depth: int = 0             # recurse only
    keep_hops: bool = False    # recurse only: rendered block
    path: tuple = ()           # (block_idx,) recurse / (block_idx, i, ...) hop
    filt_shape: tuple | None = None   # structure-only filter canonical


@dataclass(frozen=True)
class VarRead:
    """How one block's read of the var of an @recurse stage that no block
    renders will be answered. `by` is the label `tree_var_reads_total`
    counts the read under: `count` (the lane's population count, no node
    named), `probe` (the root filter's candidates tested against the
    lane's bit of `seen`), `column` (the lane's members listed)."""

    block: int
    stage: int
    by: str


@dataclass
class TreePlan:
    """One kernel group: homogeneous stage structure, per-query params."""

    sig: tuple
    stages: list[StageSpec]
    n_seeds: int
    seed_blocks: list[int]                 # slot s ← block seed_blocks[s]
    filt_paths: list[tuple]                # filt slot → owning stage path
    var_reads: tuple = ()                  # VarRead, a (block, stage) each
    queries: list = field(default_factory=list)   # per-query parsed blocks

    @property
    def program_sig(self) -> tuple:
        """What the device program is built from: the seeds and the
        stages. How the host answers a reader afterwards (`var_reads`,
        the signature's last part) compiles nothing anew."""
        return self.sig[:2]


# ---------------------------------------------------------------------------
# planning

_FILTER_FUNCS_BLOCKED = {"uid", "uid_in"}


def _filter_ok(tree: FilterNode | None) -> bool:
    """Filter trees the kernel can take: evaluable to a node set before
    launch (index lookups only — Executor.filter_set), no complement
    (needs a universe), no var/uid references (bind after launch)."""
    if tree is None:
        return True
    if tree.op == "not":
        return False
    if tree.op == "leaf":
        f = tree.func
        return not (f.name in _FILTER_FUNCS_BLOCKED or f.is_val_var
                    or f.is_count)
    return all(_filter_ok(c) for c in tree.children)


def _filter_shape(tree: FilterNode | None):
    """Structure-only canonical form (constants excluded — they vary per
    query and ride per-lane filter masks)."""
    if tree is None:
        return None
    if tree.op == "leaf":
        f = tree.func
        return ("leaf", f.name, f.attr, f.lang)
    return (tree.op, tuple(_filter_shape(c) for c in tree.children))


def _root_uses_vars(sg: SubGraph) -> bool:
    from dgraph_tpu.engine.varorder import _filter_uses, _func_uses
    uses = set()
    if sg.func is not None:
        uses |= _func_uses(sg.func)
    if sg.filters is not None:
        uses |= _filter_uses(sg.filters)
    uses |= {o.attr for o in sg.orders if o.is_val_var}
    return bool(uses)


def _root_var(sg: SubGraph):
    """`func: uid(v)`, one var and no literal uid → the var name, else
    None."""
    f = sg.func
    if (f is None or f.name != "uid" or f.uids or len(f.args) != 1
            or not isinstance(f.args[0], str)):
        return None
    return f.args[0]


def _pure_chain_root(sg: SubGraph):
    """uid(v) root with no other root-level processing → the var name,
    else None. Such a block's level sets chain straight off the stage
    that defines v, inside the kernel."""
    if (sg.filters is not None or sg.orders or sg.first or sg.offset
            or sg.after):
        return None
    return _root_var(sg)


def _counted_var(sg: SubGraph):
    """`q(func: uid(v)) { count(uid) }` → v: a block that reads nothing
    of v's set but how many it holds. Else None."""
    if (not sg.children or sg.var_name or sg.recurse is not None
            or sg.msgpass is not None
            or not all(c.is_count and c.is_uid_leaf and not c.var_name
                       for c in sg.children)):
        return None
    return _pure_chain_root(sg)


def _var_reads(schema, blocks, stages) -> tuple:
    """A VarRead for every block that reads the var of an @recurse stage
    no block renders, in the order of (stage, block). Read off the parsed
    query alone:

      count   `q(func: uid(v)) { count(uid) }`
      probe   a block rooted at `uid(v)` whose @filter is evaluable before
              the launch (`_filter_ok`: index lookups, no `not`, no var or
              uid reference; a level below the root takes no other either)
      column  any other reader: no filter, a filter `_filter_ok` refuses,
              a `uid(v)` inside a filter; the stage's own block where a
              leaf binds a value var over every visited node; and any
              reader of a name that another block binds anew, where only
              the run knows which set the name stands for when it is
              read"""
    from dgraph_tpu.engine.varorder import collect_defs, collect_uses
    reads = []
    for i, s in enumerate(stages):
        if s.kind != "recurse" or s.keep_hops:
            continue
        bi = s.path[0]
        own = blocks[bi]
        edges = [c for c in own.children if expands(schema, c)]
        names = {n for n in (own.var_name, *(c.var_name for c in edges))
                 if n}
        rebound = names & set().union(
            *(collect_defs(b) for bj, b in enumerate(blocks) if bj != bi))
        for bj, sg in enumerate(blocks):
            if bj == bi:
                if any(c.var_name for c in own.children if c not in edges):
                    reads.append(VarRead(bj, i, "column"))
                continue
            if not collect_uses(sg) & names:
                continue
            if rebound:
                by = "column"
            elif _counted_var(sg) in names:
                by = "count"
            elif (_root_var(sg) in names and sg.filters is not None
                  and _filter_ok(sg.filters)):
                by = "probe"
            else:
                by = "column"
            reads.append(VarRead(bj, i, by))
    return tuple(reads)


def _bad_directives(sg: SubGraph) -> bool:
    return bool(sg.groupby or sg.cascade or sg.normalize
                or sg.is_expand_all or sg.shortest is not None)


class _Ineligible(Exception):
    pass


def plan_tree(store, blocks) -> tuple[tuple, TreePlan] | None:
    """(signature, plan skeleton) when the whole query fits the level-tree
    kernel, else None. Signature captures everything that must match for
    two queries to share a launch: the stage DAG (kinds, predicates,
    directions, parentage, filter shapes, recurse depths)."""
    try:
        return _plan_tree(store, blocks)
    except _Ineligible:
        return None


def _plan_tree(store, blocks):
    schema = store.schema
    stages: list[StageSpec] = []
    seed_blocks: list[int] = []
    filt_paths: list[tuple] = []
    var_stage: dict[str, int] = {}
    try:
        order = execution_order(blocks)   # also rejects circular deps
    except ValueError:
        raise _Ineligible from None

    def add_filter(sg: SubGraph, path) -> tuple[int | None, tuple | None]:
        if sg.filters is None:
            return None, None
        if not _filter_ok(sg.filters):
            raise _Ineligible
        filt_paths.append(path)
        return len(filt_paths) - 1, _filter_shape(sg.filters)

    def walk_children(sg: SubGraph, parent_ref, path) -> None:
        child_i = 0
        for c in sg.children:
            if not expands(schema, c):
                continue
            if (_bad_directives(c) or c.recurse is not None
                    or c.lang):
                raise _Ineligible
            cpath = (*path, child_i)
            child_i += 1
            slot, fshape = add_filter(c, cpath)
            if len(stages) >= MAX_STAGES:
                raise _Ineligible
            stages.append(StageSpec(
                attr=c.attr, reverse=c.is_reverse, kind="hop",
                parent=parent_ref, filt_slot=slot, path=cpath,
                filt_shape=fshape))
            idx = len(stages) - 1
            if c.var_name:
                var_stage[c.var_name] = idx
            walk_children(c, ("stage", idx), cpath)

    any_stage_block = False
    for bi in order:
        sg = blocks[bi]
        if _bad_directives(sg):
            raise _Ineligible
        edge_children = [c for c in sg.children if expands(schema, c)]
        if sg.recurse is not None:
            r = sg.recurse
            if (r.loop or not r.depth or r.depth > MAX_KERNEL_DEPTH
                    or len(edge_children) != 1):
                raise _Ineligible
            e = edge_children[0]
            if (e.facet_filter is not None or e.facet_keys is not None
                    or e.facet_vars is not None or e.facet_orders
                    or e.first or e.offset or e.after or e.orders
                    or e.children or e.lang):
                raise _Ineligible
            if _root_uses_vars(sg):
                raise _Ineligible
            slot, fshape = add_filter(e, (bi,))
            seed_blocks.append(bi)
            if len(stages) >= MAX_STAGES:
                raise _Ineligible
            # per-hop masks only where a block renders the tree: a `var`
            # block's consumers read the reachable set or its count
            # (_MaskedExecutor), and on a graph whose 3-hop reaches most
            # of it a walk over the visited edges is the dear way to a
            # set nobody renders
            stages.append(StageSpec(
                attr=e.attr, reverse=e.is_reverse, kind="recurse",
                parent=("seed", len(seed_blocks) - 1), filt_slot=slot,
                depth=r.depth,
                keep_hops=not sg.is_internal or sg.msgpass is not None,
                path=(bi,), filt_shape=fshape))
            if e.var_name or sg.var_name:
                # block var = reachable set = the stage's seen mask;
                # an edge-child var inside @recurse binds the same set
                for name in filter(None, (e.var_name, sg.var_name)):
                    var_stage[name] = len(stages) - 1
            any_stage_block = True
            continue
        if not edge_children:
            # host-only block (value leaves / aggregations); vars it
            # defines are bound during the per-query run
            continue
        chain_var = _pure_chain_root(sg)
        if chain_var is not None and chain_var in var_stage:
            parent_ref = ("stage", var_stage[chain_var])
        else:
            if _root_uses_vars(sg):
                raise _Ineligible
            seed_blocks.append(bi)
            parent_ref = ("seed", len(seed_blocks) - 1)
        walk_children(sg, parent_ref, (bi,))
        any_stage_block = True

    if not any_stage_block or not stages:
        raise _Ineligible
    # how a reader of an unrendered stage's var is answered is structure,
    # like a filter's shape: two queries share a launch only where their
    # records agree
    var_reads = _var_reads(schema, blocks, stages)
    sig = (len(seed_blocks), tuple(
        (s.kind, s.attr, s.reverse, s.parent, s.depth, s.keep_hops,
         s.path, s.filt_shape) for s in stages), var_reads)
    plan = TreePlan(sig=sig, stages=stages, n_seeds=len(seed_blocks),
                    seed_blocks=seed_blocks, filt_paths=filt_paths,
                    var_reads=var_reads)
    return sig, plan


class _StageIndex:
    """Maps (path) → per-query SubGraph + stage idx, resolved with the
    schema like the executor resolves children."""

    def __init__(self, store, plan: TreePlan, blocks):
        self.by_path: dict[tuple, int] = {
            s.path: i for i, s in enumerate(plan.stages)}
        self.sg_by_path: dict[tuple, SubGraph] = {}
        schema = store.schema
        for bi, sg in enumerate(blocks):
            if sg.recurse is not None:
                ecs = [c for c in sg.children if expands(schema, c)]
                if len(ecs) == 1 and (bi,) in self.by_path:
                    self.sg_by_path[(bi,)] = ecs[0]
                continue
            self._walk(schema, sg, (bi,))

    def _walk(self, schema, sg, path):
        child_i = 0
        for c in sg.children:
            if not expands(schema, c):
                continue
            cpath = (*path, child_i)
            child_i += 1
            if cpath in self.by_path:
                self.sg_by_path[cpath] = c
                self._walk(schema, c, cpath)


# ---------------------------------------------------------------------------
# execution

@dataclass
class _Launch:
    """What one launch handed back, as the per-query runs read it."""

    # all keyed by stage index
    masks: dict = field(default_factory=dict)       # hop: host [n+1, W]
    hops: dict = field(default_factory=dict)        # rendered recurse:
    #                                                 host [depth, n+1, W]
    counts: dict = field(default_factory=dict)      # recurse: int32[lanes]
    seen: dict = field(default_factory=dict)        # recurse, where the plan
    #                                                 records a probe or a
    #                                                 column reader: host
    #                                                 [n+1, W], permuted rows
    ells: dict = field(default_factory=dict)        # recurse: the relation's
    #                                                 ELL (its permutations)

    def column(self, stage_idx: int, lane: int) -> np.ndarray:
        """Lane's members of a recurse stage's reachable set, ascending
        global ranks: its column of `seen`, one O(n) bit test."""
        rows = np.nonzero(self.seen[stage_idx][:-1, lane // 32]
                          & np.uint32(1 << (lane % 32)))[0]
        return np.sort(
            self.ells[stage_idx].perm_order[rows]).astype(np.int32)

    def probe(self, stage_idx: int, lane: int,
              ranks: np.ndarray) -> np.ndarray:
        """Those of `ranks` (global) that lane's reachable set holds, in
        the order given: one bit test a rank, at its permuted row."""
        rows = self.ells[stage_idx].new_of_old[ranks]
        return ranks[(self.seen[stage_idx][rows, lane // 32]
                      & np.uint32(1 << (lane % 32))) != 0]


def run_tree_batch(store, plan: TreePlan, device_threshold: int) -> list:
    """Execute one homogeneous group as a single make_ell_tree launch and
    render each query with the standard engine over mask-constrained
    expansion. Returns one JSON dict per query (None → caller falls back
    to per-query execution). Once a request each, the phases of the
    shortest route where the work is the same: `batch.seed` (roots,
    filter sets, packing, uploads), `batch.device_wait` (dispatch until
    the host holds the recurse stages' counts), `batch.fetch` (the masks
    a rendered level needs, and an unrendered @recurse stage's `seen`
    where the plan records a reader of its members), `batch.render` (the
    per-query runs)."""
    import jax

    from dgraph_tpu.engine.batch import (_ell_for, _note_kernel_features,
                                         note_pulls)
    from dgraph_tpu.engine.outputnode import to_json
    from dgraph_tpu.utils import costprofile, deadline, tracing
    from dgraph_tpu.utils.jitcache import jit_call

    n = store.n_nodes
    B = len(plan.queries)
    words = -(-B // 32)
    W = 1 << max(words - 1, 0).bit_length() if words > 1 else 1
    lanes = 32 * W

    with tracing.span("batch.seed", phase=True, queries=B, lanes=lanes):
        # per-(attr, dir) device state, shared with the recurse batch path
        rels = {}
        for s in plan.stages:
            key = (s.attr, s.reverse)
            if key not in rels:
                g = _ell_for(store, s.attr, s.reverse)
                if g is None:             # empty relation: no kernel win
                    return None
                if g.n != n:
                    return None
                rels[key] = g

        # per-query seeds (host root evaluation) and filter node sets
        seed_lists: list[list[np.ndarray]] = [
            [] for _ in range(plan.n_seeds)]
        filt_lists: list[list[np.ndarray]] = [[] for _ in plan.filt_paths]
        idx_per_query: list[_StageIndex] = []
        root_displays: list[dict[int, np.ndarray]] = []
        # graftlint: allow(cache-registration): per-call local memo of this one batch's filter sets — it dies with the function, never holds bytes across requests
        filt_cache: dict = {}
        for q, blocks in enumerate(plan.queries):
            ex = Executor(store, device_threshold=device_threshold)
            sidx = _StageIndex(store, plan, blocks)
            idx_per_query.append(sidx)
            displays: dict[int, np.ndarray] = {}
            root_displays.append(displays)
            for slot, bi in enumerate(plan.seed_blocks):
                try:
                    display = ex.root_display(blocks[bi])
                except Exception:
                    return None
                displays[bi] = display
                seed_lists[slot].append(
                    np.unique(display).astype(np.int32))
            for slot, path in enumerate(plan.filt_paths):
                sg = sidx.sg_by_path.get(path)
                if sg is None or sg.filters is None:
                    return None
                ckey = _filter_const_key(sg.filters)
                allowed = filt_cache.get(ckey)
                if allowed is None:
                    allowed = ex.filter_set(sg.filters)
                    if allowed is None:
                        return None
                    filt_cache[ckey] = allowed
                filt_lists[slot].append(allowed)

        # budget gate before the device is committed to the fused program
        deadline.checkpoint("kernel")
        METRICS.inc("kernel_group_launches_total", family="tree")
        METRICS.inc("kernel_group_queries_total", float(B), family="tree")
        METRICS.inc("kernel_padded_lanes_total", float(lanes - B),
                    family="tree")
        _note_kernel_features("*", "tree", lanes, lanes - B,
                              len(plan.stages), B)
        fn = _tree_kernel_for(store, plan, rels, n, W)
        seeds = tuple(jax.device_put(_pack_global(n, lst, lanes))
                      for lst in seed_lists)
        filts = tuple(jax.device_put(_pack_global(n, lst, lanes))
                      for lst in filt_lists)

    recurse = [i for i, s in enumerate(plan.stages) if s.kind == "recurse"]
    with tracing.span("batch.tree_kernel", stages=len(plan.stages),
                      queries=B, lanes=lanes,
                      padded_lanes=lanes - B) as ksp:
        with tracing.span("batch.device_wait", phase=True,
                          stages=len(plan.stages)):
            with jit_call("treebatch.tree_kernel", (plan.program_sig, W, n)):
                outs = fn(seeds, filts)
            # the dispatch returns at once: the span ends when the host
            # holds what every recurse stage counted (two int32[lanes] a
            # stage, how many of its hops pushed and over how many slots),
            # or, where no stage counts, when the masks are done
            tallies = jax.device_get([outs[i][1:5] for i in recurse])
            if not recurse:
                jax.block_until_ready(outs)
        with tracing.span("batch.fetch", phase=True) as sp:
            # bit tests against these masks rebuild the edge rows of the
            # levels a block renders; an unrendered recurse stage's set
            # stays on the device unless the plan records a reader of its
            # members (a count reads the tallies alone)
            listed = {r.stage for r in plan.var_reads if r.by != "count"}
            launch = _Launch()
            for i, (s, o) in enumerate(zip(plan.stages, outs)):
                if s.kind == "recurse":
                    launch.ells[i] = rels[s.attr, s.reverse]
                    if s.keep_hops:
                        launch.hops[i] = np.asarray(o[5])
                    elif i in listed:
                        launch.seen[i] = np.asarray(o[0])
                else:
                    launch.masks[i] = np.asarray(o)
            sp.attrs["bytes"] = sum(
                m.nbytes for d in (launch.masks, launch.hops, launch.seen)
                for m in d.values())
    # launch count + dispatch gap are recorded by jit_call itself
    costprofile.add_kernel("tree", execute_us=ksp.dur_us)
    for i, (count, edges, pushed, slots) in zip(recurse, tallies):
        launch.counts[i] = count
        # the device's own counts of the stage's hops that pushed over the
        # frontier's out-edges, of the `depth` it ran, and of those edges
        METRICS.inc("kernel_hops_run_total", float(plan.stages[i].depth),
                    family="tree")
        METRICS.inc("kernel_hops_push_total", float(pushed), family="tree")
        METRICS.inc("kernel_push_slots_total", float(slots), family="tree")
        note_pulls(launch.ells[i], "tree",
                   plan.stages[i].depth - int(pushed))
        # the north star's traversed edges: a lane's sum fits int32, the
        # lanes' sum need not
        METRICS.inc("kernel_edges_traversed_total",
                    float(edges[:B].astype(np.int64).sum()), family="tree")
    for s in plan.stages:
        if s.kind == "hop":         # a hop stage is one pull
            note_pulls(rels[s.attr, s.reverse], "tree", 1)

    with tracing.span("batch.render", phase=True, queries=B):
        out_json = []
        read_by = {(r.block, r.stage): r.by for r in plan.var_reads}
        for q, blocks in enumerate(plan.queries):
            ex = _MaskedExecutor(store, q, idx_per_query[q], launch,
                                 root_displays[q], read_by,
                                 device_threshold=device_threshold)
            results: dict[int, LevelNode] = {}
            for bi in execution_order(blocks):
                ex._path = (bi,)
                results[bi] = ex.run_block(blocks[bi])
            roots = [results[bi] for bi in range(len(blocks))]
            out_json.append(to_json(ex, roots))
    return out_json


def _filter_const_key(tree: FilterNode):
    """Canonical key INCLUDING constants — identical filters across the
    batch evaluate once."""
    if tree.op == "leaf":
        f = tree.func
        return ("leaf", f.name, f.attr, f.lang, tuple(map(str, f.args)),
                tuple(f.uids))
    return (tree.op, tuple(_filter_const_key(c) for c in tree.children))


def _pack_global(n: int, rank_lists, lanes: int) -> np.ndarray:
    """Per-lane rank sets → [n+1, lanes/32] uint32 mask, global space."""
    m = np.zeros((n + 1, lanes // 32), np.uint32)
    for q, ranks in enumerate(rank_lists):
        if len(ranks):
            m[np.asarray(ranks, np.int64), q // 32] |= np.uint32(
                1 << (q % 32))
    return m


def _tree_kernel_for(store, plan: TreePlan, rels, n: int, W: int):
    """Compiled tree kernel per (snapshot, signature, lane width); device
    ELL blocks (DeviceEll, via the shared batch cache) and permutation
    vectors shared across signatures."""
    import jax

    from dgraph_tpu.engine.batch import (_cache_host, _cache_lock, _dev_for,
                                         _dev_with_out)
    from dgraph_tpu.ops.bfs import make_ell_tree, prepare_parts
    from dgraph_tpu.ops.pallas_hop import pallas_enabled

    hosts = {_cache_host(store, a, r) for a, r in rels}
    host = hosts.pop() if len(hosts) == 1 else store
    key = (plan.program_sig, W, pallas_enabled())
    # a relation that a recurse stage expands brings its out-CSR, for the
    # stage's pushed hops: the one the lane step of the same relation reads
    recursed = {(s.attr, s.reverse) for s in plan.stages
                if s.kind == "recurse"}
    devells = {rkey: (_dev_with_out if rkey in recursed else _dev_for)(
        store, *rkey)[1] for rkey in rels}
    with _cache_lock:
        fns = getattr(host, "_tree_fns", None)
        if fns is None:
            fns = host._tree_fns = {}
        if key in fns:
            return fns[key]
        devs = getattr(host, "_tree_devs", None)
        if devs is None:
            devs = host._tree_devs = {}
        prep = getattr(host, "_tree_prep", None)
        if prep is None:
            prep = host._tree_prep = {}
        for rkey, g in rels.items():
            if rkey not in devs:
                perm_in = np.concatenate(
                    [g.perm_order, [n]]).astype(np.int32)
                out_idx = np.concatenate(
                    [g.new_of_old, [n]]).astype(np.int32)
                devs[rkey] = (jax.device_put(perm_in),
                              jax.device_put(out_idx))
            # prepare_parts is width-independent on the XLA path and the
            # pallas row padding is too — one prepped copy per flag state
            pkey = (rkey, pallas_enabled())
            if pkey not in prep:
                prep[pkey] = prepare_parts(devells[rkey], W)
        stage_descs = []
        for s in plan.stages:
            rkey_s = (s.attr, s.reverse)
            perm_in, out_idx = devs[rkey_s]
            prepared = prep[(rkey_s, pallas_enabled())]
            stage_descs.append({
                "kind": s.kind, "prepared": prepared, "perm_in": perm_in,
                "out_idx": out_idx, "parent": s.parent,
                "out": devells[rkey_s].out if s.kind == "recurse" else None,
                "filt": s.filt_slot, "depth": s.depth,
                "keep_hops": s.keep_hops})
        fns[key] = make_ell_tree(stage_descs, n, W)
        return fns[key]


class _MaskedExecutor(Executor):
    """Per-query engine whose uid expansions are constrained by the
    kernel's level masks: a child level's edge list is parents' CSR rows
    bit-tested against the stage mask (filters already folded in on
    device), then ordering/pagination/vars/rendering run unchanged. The
    var of an @recurse stage that no block renders is bound to nothing
    until a consumer reads it (`_stage_vars`), and each block that reads
    it is answered as the plan recorded (`read_by`: `TreePlan.var_reads`
    by (block, stage))."""

    def __init__(self, store, lane: int, sidx: _StageIndex,
                 launch: _Launch, root_displays=None, read_by=None, **kw):
        super().__init__(store, **kw)
        self._read_by = read_by or {}
        self._counted: set[tuple] = set()    # (block, stage) reads counted
        self._lane = lane
        self._lane_word = lane // 32
        self._lane_bit = np.uint32(1 << (lane % 32))
        self._sidx = sidx
        self._launch = launch
        self._root_displays = root_displays or {}
        self._path: tuple = ()
        # var name → the unrendered recurse stage whose reachable set it
        # is, and the array a read of it bound (None until one did)
        self._stage_vars: dict[str, int] = {}
        self._stage_bound: dict[str, np.ndarray] = {}
        self._walk_vars: set[str] = set()    # bound by _masked_recurse

    def root_display(self, sg: SubGraph) -> np.ndarray:
        # seed blocks evaluated their root once pre-launch; reuse it
        if self._path and len(self._path) == 1:
            cached = self._root_displays.get(self._path[0])
            if cached is not None:
                return cached
        return super().root_display(sg)

    def _member(self, stage_idx: int, ranks: np.ndarray) -> np.ndarray:
        m = self._launch.masks[stage_idx]
        return (m[ranks, self._lane_word] & self._lane_bit) != 0

    # -- vars of unrendered recurse stages -----------------------------------
    def _stage_of(self, name: str) -> int | None:
        """The stage whose set `name` still stands for (a later block
        may have bound the name anew)."""
        stage_idx = self._stage_vars.get(name)
        if stage_idx is None or (
                name in self.uid_vars
                and self.uid_vars[name] is not self._stage_bound.get(name)):
            return None
        return stage_idx

    def _read(self, stage_idx: int) -> str:
        """How the plan recorded this block's read of the stage's var;
        counts the read under that label, once a block."""
        key = (self._path[0], stage_idx)
        by = self._read_by[key]
        if key not in self._counted:
            self._counted.add(key)
            METRICS.inc("tree_var_reads_total", by=by)
        return by

    def _root_read(self, sg: SubGraph) -> tuple:
        """(stage, by) where the block is rooted at the var of an
        unrendered stage, else (None, None)."""
        var = _root_var(sg)
        stage_idx = self._stage_of(var) if var is not None else None
        if stage_idx is None:
            return None, None
        return stage_idx, self._read(stage_idx)

    def _var_ranks(self, name: str) -> np.ndarray:
        stage_idx = self._stage_of(name)
        if stage_idx is not None:
            self._read(stage_idx)
            if name not in self.uid_vars:
                self.uid_vars[name] = self._stage_bound[name] = \
                    self._launch.column(stage_idx, self._lane)
        elif name in self._walk_vars and name in self.uid_vars:
            METRICS.inc("tree_var_reads_total", by="edge_walk")
        return super()._var_ranks(name)

    def root_ranks(self, sg: SubGraph) -> np.ndarray:
        # recorded `probe`: the filter's candidates first, the set asked
        # about them; the var stays unbound (another block may list it)
        stage_idx, by = self._root_read(sg)
        if by != "probe":
            return super().root_ranks(sg)
        from dgraph_tpu.utils import tracing
        with tracing.span("batch.probe", stage=stage_idx) as sp:
            cands = self.filter_set(sg.filters)
            sp.attrs["rows"] = len(cands)
            METRICS.inc("tree_probe_rows_total", float(len(cands)))
            return self._launch.probe(stage_idx, self._lane, cands)

    def _run_block(self, sg: SubGraph) -> LevelNode:
        # recorded `count`: the lane's count is the whole answer, and no
        # node is named
        stage_idx, by = self._root_read(sg)
        if by == "count":
            return LevelNode(
                sg=sg, nodes=EMPTY, display=EMPTY,
                leaf_sgs=list(sg.children),
                count=int(self._launch.counts[stage_idx][self._lane]))
        if sg.recurse is not None and self._path in self._sidx.by_path:
            # the launch ran this block's hops: no fused program of the
            # block's own runs them again
            return self._run_staged(sg)
        return super()._run_block(sg)

    # -- expansion override --------------------------------------------------
    def _level_edges(self, sg: SubGraph, frontier: np.ndarray):
        stage_idx = self._sidx.by_path.get(self._path)
        if stage_idx is None:
            # a level the planner did not stage (host-only block)
            return super()._level_edges(sg, frontier)
        nbrs, seg, pos = self._gather_rows(sg, frontier)
        if len(nbrs):
            keep = self._member(stage_idx, nbrs)
            nbrs, seg, pos = nbrs[keep], seg[keep], pos[keep]
        nbrs, seg, pos = self.facet_filter_edges(sg, sg.attr, nbrs, seg,
                                                 pos)
        return nbrs, seg, pos, False

    def _gather_rows(self, sg: SubGraph, frontier: np.ndarray):
        from dgraph_tpu.engine.execute import csr_rows
        rel = self.store.rel(sg.attr, sg.is_reverse)
        if not len(frontier) or rel.nnz == 0:
            return EMPTY, EMPTY, EMPTY64
        return csr_rows(rel, frontier)

    # -- tree descent with path bookkeeping ----------------------------------
    def _descend(self, parent: LevelNode) -> None:
        sg = parent.sg
        if sg.recurse is not None:
            stage_idx = self._sidx.by_path.get(self._path)
            if stage_idx is None:
                from dgraph_tpu.engine.recurse import expand_recurse
                expand_recurse(self, parent)
            elif stage_idx in self._launch.hops:
                self._masked_recurse(parent, stage_idx)
            else:
                self._unrendered_recurse(parent, stage_idx)
            return
        child_i = 0
        base_path = self._path
        for child_sg in self._concrete_children(parent):
            if self._expands(child_sg):
                self._path = (*base_path, child_i)
                child_i += 1
                parent.children.append(
                    self.run_child(child_sg, parent.nodes))
            else:
                parent.leaf_sgs.append(child_sg)
                self._record_leaf_vars(child_sg, parent)
        self._path = base_path

    def _masked_recurse(self, root: LevelNode, stage_idx: int) -> None:
        """RecurseData from the kernel's per-hop first-visit masks: hop
        h's kept edges are (parent CSR row) ∩ hops[h] — the host loop's
        loop=false semantics, filters already folded into the masks."""
        from dgraph_tpu.engine.recurse import (RecurseData,
                                               _bind_recurse_vars)

        sg = root.sg
        data = RecurseData(loop=False)
        for c in sg.children:
            (data.edge_sgs if self._expands(c)
             else data.leaf_sgs).append(c)
        esg = data.edge_sgs[0]
        rel = self.store.rel(esg.attr, esg.is_reverse)
        hops = self._launch.hops[stage_idx]
        w, bit = self._lane_word, self._lane_bit

        parents = root.nodes
        all_nodes = [root.nodes]
        p_parts, c_parts = [], []
        for h in range(hops.shape[0]):
            if not len(parents):
                break
            nbrs, seg, _pos = self._gather_rows(esg, parents)
            if not len(nbrs):
                break
            keep = (hops[h, nbrs, w] & bit) != 0
            if not keep.any():
                break
            p_parts.append(parents[seg[keep]].astype(np.int32))
            kept = nbrs[keep].astype(np.int32)
            c_parts.append(kept)
            parents = np.unique(kept)
            all_nodes.append(parents)
        if p_parts:
            data.edges[0] = (np.concatenate(p_parts),
                             np.concatenate(c_parts))
        data.all_nodes = np.unique(
            np.concatenate(all_nodes)).astype(np.int32)
        _bind_recurse_vars(self, root, data, sg)
        if sg.var_name:
            self._walk_vars.add(sg.var_name)
        root.recurse_data = data

    def _unrendered_recurse(self, root: LevelNode, stage_idx: int) -> None:
        """A `var` @recurse block: nothing of it is rendered, so nothing
        is walked. Its uid var stands for the stage's set until read
        (_var_ranks, _run_block); a value var on one of its leaves binds
        over every visited node and so reads the column now."""
        from dgraph_tpu.engine.recurse import (RecurseData,
                                               _bind_recurse_vars,
                                               split_children)
        sg = root.sg
        data = split_children(self, sg, RecurseData(loop=False))
        if any(leaf.var_name for leaf in data.leaf_sgs):
            self._read(stage_idx)
            data.all_nodes = self._launch.column(stage_idx, self._lane)
            _bind_recurse_vars(self, root, data, sg)
            if sg.var_name:
                self._stage_bound[sg.var_name] = data.all_nodes
        elif sg.var_name:
            # _run_staged bound the name to the block's roots
            self.uid_vars.pop(sg.var_name, None)
        if sg.var_name:
            self._stage_vars[sg.var_name] = stage_idx
        root.recurse_data = data
