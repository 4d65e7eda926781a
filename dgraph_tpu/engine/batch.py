"""Batched @recurse serving: many concurrent queries, ONE lane kernel.

Reference parity: the reference serves a concurrent query mix with
per-query goroutines (worker/task.go); the TPU-native equivalent packs
structurally-compatible `@recurse` queries into the bit-lanes of
`ops/bfs.py ell_recurse` — one fused multi-hop program answers the whole
batch (the north-star kernel, reached from the SERVING path, not just
the bench). Ineligible queries fall back to the per-query engine.

Four kernel families ride the lanes (PR 7 widened the set, PR 46 added
the last):
  * unfiltered single-block @recurse — the dedicated recurse path here;
  * level trees / filtered recurse / var chains — engine/treebatch.py;
  * unweighted `shortest` blocks (LDBC IC13 shapes) — lane-BFS with
    host walk-back, staged through donated mask buffers (this module);
  * facet-weighted `shortest` blocks (the literal IC14, `knows
    @facets(weight)`) — a lane program that carries an integer distance a
    lane and node and relaxes it over the in-edge lists, one launch a
    batch, with a host walk-back over tight edges (_run_weighted_batch).

Batch PLANS are memoized by (schema fingerprint, query texts) riding
utils/jitcache.Memo: a repeated query template skips parsing and
`plan_batch_groups` entirely (plan_cache_{hits,misses}_total), the same
way the ELL build and the compiled kernels already amortize per
snapshot.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from dgraph_tpu.engine.execute import Executor, LevelNode
from dgraph_tpu.engine.ir import SubGraph
from dgraph_tpu.engine.outputnode import to_json
from dgraph_tpu.engine.recurse import RecurseData, _bind_recurse_vars
from dgraph_tpu.utils import costprofile, deadline, locks, memgov, tracing
from dgraph_tpu.utils.jitcache import Memo, jit_call
from dgraph_tpu.utils.metrics import METRICS

MIN_BATCH = 4            # below this the per-query engine is cheaper
# cost-packed planning (ISSUE 9): a group SMALLER than MIN_BATCH still
# earns a kernel launch when its predicted cost says the work dwarfs
# the launch overhead — grouping by predicted cost, not query count
# (utils/costprior.py; priors below the sample floor leave the count
# rule in charge)
KERNEL_WORTH_US = 5_000.0
# @recurse depth is a static arg of its jitted kernel: each distinct
# value is an XLA compile, and the scan materializes a [depth, n+1, W]
# hops buffer with no early exit. Depths past any real graph's diameter
# fall back to the per-query engine (whose host loop exits when the
# frontier empties) instead of letting a client-controlled depth size
# device buffers.
MAX_KERNEL_DEPTH = 64
# shortest lane-BFS: the most hops one kernel launch runs, which sizes
# its [SHORTEST_STAGE, n+1, W] level buffer and bounds one
# uninterruptible dispatch between deadline checkpoints. The launch
# stops itself at the hop that closes its last open lane, so a short
# path pays for its own hops only. Three rules close a lane (ops/bfs.py
# make_ell_step; the host's scan applies the same): at the seed, before
# any hop, when the target has no in-edge or the source is one or two
# edges before it; ahead, at the hop whose fresh mask reaches an
# in-neighbour of the target or an in-neighbour of one (numpaths = 1),
# so the one or two hops that would show the target itself are never
# run; exhausted, when the fresh mask holds nothing of the lane. Mask
# carries are DONATED between stages.
SHORTEST_STAGE = 8
# the look-ahead's second level is read for a lane only if the in-edges
# of its target's in-neighbours number at most this many: a hub target's
# second level is most of the graph, and a hub is near every source, so
# its lane does not set a launch's length. Chosen from counts of the
# follower cell's graph and pairs (numpy, PERF.md section 6, PR 41) so
# that no lane whose pair is 5 or more edges apart is left out: over 16
# requests of 64 pairs the 659 such lanes read 34,693 in-edges at most
# (median 1,521; the 108 six or more apart 5,824), over 64 more the
# 2,752 read 54,997; the 17 lanes of all 5,120 over the constant
# (70,393 to 377,837 in-edges) were 2 to 4 edges apart.
NEAR2_MAX_EDGES = 65536
# the weighted lane program (ops/bfs.py make_ell_relax) runs at most this
# many rounds for every edge the block's depth allows: `depth` rounds to
# reach a target and as many past it, for a cheaper path of more edges. A
# lane still open then is walked on the host (counted, reason "rounds"),
# so the cap bounds one uninterruptible dispatch and the distance type,
# never an answer.
RELAX_ROUNDS_PER_DEPTH = 2
# distances the walk-back reads from the device a gather: (row, lane)
# pairs, padded to this many, so one compiled gather serves every step
WALK_FETCH = 1 << 14


class _BatchPlan:
    def __init__(self, blocks, attr, reverse, depth):
        self.blocks = blocks          # one root SubGraph per query
        self.attr = attr
        self.reverse = reverse
        self.depth = depth


class _ShortestPlan:
    """One shortest-path kernel group: same family ("shortest": hops
    count; "weighted": one integer facet's values do), predicate,
    direction, depth cap, numpaths and weight bounds across the batch;
    per-query (blocks, shortest block index, src uid, dst uid)."""

    def __init__(self, sig, items):
        self.sig = sig
        (self.family, self.attr, self.reverse, self.depth, self.k,
         self.minw, self.maxw, self.first_visit, *facet) = sig
        # the weighted family's signature ends in its facet key
        self.weight_key = facet[0] if facet else None
        self.queries = [blocks for blocks, _bi, _s, _d in items]
        self.block_idx = [bi for _b, bi, _s, _d in items]
        self.src_uids = [s for _b, _bi, s, _d in items]
        self.dst_uids = [d for _b, _bi, _s, d in items]


def _expands(store, c: SubGraph) -> bool:
    from dgraph_tpu.engine.execute import expands
    return expands(store.schema, c)


def _eligible(store, blocks):
    """(signature, root_sg) when the query fits the lane kernel, else
    None. The signature is what must MATCH across a kernel launch."""
    if len(blocks) != 1:
        return None
    sg = blocks[0]
    r = sg.recurse
    if r is not None and r.depth and r.depth > MAX_KERNEL_DEPTH:
        return None
    if (r is None or r.loop or not r.depth or sg.shortest is not None
            or sg.filters is not None or sg.first or sg.offset
            or sg.after or sg.orders or sg.groupby or sg.cascade
            or sg.normalize or sg.var_name):
        return None
    edge_sgs = [c for c in sg.children if _expands(store, c)]
    if len(edge_sgs) != 1:
        return None
    e = edge_sgs[0]
    if (e.filters is not None or e.facet_filter is not None
            or e.facet_orders or e.facet_keys is not None
            or e.first or e.offset or e.after or e.orders
            or e.var_name):
        return None
    return (e.attr, e.is_reverse, r.depth), sg


def _eligible_shortest(store, blocks):
    """(signature, (blocks, shortest block idx, src uid, dst uid)) when
    the query's `shortest` block fits the lane-BFS kernel, else None.

    Kernel-eligible shapes: shortest over exactly one edge predicate,
    no filters on the edge, and a reverse CSR available for the host
    walk-back (path reconstruction follows in-edges). Unweighted,
    numpaths == 1 rides the first-visit BFS; numpaths > 1 / weight
    bounds ride the level-DAG variant. Facet-weighted (the literal IC14
    `knows @facets(weight)`), the block rides the weighted family, a
    signature of its own that ends in the facet key, where
    weighted_refusal finds nothing against it; else it stays on the host
    (engine/shortest.py _weighted_shortest, which counts it under
    `weighted_host_fallbacks_total{reason=}`)."""
    from dgraph_tpu.engine.shortest import MAX_PATH_DEPTH

    sidx = [i for i, b in enumerate(blocks) if b.shortest is not None]
    if len(sidx) != 1:
        return None
    bi = sidx[0]
    sg = blocks[bi]
    a = sg.shortest
    edge_sgs = [c for c in sg.children if _expands(store, c)]
    if any(c.facet_keys for c in edge_sgs):
        if weighted_refusal(store, sg) is not None:
            return None
        e = edge_sgs[0]
        sig = ("weighted", e.attr, e.is_reverse,
               a.depth or MAX_PATH_DEPTH, 1, float("-inf"), float("inf"),
               False, e.facet_keys[0][1])
        return sig, (blocks, bi, a.from_uid, a.to_uid)
    if len(edge_sgs) != 1:
        return None
    e = edge_sgs[0]
    if (e.filters is not None or e.facet_keys is not None
            or e.facet_filter is not None or e.facet_orders
            or e.children or e.first or e.offset or e.after or e.orders
            or e.var_name or e.lang):
        return None
    # other blocks run per-query on the host AFTER the kernel binds the
    # path var — but only when they don't re-enter shortest themselves
    k = max(1, a.numpaths)
    bounded = a.minweight > float("-inf") or a.maxweight < float("inf")
    max_depth = a.depth or MAX_PATH_DEPTH
    if np.isfinite(a.maxweight):
        max_depth = min(max_depth, max(int(a.maxweight), 0))
    if max_depth < 1 or max_depth > MAX_KERNEL_DEPTH:
        return None
    try:
        if store.rel(e.attr, not e.is_reverse).nnz == 0:
            return None                  # walk-back needs in-edges
        if store.rel(e.attr, e.is_reverse).nnz == 0:
            return None
    except Exception:  # noqa: BLE001 — foreign/routed tablet miss
        return None
    first_visit = k == 1 and not bounded
    sig = ("shortest", e.attr, e.is_reverse, max_depth, k,
           a.minweight, a.maxweight, first_visit)
    return sig, (blocks, bi, a.from_uid, a.to_uid)


def weighted_refusal(store, sg) -> str | None:
    """Why the weighted lane family does not take this facet-weighted
    `shortest` block, as the `reason` of
    `weighted_host_fallbacks_total`; None where it does. The family
    answers ONE cheapest path over one edge block weighted by one integer
    facet, exactly: Yen's spurs (`numpaths` > 1) and bounded counting
    stay on the host, and so does a column the integer distances cannot
    stand for (a float or a string among the values, a negative weight,
    costs past int32 within the block's rounds). The hub block has no
    min-plus product and takes edges out of the lists, so a relation
    whose ELL holds one is left to the host (no admitted configuration's
    weighted relation has one: LDBC's `knows` gets none, ops/bfs.py)."""
    from dgraph_tpu.engine.shortest import MAX_PATH_DEPTH
    from dgraph_tpu.ops.bfs import relax_dtype

    a = sg.shortest
    edge_sgs = [c for c in sg.children if _expands(store, c)]
    if len(edge_sgs) != 1:
        return "edge_blocks"
    e = edge_sgs[0]
    if len(e.facet_keys or ()) != 1:
        return "facet_keys"
    if (e.filters is not None or e.facet_filter is not None
            or e.facet_orders or e.children or e.first or e.offset
            or e.after or e.orders or e.var_name or e.lang):
        return "edge_args"
    if a.numpaths > 1:
        return "numpaths"
    if a.minweight > float("-inf") or a.maxweight < float("inf"):
        return "bounds"
    depth = a.depth or MAX_PATH_DEPTH
    if depth < 1 or depth > MAX_KERNEL_DEPTH:
        return "depth"
    try:
        if not (store.rel(e.attr, False).nnz
                and store.rel(e.attr, True).nnz):
            return "no_edges"                # walk-back needs in-edges
        pd = store.preds.get(e.attr)
        col = pd.efacets.get(e.facet_keys[0][1]) if pd else None
    except Exception:  # noqa: BLE001 — foreign/routed tablet miss
        return "no_edges"
    least, largest = 1, 1                    # an edge without the facet
    if col is not None and len(col.pos):
        span = col.int_range()
        if span is None:
            return "facet_type"
        least, largest = span[0], max(span[1], 1)
    if least < 0:
        return "negative"
    if relax_dtype(largest, RELAX_ROUNDS_PER_DEPTH * depth) is None:
        return "range"
    g = (getattr(_cache_host(store, e.attr, e.is_reverse), "_ell_cache",
                 None) or {}).get((e.attr, e.is_reverse))
    if g is not None and g.dense is not None:
        return "hub_block"
    return None


def plan_batch(store, queries_blocks):
    """Inspect parsed queries; a plan comes back only when EVERY query
    fits one lane-kernel launch (the homogeneous fast path)."""
    plans, leftover = plan_batch_groups(store, queries_blocks)
    if len(plans) == 1 and not leftover:
        return plans[0][0]
    return None


def plan_batch_groups(store, queries_blocks):
    """Split a MIXED batch into structurally-compatible kernel groups:
    ([(plan, original_indices)], leftover_indices). Groups smaller than
    MIN_BATCH fall back to per-query execution with the leftovers —
    one incompatible query no longer disables the kernel for the rest
    (reference: the per-goroutine mix, served batch-wise here).

    Four kernel families: unfiltered single-block @recurse takes the
    dedicated recurse path (`_BatchPlan`, no permutation translation);
    unweighted `shortest` blocks take the staged lane-BFS and
    facet-weighted ones the relaxing lane program (`_ShortestPlan`, one
    group a signature: a weighted block never shares a launch with an
    unweighted one); everything else — filtered recurse, nested level
    trees, multi-block var chains — tries the level-tree planner
    (engine/treebatch.py)."""
    from dgraph_tpu.engine.treebatch import TreePlan, plan_tree

    groups: dict = {}
    sp_groups: dict = {}
    tree_groups: dict = {}
    leftover: list[int] = []
    for i, blocks in enumerate(queries_blocks):
        er = _eligible(store, blocks)
        if er is not None:
            groups.setdefault(er[0], []).append((i, er[1]))
            continue
        es = _eligible_shortest(store, blocks)
        if es is not None:
            sp_groups.setdefault(es[0], []).append((i, es[1]))
            continue
        tp = plan_tree(store, blocks)
        if tp is not None:
            tree_groups.setdefault(tp[0], []).append((i, blocks, tp[1]))
            continue
        leftover.append(i)
    plans = []
    for sig, items in groups.items():
        if not _kernel_worth(f"recurse:{sig[0]}~d{sig[2]}", len(items)):
            leftover.extend(i for i, _ in items)
        else:
            plans.append((_BatchPlan([sg for _, sg in items],
                                     sig[0], sig[1], sig[2]),
                          [i for i, _ in items]))
    for sig, items in sp_groups.items():
        if not _kernel_worth(f"{sig[0]}:{sig[1]}~d{sig[3]}",
                             len(items)):
            leftover.extend(i for i, _ in items)
        else:
            plans.append((_ShortestPlan(sig, [it for _, it in items]),
                          [i for i, _ in items]))
    for sig, items in tree_groups.items():
        plan: TreePlan = items[0][2]
        if not _kernel_worth(f"tree:*~d{len(plan.stages)}",
                             len(items)):
            leftover.extend(i for i, _b, _p in items)
        else:
            plan.queries = [b for _i, b, _p in items]
            plans.append((plan, [i for i, _b, _p in items]))
    leftover.sort()
    return plans, leftover


def _kernel_worth(shape: str, n: int) -> bool:
    """Launch gate, by predicted COST rather than query count alone
    (ISSUE 9): `MIN_BATCH` keeps its historical role, but a smaller
    group whose per-shape prior says the work dwarfs the launch
    overhead (`KERNEL_WORTH_US`) still rides the kernel. Without a
    trusted prior (unseen shape, priors off) the count rule decides —
    bit-identical to the pre-prior planner."""
    if n >= MIN_BATCH:
        return True
    if n == 0:
        return False
    from dgraph_tpu.utils import costprior
    if not costprior.enabled():
        return False
    us = costprior.PRIORS.predict_shape(shape)
    return us is not None and us >= KERNEL_WORTH_US


# -- cost-packed launch ordering ---------------------------------------------

def _plan_shape(plan) -> str:
    """The shape-fingerprint component a plan's launch will record
    (matches _note_kernel_features's add_shape) — the prior lookup
    key."""
    from dgraph_tpu.engine.treebatch import TreePlan
    if isinstance(plan, _ShortestPlan):
        return f"{plan.family}:{plan.attr}~d{plan.depth}"
    if isinstance(plan, TreePlan):
        return f"tree:*~d{len(plan.stages)}"
    return f"recurse:{plan.attr}~d{plan.depth}"


def _plan_queries(plan) -> int:
    from dgraph_tpu.engine.treebatch import TreePlan
    if isinstance(plan, (_ShortestPlan, TreePlan)):
        return len(plan.queries)
    return len(plan.blocks)


def plan_cost_us(plan) -> float:
    """Predicted µs for one kernel-group launch: per-shape prior first,
    the feature least-squares fit for unseen shapes (lanes/depth/
    queries are known at plan time — the TpuGraphs-style static
    prediction), query count as the last resort proxy."""
    from dgraph_tpu.engine.treebatch import TreePlan
    from dgraph_tpu.utils import costprior
    n = _plan_queries(plan)
    us = costprior.PRIORS.predict_shape(_plan_shape(plan))
    if us is None:
        depth = (len(plan.stages) if isinstance(plan, TreePlan)
                 else plan.depth)
        us = costprior.PRIORS.predict_features(
            {"lanes": _lane_count(n), "depth": depth, "queries": n})
    if us is None:
        us = 1000.0 * n      # count proxy: every query worth ~1 ms
    return float(us)


def order_plans_by_cost(plans, enabled: bool = True):
    """Order kernel groups for launch by DESCENDING predicted cost
    (longest-processing-time-first: under a shared deadline the
    expensive group starts while the budget is freshest, and total
    makespan shrinks). Gauges the pack imbalance across launches both
    ways — query-count view vs predicted-cost view
    (`plan_pack_imbalance{stage=}`) — so the win of cost packing over
    count packing is visible per batch. Returns a new list; the cached
    plan list is never mutated."""
    plans = list(plans)
    from dgraph_tpu.utils import costprior
    if not enabled or not costprior.enabled() or len(plans) < 2:
        return plans
    counts = [float(_plan_queries(p)) for p, _ in plans]
    costs = [plan_cost_us(p) for p, _ in plans]
    for stage, vals in (("count", counts), ("predicted", costs)):
        mean = sum(vals) / len(vals)
        METRICS.set_gauge("plan_pack_imbalance",
                          max(vals) / mean if mean > 0 else 1.0,
                          stage=stage)
    order = sorted(range(len(plans)), key=lambda i: -costs[i])
    return [plans[i] for i in order]


# -- plan cache --------------------------------------------------------------

# batch plans keyed by (schema fingerprint, query texts): a repeated
# query template (dashboards, benchmark mixes) skips parse + planning
# entirely. Plans carry only parsed SubGraphs — seeds and filters are
# (re)evaluated against the CURRENT snapshot at run time, so reuse
# across stores is sound as long as the schema shape matched.
_plan_memo = Memo("batch.plan", capacity=256, governed="batch.plan")


def _schema_fingerprint(store) -> tuple:
    sch = store.schema
    fp = sch.__dict__.get("_plan_fp")
    if fp is None:
        fp = (tuple(sorted((k, repr(v))
                           for k, v in sch.predicates.items())),
              tuple(sorted((k, repr(v)) for k, v in sch.types.items())))
        sch.__dict__["_plan_fp"] = fp
    return fp


def plan_batch_groups_cached(store, dqls: list):
    """parse + plan_batch_groups with plan memoization. Returns
    ([(plan, original_indices)], leftover_indices); unparseable queries
    land in leftover (the per-query path reproduces their errors)."""
    from dgraph_tpu.dql.parser import parse

    key = (_schema_fingerprint(store), tuple(dqls))
    cached = _plan_memo.get(key)
    if cached is not None:
        METRICS.inc("plan_cache_hits_total", cache="batch")
        costprofile.note("plan_cache_hit", 1)
        return cached
    METRICS.inc("plan_cache_misses_total", cache="batch")
    costprofile.note("plan_cache_hit", 0)
    with tracing.span("batch.plan", phase=True,
                      queries=len(dqls)) as sp:
        parsed = {}
        for i, q in enumerate(dqls):
            try:
                parsed[i] = parse(q)
            except Exception:  # noqa: BLE001 — reproduced per-query
                pass
        order = sorted(parsed)
        plans, group_left = plan_batch_groups(
            store, [parsed[i] for i in order])
        plans = [(p, [order[j] for j in idxs]) for p, idxs in plans]
        leftover = sorted([order[j] for j in group_left]
                          + [i for i in range(len(dqls))
                             if i not in parsed])
    # store under the POST-planning fingerprint: planning may auto-create
    # default schema entries for unknown predicates, which would
    # otherwise shift the lookup key once and miss forever
    costprofile.add("plan_us", sp.dur_us)
    sch = store.schema
    sch.__dict__.pop("_plan_fp", None)
    _plan_memo.put((_schema_fingerprint(store), tuple(dqls)),
                   (plans, leftover), rebuild_us=sp.dur_us)
    memgov.GOVERNOR.maybe_evict("host")
    return plans, leftover


def run_batch(store, plan, device_threshold: int) -> list:
    """Execute the batch as one lane-kernel launch and render each query
    with the standard renderer (full leaf/value support). Dispatches on
    plan family: recurse lane plan here, level-tree plan in treebatch,
    shortest lane-BFS in _run_shortest_batch."""
    import jax

    from dgraph_tpu.engine.treebatch import TreePlan, run_tree_batch

    if isinstance(plan, TreePlan):
        return run_tree_batch(store, plan, device_threshold)
    if isinstance(plan, _ShortestPlan):
        run = (_run_weighted_batch if plan.weight_key is not None
               else _run_shortest_batch)
        return run(store, plan, device_threshold)

    from dgraph_tpu.ops.bfs import pack_seed_masks

    # the phases of the two other routes, under their names, once a request
    with tracing.span("batch.seed", phase=True, queries=len(plan.blocks)):
        g = _ell_for(store, plan.attr, plan.reverse)
        if g is None:
            return None

        # root seed ranks per query (host index lookups, as run_block
        # does). Lane words round UP to a power of two: padding lanes are
        # zero-seeded and free, and bucketing bounds distinct kernel
        # compiles at O(log B) instead of one multi-second XLA compile
        # per client batch size.
        ex0 = Executor(store, device_threshold=device_threshold)
        seeds = [ex0.root_ranks(sg) for sg in plan.blocks]
        B = _lane_count(len(seeds))
        seed_lists = seeds + [np.zeros(0, np.int32)] * (B - len(seeds))
        mask0 = pack_seed_masks(g, seed_lists)

    # kernel launch gate: past here the fused multi-hop program is one
    # uninterruptible XLA dispatch — the budget check happens before
    # the device is committed, not inside the kernel
    deadline.checkpoint("kernel")
    # kernel-group telemetry: membership, lane-padding waste, compiles
    METRICS.inc("kernel_group_launches_total", family="recurse")
    METRICS.inc("kernel_group_queries_total", float(len(plan.blocks)),
                family="recurse")
    METRICS.inc("kernel_padded_lanes_total", float(B - len(seeds)),
                family="recurse")
    _note_kernel_features(plan.attr, "recurse", B, B - len(seeds),
                          plan.depth, len(plan.blocks))
    costprofile.note_max("bucket_mix", len(g.parts))
    with tracing.span("batch.recurse_kernel", attr=plan.attr,
                      depth=plan.depth, queries=len(plan.blocks),
                      lanes=B, padded_lanes=B - len(seeds)) as sp:
        fn = _recurse_for(store, plan.attr, plan.reverse, mask0.shape[1])
        lkey = (plan.attr, plan.reverse, int(mask0.shape[1]),
                plan.depth, g.n)

        def _launch():
            memgov.check_alloc_fault("bfs.ell_recurse")
            with jit_call("bfs.ell_recurse", lkey):
                # the seed mask is donated to the kernel (ops/bfs.py):
                # put a fresh copy per launch (so the OOM retry has an
                # undonated buffer) and let the scan reuse it
                return fn(jax.device_put(mask0), plan.depth, True)

        with tracing.span("batch.device_wait", phase=True,
                          hops=plan.depth):
            # allocation failure: evict-to-low + one retry; a second
            # failure sticky-degrades this launch shape and OomDegraded
            # propagates — api.query_batch's per-query fallback serves
            # bit-identically
            _last, _seen, _edges, hops = memgov.oom_retry(
                "bfs.ell_recurse", lkey, _launch)
            # the dispatch returns at once: the span ends when the
            # device has run the hops
            jax.block_until_ready(hops)
        with tracing.span("batch.fetch", phase=True) as fsp:
            hops = np.asarray(hops)      # [depth, n+1, W] fresh masks
            fsp.attrs["bytes"] = hops.nbytes
    # launch count + dispatch gap are recorded by jit_call itself
    exec_us = sp.dur_us
    costprofile.add_kernel("recurse", execute_us=exec_us)
    costprofile.add_tablet_cost(plan.attr, exec_us)
    # gather-traffic model per hop (the bench's HBM model): index reads
    # + one mask row per padded slot, times the scan depth
    costprofile.add("bytes_gathered",
                    plan.depth * g.padded_edges
                    * (4 + mask0.shape[1] * 4))
    note_pulls(g, "recurse", plan.depth)
    rel = store.rel(plan.attr, plan.reverse)

    with tracing.span("batch.render", phase=True,
                      queries=len(plan.blocks)):
        root_nodes = [np.unique(s).astype(np.int32) for s in seeds]
        datas = _rebuild_recurse_batch(store, g, rel, hops, plan.blocks,
                                       root_nodes)
        out = []
        for q, sg in enumerate(plan.blocks):
            ex = Executor(store, device_threshold=device_threshold)
            node = LevelNode(sg=sg, nodes=root_nodes[q],
                             display=root_nodes[q])
            _bind_recurse_vars(ex, node, datas[q], sg)
            node.recurse_data = datas[q]
            out.append(to_json(ex, [node]))
    return out


def _lane_count(nq: int) -> int:
    words = -(-nq // 32)
    return 32 * (1 << (words - 1).bit_length() if words > 1 else 1)


def _note_kernel_features(attr: str, family: str, lanes: int,
                          padded: int, depth: int, queries: int) -> None:
    """Feed one kernel-group launch's plan features into the ambient
    cost recorder (utils/costprofile.py): the shape component joins the
    record to its digest key; lanes/padding/depth are the TpuGraphs-
    style regressors the future cost model trains on."""
    costprofile.add_shape(f"{family}:{attr}~d{depth}")
    costprofile.note_max("lanes", lanes)
    costprofile.note_max("depth", depth)
    costprofile.add("padded_lanes", padded)
    costprofile.note_max("padding_frac",
                         int(1000 * padded / max(lanes, 1)))
    costprofile.add("queries", queries)


def _rebuild_recurse_batch(store, g, rel, hops, blocks,
                           root_nodes) -> list:
    """Per-query first-visit trees from the kernel's per-hop fresh
    masks, ONE batched numpy pass per hop: all queries' parents expand
    through a single shared CSR gather, membership tests are packed-mask
    bit tests (no per-query np.isin / per-query degree slicing), and the
    next frontier falls out of the kept children — exactly the host
    loop's loop=false semantics, B× fewer numpy passes."""
    B = len(blocks)
    depth = hops.shape[0]
    datas = []
    for sg in blocks:
        d = RecurseData(loop=False)
        for c in sg.children:
            (d.edge_sgs if _expands(store, c)
             else d.leaf_sgs).append(c)
        datas.append(d)

    from dgraph_tpu.engine.execute import csr_rows
    qword = np.array([q // 32 for q in range(B)], np.int64)
    qbit = np.array([np.uint32(1 << (q % 32)) for q in range(B)],
                    np.uint32)
    parents = [rn.astype(np.int32) for rn in root_nodes]
    all_nodes = [[rn] for rn in root_nodes]
    p_parts: list[list] = [[] for _ in range(B)]
    c_parts: list[list] = [[] for _ in range(B)]
    for h in range(depth):
        live = [q for q in range(B) if len(parents[q])]
        if not live:
            break
        cat = np.concatenate([parents[q] for q in live])
        counts = np.array([len(parents[q]) for q in live])
        qid = np.repeat(np.arange(len(live)), counts)
        nbrs, seg, _pos = csr_rows(rel, cat)
        if not len(nbrs):
            break
        qe = qid[seg]                      # per-edge live-query index
        rows = g.new_of_old[nbrs]          # permuted mask rows
        lanes = np.asarray(live, np.int64)
        w = qword[lanes[qe]]
        b = qbit[lanes[qe]]
        keep = (hops[h, rows, w] & b) != 0
        kp, kc, kq = cat[seg[keep]], nbrs[keep], qe[keep]
        # edges are query-grouped (cat was), so one split serves all
        bounds = np.searchsorted(kq, np.arange(len(live) + 1))
        for i, q in enumerate(live):
            lo, hi = bounds[i], bounds[i + 1]
            if lo == hi:
                parents[q] = np.zeros(0, np.int32)
                continue
            p_parts[q].append(kp[lo:hi].astype(np.int32))
            c_parts[q].append(kc[lo:hi].astype(np.int32))
            fresh = np.unique(kc[lo:hi]).astype(np.int32)
            parents[q] = fresh
            all_nodes[q].append(fresh)
    edges_total = 0
    for q in range(B):
        if p_parts[q]:
            datas[q].edges[0] = (np.concatenate(p_parts[q]),
                                 np.concatenate(c_parts[q]))
            edges_total += len(datas[q].edges[0][0])
        datas[q].all_nodes = np.unique(
            np.concatenate(all_nodes[q])).astype(np.int32)
    if edges_total:
        costprofile.add("edges_traversed", edges_total)
    return datas


def _rebuild_recurse_data(store, g, rel, hops, q: int, sg: SubGraph,
                          root_nodes: np.ndarray,
                          depth: int) -> RecurseData:
    """Single-query form of the rebuild (kept for direct callers and
    regression tests): extract lane q into a one-word mask stack and
    run the batched pass — membership via packed-mask bit tests instead
    of the old O(edges·log) np.isin against an unsorted fresh set, CSR
    degree slicing shared inside csr_rows."""
    bit = np.uint32(1 << (q % 32))
    lane = ((hops[:depth, :, q // 32] & bit) != 0).astype(np.uint32)
    return _rebuild_recurse_batch(store, g, rel, lane[:, :, None],
                                  [sg], [root_nodes])[0]


# -- shortest lane-BFS -------------------------------------------------------

def _run_shortest_batch(store, plan: _ShortestPlan,
                        device_threshold: int) -> list:
    """Execute one shortest kernel group: seed each lane with its query's
    source, run the staged lane-BFS (first-visit masks for numpaths=1,
    full level-DAG otherwise), then rebuild each query's PathData on the
    host by walking the found levels BACKWARD over the reverse CSR —
    bit-identical to engine/shortest.py's per-query loop, asserted by
    tests/test_batch.py against LDBC IC13/IC14 shapes."""
    import jax

    levels: list[np.ndarray] = []      # [n+1, W] per hop, permuted space
    with tracing.span("batch.seed", phase=True,
                      queries=len(plan.queries)) as sp:
        # four child spans, ring only: with the phase's own CPU and
        # system time they say where a launch's preparation goes
        with tracing.span("seed.ranks"):
            g = _ell_for(store, plan.attr, plan.reverse)
            if g is None:
                return None
            rrel = store.rel(plan.attr, not plan.reverse)
            if rrel.nnz == 0:
                return None
            n = g.n
            B = len(plan.queries)

            src = store.rank_of(np.asarray(plan.src_uids, np.int64))
            dst = store.rank_of(np.asarray(plan.dst_uids, np.int64))
            lanes = _lane_count(B)
            W = lanes // 32

            # lanes needing a kernel at all: known endpoints, src != dst
            active = [q for q in range(B)
                      if src[q] >= 0 and dst[q] >= 0 and src[q] != dst[q]]
        # the look-ahead (numpaths = 1): a lane closes at the hop that
        # reaches a row one or two edges before its target, so a path of
        # d edges takes d - 2 hops (d - 1 where the second level is left
        # out), and the depth cap, which counts edges, one hop fewer
        # than it says (a lane left with one level may need it).
        # near_old[q]: the target's in-neighbours, near_rows[q] their
        # rows; near2[q]: THEIR in-neighbours' rows and, beside each,
        # the in-neighbour it leads to. Settled here and never opened: a
        # target nobody leads to, and a source that is itself in the
        # first level (a path of one edge) or in the second (of two:
        # ahead2[q] = (-1, the node between))
        hop_cap = plan.depth - 1 if plan.first_visit else plan.depth
        opened, near_old, near_rows, near2, ahead2 = active, {}, {}, {}, {}
        if plan.first_visit:
            with tracing.span("seed.near"):
                for q in active:
                    preds = rrel.row(int(dst[q]))
                    if len(preds) and not (preds == src[q]).any():
                        near_old[q] = preds
                        near_rows[q] = g.new_of_old[preds]
            METRICS.inc("kernel_lanes_closed_total",
                        float(len(active) - len(near_old)),
                        family="shortest", by="seed")
            # depth: 1 allows no hop at all: the seed settled what it can
            if hop_cap and near_old:
                with tracing.span("seed.near2") as sp2:
                    near2, ahead2, read = _second_level(g, rrel, near_old,
                                                        src)
                    sp2.attrs.update(read)
                METRICS.inc("kernel_lanes_closed_total",
                            float(len(ahead2)), family="shortest",
                            by="seed2")
                METRICS.inc("kernel_near2_edges_total",
                            float(read["edges"]))
                METRICS.inc("kernel_near2_capped_total",
                            float(read["capped"]))
            opened = [q for q in near_old if q not in ahead2] \
                if hop_cap else []
        sp.attrs.update(lanes=lanes, active=len(active),
                        opened=len(opened))
        if opened:
            with tracing.span("seed.masks"):
                mask0 = np.zeros((n + 1, W), np.uint32)
                near = np.zeros_like(mask0) if plan.first_visit else None
                for q in opened:
                    wq, bq = q // 32, np.uint32(1 << (q % 32))
                    mask0[g.new_of_old[int(src[q])], wq] |= bq
                    if plan.first_visit:
                        # one mask holds both levels: the device closes
                        # a lane at the first hop that meets either
                        near[near_rows[q], wq] |= bq
                        if q in near2:
                            near[near2[q][0], wq] |= bq
            deadline.checkpoint("kernel")
            METRICS.inc("kernel_group_launches_total", family="shortest")
            METRICS.inc("kernel_group_queries_total", float(B),
                        family="shortest")
            METRICS.inc("kernel_padded_lanes_total", float(lanes - B),
                        family="shortest")
            _note_kernel_features(plan.attr, "shortest", lanes, lanes - B,
                                  plan.depth, B)
            costprofile.note_max("bucket_mix", len(g.parts))
            with tracing.span("seed.upload"):
                step = _step_for(store, plan.attr, plan.reverse, W,
                                 plan.first_visit)
                skey = (plan.attr, plan.reverse, W, plan.first_visit, n)
                if memgov.GOVERNOR.is_degraded("bfs.ell_step", skey):
                    # sticky OOM degrade: the per-query path serves this
                    # shape
                    raise memgov.OomDegraded("bfs.ell_step", str(skey))
                unresolved = {q: None for q in opened}   # lanes still open
                frontier = jax.device_put(mask0)
                seen = jax.device_put(mask0)
                near = jax.device_put(near)
    if opened:
        with tracing.span("batch.shortest_kernel", attr=plan.attr,
                          depth=plan.depth, queries=B, lanes=lanes,
                          padded_lanes=lanes - B,
                          first_visit=plan.first_visit) as ksp:
            done = pulled = 0
            while done < hop_cap and unresolved:
                # budget gate per stage: each launch is one
                # uninterruptible dispatch of at most SHORTEST_STAGE hops
                deadline.checkpoint("kernel")
                chunk = min(SHORTEST_STAGE, hop_cap - done)
                with tracing.span("batch.device_wait", phase=True,
                                  hops=chunk) as sp:
                    try:
                        memgov.check_alloc_fault("bfs.ell_step")
                        # each staged dispatch is one launch: jit_call
                        # counts it and bills the host gap between stages
                        with jit_call("bfs.ell_step",
                                      (plan.attr, plan.reverse, W,
                                       plan.first_visit, n)):
                            (frontier, seen, hops, ran, _open, pushed,
                             slots) = step(
                                frontier, seen, near,
                                _lane_mask(unresolved, W), np.int32(chunk))
                        # the dispatch returns at once: the span ends
                        # when the device says how many hops it ran, how
                        # many of them pushed and over how many slots
                        ran, pushed, slots = map(int, jax.device_get(
                            (ran, pushed, slots)))
                    except Exception as e:
                        if not memgov.is_alloc_failure(e):
                            raise
                        # the carries are DONATED: a failed dispatch
                        # leaves no valid buffers to retry with, so this
                        # site degrades in one step — evict for the next
                        # caller, sticky-mark the shape, per-query path
                        # serves
                        memgov.GOVERNOR.note_oom("bfs.ell_step",
                                                 str(skey))
                        memgov.GOVERNOR.degrade("bfs.ell_step", skey)
                        raise memgov.OomDegraded("bfs.ell_step",
                                                 str(skey)) from e
                    sp.attrs.update(hops_run=ran, hops_push=pushed,
                                    push_slots=slots)
                with tracing.span("batch.fetch", phase=True) as sp:
                    # only the levels the device ran are data
                    for lvl in hops[:ran]:
                        lvl.copy_to_host_async()
                    lvls = [np.asarray(lvl) for lvl in hops[:ran]]
                    sp.attrs["bytes"] = sum(lvl.nbytes for lvl in lvls)
                with tracing.span("batch.scan", phase=True,
                                  hops=ran) as sp:
                    # the host's own reading of which lane closed where
                    # (the device's exit applies the same rules): `used`
                    # is the hop that closed the last open lane, so
                    # used == ran says the device's exit was exact
                    used = ran
                    closed = {"ahead": 0, "ahead2": 0, "exhausted": 0}
                    for h, lvl in enumerate(lvls):
                        levels.append(lvl)
                        alive = np.bitwise_or.reduce(lvl[:n], axis=0)
                        for q in list(unresolved):
                            wq, bq = q // 32, np.uint32(1 << (q % 32))
                            # the inner level first: a row of both is one
                            # edge from the target, not two
                            if not (alive[wq] & bq):
                                by = "exhausted"
                            elif q in near_rows and \
                                    (lvl[near_rows[q], wq] & bq).any():
                                by = "ahead"    # the walk-back finishes it
                            elif q in near2 and (hit := np.flatnonzero(
                                    lvl[near2[q][0], wq] & bq)).size:
                                # the first row hit leads to the target's
                                # first in-neighbour two edges on
                                by = "ahead2"
                                ahead2[q] = (len(levels) - 1,
                                             int(near2[q][1][hit[0]]))
                            else:
                                continue
                            unresolved.pop(q)
                            closed[by] += 1
                        if not unresolved:
                            used = h + 1
                            break
                    sp.attrs.update(hops_used=used,
                                    lanes_ahead=closed["ahead"],
                                    lanes_ahead2=closed["ahead2"])
                for by, lanes_closed in closed.items():
                    METRICS.inc("kernel_lanes_closed_total",
                                float(lanes_closed), family="shortest",
                                by=by)
                METRICS.inc("kernel_hops_run_total", float(ran),
                            family="shortest")
                METRICS.inc("kernel_hops_used_total", float(used),
                            family="shortest")
                METRICS.inc("kernel_hops_push_total", float(pushed),
                            family="shortest")
                METRICS.inc("kernel_push_slots_total", float(slots),
                            family="shortest")
                done += ran
                pulled += ran - pushed
        costprofile.add_kernel("shortest", execute_us=ksp.dur_us)
        costprofile.add_tablet_cost(plan.attr, ksp.dur_us)
        # a pushed hop gathers its frontier's out-edges, a count only the
        # device knows and small beside a pull's: only the pulls are billed
        costprofile.add("bytes_gathered",
                        pulled * g.padded_edges * (4 + W * 4))
        note_pulls(g, "shortest", pulled)

    # two passes over the queries, one span each: a span a query would
    # make the trace grow with the batch
    with tracing.span("batch.walk_back", phase=True, queries=B):
        datas = [_shortest_path_data(store, plan, g, rrel, levels,
                                     int(src[q]), int(dst[q]), q,
                                     ahead2.get(q))
                 for q in range(B)]
    return _render_shortest(store, plan, datas, device_threshold)


def _render_shortest(store, plan: _ShortestPlan, datas: list,
                     device_threshold: int):
    """Each query of a shortest group rendered around its lane's PathData:
    the shortest block binds its var, the other blocks run on the host
    after it. None where a query's blocks have no order to run in."""
    from dgraph_tpu.engine.varorder import execution_order

    B = len(plan.queries)
    with tracing.span("batch.render", phase=True, queries=B):
        out = []
        for q in range(B):
            blocks = plan.queries[q]
            data = datas[q]
            ex = Executor(store, device_threshold=device_threshold)
            results: dict[int, LevelNode] = {}
            try:
                order = execution_order(blocks)
            except ValueError:
                return None
            for bi in order:
                sg = blocks[bi]
                if bi == plan.block_idx[q]:
                    node = LevelNode(sg=sg, nodes=data.nodes,
                                     path_data=data)
                    if sg.var_name:
                        ex.uid_vars[sg.var_name] = data.nodes
                    results[bi] = node
                else:
                    results[bi] = ex.run_block(sg)
            out.append(to_json(ex, [results[i]
                                    for i in range(len(blocks))]))
    return out


def _second_level(g, rrel, near_old: dict, src: np.ndarray):
    """The look-ahead's second backward level, read from the host's
    reverse CSR by whole arrays. `near_old[q]` is the in-neighbours of
    lane q's target, ascending. A lane takes the level if those rows
    have an in-edge and NEAR2_MAX_EDGES of them at most; its entries are
    kept in the order read (in-neighbour after in-neighbour, each one's
    in-edges ascending), so the first entry that sits on a level is the
    walk-back's own choice: the first parent of the target's first
    parent. Returns (near2, ahead2, read): near2[q] = (rows, via), the
    entries' permuted rows and beside each the in-neighbour of the
    target it leads to, for the lanes to open; ahead2[q] = (-1, via) for
    a lane whose source is itself an entry (a path of two edges, never
    opened); read: the `rows` whose in-edges were read, the `edges`
    read, the lanes `capped`."""
    from dgraph_tpu.engine.execute import csr_rows

    lanes = np.fromiter(near_old, np.int64)
    sizes = np.array([len(near_old[q]) for q in near_old])
    preds = np.concatenate(list(near_old.values()))
    deg = rrel.indptr[preds + 1] - rrel.indptr[preds]
    edges = np.add.reduceat(deg, np.cumsum(sizes) - sizes)
    over = edges > NEAR2_MAX_EDGES
    take = (edges > 0) & ~over
    lanes, edges = lanes[take], edges[take]
    preds = preds[np.repeat(take, sizes)]
    nbrs, seg, _pos = csr_rows(rrel, preds)
    rows, via = g.new_of_old[nbrs], preds[seg]
    cuts = np.concatenate(([0], np.cumsum(edges))).tolist()
    near2, ahead2 = {}, {}
    for q, lo, hi in zip(lanes.tolist(), cuts, cuts[1:]):
        at_src = np.flatnonzero(nbrs[lo:hi] == src[q])
        if at_src.size:
            ahead2[q] = (-1, int(via[lo + at_src[0]]))
        else:
            near2[q] = (rows[lo:hi], via[lo:hi])
    return near2, ahead2, {"rows": len(preds), "edges": int(edges.sum()),
                           "capped": int(over.sum())}


def _lane_mask(lanes, W: int) -> np.ndarray:
    """Packed uint32[W] mask with the bit of every lane in `lanes`."""
    q = np.fromiter(lanes, np.int64)
    mask = np.zeros(W, np.uint32)
    np.bitwise_or.at(mask, q // 32,
                     np.uint32(1) << (q % 32).astype(np.uint32))
    return mask


def _level_member(g, levels, lvl: int, ranks: np.ndarray, q: int):
    """Bit-test OLD ranks against the level-`lvl` fresh/level mask."""
    m = levels[lvl]
    rows = g.new_of_old[ranks]
    return (m[rows, q // 32] & np.uint32(1 << (q % 32))) != 0


def _shortest_path_data(store, plan, g, rrel, levels, src: int,
                        dst: int, q: int, ahead2=None):
    """Rebuild one lane's PathData from the kernel levels — the exact
    paths (and enumeration ORDER) the host loop produces. `ahead2`, for
    a lane the look-ahead's second level closed: (h, p), the level it
    closed at (-1: at the seed) and the target's first parent p."""
    from dgraph_tpu.engine.shortest import PathData

    blocks = plan.queries[q]
    sg = blocks[plan.block_idx[q]]
    data = PathData(edge_sgs=[c for c in sg.children
                              if _expands(store, c)])
    if src < 0 or dst < 0:
        return data
    k = plan.k

    def parents_of(rank: int, lvl: int) -> list[int]:
        """In-neighbors of `rank` on level `lvl`, ascending — identical
        to the host loop's parent-list order (sorted frontier, one
        pred)."""
        preds = rrel.row(rank).astype(np.int64)
        if not len(preds):
            return []
        if lvl < 0:
            return [int(src)] if (preds == src).any() else []
        keep = _level_member(g, levels, lvl, preds, q)
        return [int(p) for p in preds[keep]]

    paths: list[list[tuple[int, int]]] = []
    if src == dst:
        if plan.minw <= 0 <= plan.maxw:
            paths.append([(src, -1)])
    elif plan.first_visit:
        # the look-ahead's reading of the levels: dst is h + 2 edges
        # from src when one of its in-neighbours sits on level h (-1:
        # src itself), and the level that would show dst was never run.
        # The depth cap counts edges, so h stops at depth - 2. A lane
        # closed on the second level at h is h + 3 edges long: none of
        # dst's in-neighbours sits on a level up to h, so p, the first
        # of them that has one there, is dst's first parent on the level
        # h + 1 that was never run either, and the walk goes on from p
        rev = [(dst, 0)]
        cur = dst
        if ahead2 is not None:
            found = ahead2[0] if ahead2[0] + 3 <= plan.depth else None
            cur = ahead2[1]
            rev.append((cur, 0))
        else:
            found = next((h for h in range(-1, min(len(levels),
                                                   plan.depth - 1))
                          if parents_of(dst, h)), None)
        if found is not None:
            # walk back choosing each level's FIRST parent — first-visit
            # BFS makes that exactly the host fast path's plist[0]
            for lvl in range(found, -2, -1):
                ps = parents_of(cur, lvl)
                cur = ps[0]
                rev.append((cur, 0) if lvl >= 0 else (cur, -1))
            paths.append(rev[::-1])
    else:
        # level-DAG enumeration in the host's order: per level (length
        # order), DFS over ascending parent lists, simple paths only
        def walk_back(lvl: int, rank: int, on_path: frozenset):
            for p in parents_of(rank, lvl - 1):
                if lvl == 0:
                    if p == src:
                        yield [(src, -1), (rank, 0)]
                elif p not in on_path:
                    for prefix in walk_back(lvl - 1, p, on_path | {p}):
                        yield prefix + [(rank, 0)]

        for lvl in range(len(levels)):
            deadline.checkpoint("bfs")
            hops_count = lvl + 1
            if not (plan.minw <= hops_count <= plan.maxw):
                continue
            if not _level_member(g, levels, lvl, np.array([dst]), q)[0]:
                continue
            for path in walk_back(lvl, dst, frozenset([dst, src])):
                paths.append(path)
                if len(paths) >= k:
                    break
            if len(paths) >= k:
                break
    data.paths = paths[:k]
    if data.paths:
        data.nodes = np.unique(np.array(
            [r for p in data.paths for r, _ in p], np.int32))
    return data


# -- weighted shortest: the relaxing lane program -----------------------------

def _run_weighted_batch(store, plan: _ShortestPlan, device_threshold: int):
    """Execute one weighted kernel group: every lane's source at cost 0,
    ONE launch that relaxes integer distances over the relation's in-edge
    lists until every lane's target is settled (ops/bfs.py
    make_ell_relax), then each lane's path walked back on the host over
    tight edges, reading from the device only the distances of the
    in-neighbours of the path's own nodes — byte-identical to
    engine/shortest.py's host relaxation (tests/test_weighted_lanes.py).
    None (the per-query host route answers) where the relation turns out
    to have a hub block or weights the family does not take."""
    import jax

    from dgraph_tpu.ops.bfs import relax_dtype

    B = len(plan.queries)
    dist = cost = None
    open_ = ()
    with tracing.span("batch.seed", phase=True, queries=B) as sp:
        g, _dev = _dev_for(store, plan.attr, plan.reverse)
        rrel = store.rel(plan.attr, not plan.reverse)
        if g is None or rrel.nnz == 0 or g.dense is not None:
            return None
        wts = _weights_for(store, plan.attr, plan.reverse, plan.weight_key)
        rounds_cap = RELAX_ROUNDS_PER_DEPTH * plan.depth
        # (dtype, INF) of the distances; None: costs past int32
        dist_type = wts and relax_dtype(wts.largest, rounds_cap)
        if not dist_type:
            return None
        n = g.n
        src = store.rank_of(np.asarray(plan.src_uids, np.int64))
        dst = store.rank_of(np.asarray(plan.dst_uids, np.int64))
        lanes = _lane_count(B)
        # lanes needing the program at all: known endpoints, src != dst
        active = np.zeros(lanes, bool)
        active[:B] = (src >= 0) & (dst >= 0) & (src != dst)
        rows = np.full((2, lanes), n, np.int32)
        rows[0, :B][active[:B]] = g.new_of_old[src[active[:B]]]
        rows[1, :B][active[:B]] = g.new_of_old[dst[active[:B]]]
        sp.attrs.update(lanes=lanes, active=int(active.sum()))
        if active.any():
            relax, lkey = _relax_for(store, plan, wts, lanes, rounds_cap,
                                     dist_type)
            deadline.checkpoint("kernel")
            METRICS.inc("kernel_group_launches_total", family="weighted")
            METRICS.inc("kernel_group_queries_total", float(B),
                        family="weighted")
            METRICS.inc("kernel_padded_lanes_total", float(lanes - B),
                        family="weighted")
            _note_kernel_features(plan.attr, "weighted", lanes, lanes - B,
                                  plan.depth, B)
            costprofile.note_max("bucket_mix", len(g.parts))
    if active.any():
        with tracing.span("batch.shortest_kernel", attr=plan.attr,
                          depth=plan.depth, queries=B, lanes=lanes,
                          padded_lanes=lanes - B,
                          weight=plan.weight_key) as ksp:

            def _launch():
                memgov.check_alloc_fault("bfs.ell_relax")
                with jit_call("bfs.ell_relax", lkey):
                    return relax(rows[0], rows[1], active)

            with tracing.span("batch.device_wait", phase=True) as sp:
                # nothing is donated: an allocation failure evicts and
                # retries once, then sticky-degrades this launch shape
                dist, rounds, open_, cost = memgov.oom_retry(
                    "bfs.ell_relax", lkey, _launch)
                # the dispatch returns at once: the span ends when the
                # device says how many rounds it ran
                rounds = int(jax.device_get(rounds))
                sp.attrs["rounds"] = rounds
            with tracing.span("batch.fetch", phase=True) as sp:
                open_, cost = jax.device_get((open_, cost))
                open_ = np.flatnonzero(open_[:B]).tolist()
                sp.attrs["bytes"] = int(cost.nbytes + lanes)
        METRICS.inc("kernel_relax_rounds_total", float(rounds),
                    family="weighted")
        # a pulled round reads every stored slot, padding included
        METRICS.inc("kernel_relaxed_slots_total",
                    float(rounds * g.padded_edges), family="weighted")
        costprofile.add_kernel("weighted", execute_us=ksp.dur_us)
        costprofile.add_tablet_cost(plan.attr, ksp.dur_us)
        costprofile.add("bytes_gathered", rounds * g.padded_edges * (
            4 + wts.width + lanes * dist.dtype.itemsize))
        note_pulls(g, "weighted", rounds)

    with tracing.span("batch.walk_back", phase=True, queries=B) as sp:
        datas = _weighted_path_datas(store, plan, g, rrel, wts, dist,
                                     src, dst, cost, dist_type[1], open_,
                                     device_threshold, sp)
    return _render_shortest(store, plan, datas, device_threshold)


def _tight_walk(src: int, dst: int, cost: int):
    """One lane's walk back from its target, as engine/shortest.py
    _weighted_one's `walk` over the tight DAG: the FIRST simple path, a
    node's tight parents taken in ascending rank and a parent already on
    the path passed over (zero-weight edges can close a cycle of tight
    edges). A generator: it yields (rank, that node's distance) whenever
    it needs a node's tight parents, is sent them as [(rank, distance)],
    and returns the path's ranks from source to target, or None."""
    path, on_path = [(dst, cost)], {dst}
    pending, known = [], {}
    # graftlint: allow(hot-loop-checkpoint): a turn adds or drops a path
    # node; the caller's loop of steps holds the checkpoint
    while path:
        v, dv = path[-1]
        if len(pending) < len(path):         # v has just joined the path
            if v == src:                     # no edge into src is tight
                break
            if v not in known:
                known[v] = yield v, dv
            pending.append(iter(known[v]))
        nxt = next((p for p in pending[-1] if p[0] not in on_path), None)
        if nxt is None:                      # every parent is on the path
            pending.pop()
            on_path.discard(path.pop()[0])
        else:
            path.append(nxt)
            on_path.add(nxt[0])
    return [r for r, _d in reversed(path)] or None


def _weighted_path_datas(store, plan, g, rrel, wts, dist, src, dst, cost,
                         inf: int, open_, device_threshold: int,
                         sp) -> list:
    """Every lane's PathData from the program's distances. The lanes walk
    back in step: a step gathers, in one device call, the distances of
    the in-neighbours of every walking lane's current node (their rows
    and lanes as pairs, WALK_FETCH a call) and hands each lane its tight
    parents, in-edges u → v with dist[u] + w(u, v) == dist[v]. A path of
    h edges takes h steps, and reads a few dozen in-rows a lane, never
    the column. A lane the program left open (`open_`) is walked whole on
    the host, and counted."""
    from dgraph_tpu.engine.execute import csr_rows
    from dgraph_tpu.engine.shortest import PathData, shortest_path
    from dgraph_tpu.ops.bfs import gather_pairs

    B = len(plan.queries)
    datas = []
    for q in range(B):
        sg = plan.queries[q][plan.block_idx[q]]
        datas.append(PathData(edge_sgs=[c for c in sg.children
                                        if _expands(store, c)]))
    for q in open_:
        ex = Executor(store, device_threshold=device_threshold)
        datas[q] = shortest_path(
            ex, plan.queries[q][plan.block_idx[q]], lane_refusal="rounds")
    paths = {q: [int(src[q])] for q in range(B)
             if src[q] >= 0 and src[q] == dst[q]}
    walkers, asks = {}, {}
    for q in range(B):
        if cost is not None and q not in open_ and q not in paths \
                and src[q] >= 0 and dst[q] >= 0 and cost[q] < inf:
            walkers[q] = _tight_walk(int(src[q]), int(dst[q]), int(cost[q]))
            asks[q] = next(walkers[q])
    steps = fetched = 0
    while asks:
        deadline.checkpoint("bfs")
        steps += 1
        lanes_q = list(asks)
        at_nodes = np.array([asks[q][0] for q in lanes_q], np.int64)
        nbrs, seg, pos = csr_rows(rrel, at_nodes)
        du = np.empty(len(pos), np.int64)
        rows_all = g.new_of_old[nbrs].astype(np.int32)
        lane_all = np.asarray(lanes_q, np.int32)[seg]
        for at in range(0, len(pos), WALK_FETCH):
            part = slice(at, at + WALK_FETCH)
            rows = np.full(WALK_FETCH, g.n, np.int32)
            lane = np.zeros(WALK_FETCH, np.int32)
            size = len(rows_all[part])
            rows[:size], lane[:size] = rows_all[part], lane_all[part]
            with jit_call("bfs.relax_gather",
                          (g.n, dist.shape[1], str(dist.dtype))):
                du[part] = np.asarray(gather_pairs(dist, rows, lane))[:size]
        fetched += len(pos)
        tight = du + wts.w_in[pos] == np.array(
            [asks[q][1] for q in lanes_q], np.int64)[seg]
        # the edges are grouped by lane, as `lanes_q` lists them
        cuts = np.searchsorted(seg, np.arange(len(lanes_q) + 1)).tolist()
        for q, a, b in zip(lanes_q, cuts, cuts[1:]):
            # ascending and distinct, as the host's parent lists are
            ranks, first = np.unique(nbrs[a:b][tight[a:b]],
                                     return_index=True)
            parents = list(zip(ranks.tolist(),
                               du[a:b][tight[a:b]][first].tolist()))
            try:
                asks[q] = walkers[q].send(parents)
            except StopIteration as done:
                del asks[q]
                if done.value:
                    paths[q] = done.value
    sp.attrs.update(steps=steps, distances=fetched, host_lanes=len(open_))
    for q, ranks in paths.items():
        datas[q].paths = [[(ranks[0], -1)] + [(r, 0) for r in ranks[1:]]]
        datas[q].weights = [float(cost[q]) if len(ranks) > 1 else 0.0]
        datas[q].nodes = np.unique(np.array(ranks, np.int32))
    return datas


@dataclasses.dataclass
class _RelaxWeights:
    """One relation's facet as the weighted lane program reads it: `w_in`
    the weights by IN-edge (aligned to the reverse CSR's positions, for
    the walk-back), `dev` ops/bfs.py ell_weights' blocks on the device,
    `largest` the largest weight."""

    w_in: np.ndarray
    dev: tuple
    largest: int

    @property
    def width(self) -> int:
        """The bytes a stored weight takes."""
        return int(self.w_in.dtype.itemsize)


def _facet_by_in_edge(store, attr: str, reverse: bool, wkey: str):
    """The facet `wkey` of every stored edge of the relation, by IN-edge:
    aligned to the positions of store.rel(attr, not reverse), whose rows
    list a node's in-neighbours ascending. An edge without the facet
    weighs 1 (engine/shortest.py _edge_weights). In the narrowest
    unsigned type that holds the largest. None where the column is not
    one of non-negative integers (weighted_refusal names which)."""
    rel = store.rel(attr, reverse)
    pd = store.preds.get(attr)
    col = pd.efacets.get(wkey) if pd is not None else None
    vals, largest = None, 1
    if col is not None and len(col.pos):
        vals = col.int_values()
        if vals is None or col.int_range()[0] < 0:
            return None
        largest = max(col.int_range()[1], 1)
    wdt = next((dt for dt in (np.uint8, np.uint16, np.uint32)
                if largest <= np.iinfo(dt).max), None)
    if wdt is None:
        return None
    # the facet lives on the forward posting, by forward position
    w = np.ones(pd.fwd.nnz if pd is not None and pd.fwd is not None
                else rel.nnz, wdt)
    if vals is not None:
        w[col.pos] = vals
    if reverse:
        w = w[store.rev_to_fwd_pos(attr, np.arange(rel.nnz))]
    # the relation's transpose, sources ascending within a target: the
    # order of the reverse CSR's rows, and of build_ell's lists
    return w[np.argsort(rel.indices, kind="stable")]


def _weights_for(store, attr: str, reverse: bool, wkey: str):
    """_RelaxWeights per (snapshot, pred, dir, facet key), built beside
    the ELL whose slots they are aligned to, uploaded once, carried by
    carry_kernel_caches with it and dropped with it on a write to the
    relation. None (cached too) where the column does not qualify."""
    import jax

    from dgraph_tpu.ops.bfs import ell_weights

    host = _cache_host(store, attr, reverse)
    # beside the DeviceEll in the cache `batch.ell_dev` governs, under a
    # key one longer: (pred, dir, facet key)
    key = (attr, reverse, wkey)
    cache = getattr(host, "_ell_devs", None)
    if cache is not None and key in cache:  # hot path: no lock
        return cache[key]
    g, _dev = _dev_for(store, attr, reverse)    # takes the lock itself
    with _cache_lock:
        cache = host._ell_devs
        if key not in cache:
            with tracing.span("batch.build_ell", phase=True, pred=attr,
                              reverse=reverse, part="weights"):
                w_in = _facet_by_in_edge(store, attr, reverse, wkey)
                blocks = None if w_in is None else ell_weights(
                    g, store.rel(attr, not reverse).indptr, w_in,
                    w_in.dtype)
            if blocks is None:
                cache[key] = None
            else:
                with tracing.span("batch.upload_ell", phase=True,
                                  pred=attr, reverse=reverse,
                                  part="weights") as sp:
                    dev = jax.block_until_ready(jax.device_put(blocks))
                    sp.attrs["bytes"] = memgov.estimate_nbytes(dev)
                cache[key] = _RelaxWeights(w_in, dev, int(w_in.max()))
                METRICS.set_gauge("ell_weight_bytes",
                                  float(sp.attrs["bytes"]), pred=attr,
                                  reverse=str(reverse), facet=wkey)
                METRICS.set_gauge("ell_weight_width",
                                  float(w_in.dtype.itemsize), pred=attr,
                                  reverse=str(reverse), facet=wkey)
        out = cache[key]
    memgov.GOVERNOR.maybe_evict("device")
    return out


def _relax_for(store, plan: _ShortestPlan, wts: _RelaxWeights, lanes: int,
               rounds_cap: int, dist_type: tuple):
    """(compiled weighted lane program, its launch key) per (snapshot,
    pred, dir, facet key, lanes, round cap); `dist_type` is relax_dtype's
    (dtype, INF) for the weights and the cap."""
    from dgraph_tpu.ops.bfs import make_ell_relax

    attr, reverse = plan.attr, plan.reverse
    host = _cache_host(store, attr, reverse)
    dtype, inf = dist_type
    key = ("relax", attr, reverse, plan.weight_key, lanes, rounds_cap)
    lkey = key[1:] + (str(dtype),)
    fns = getattr(host, "_ell_fns", None)
    if fns is not None and key in fns:  # hot path: no lock
        return fns[key], lkey
    g, dev = _dev_for(store, attr, reverse)
    with _cache_lock:
        fns = getattr(host, "_ell_fns", None)
        if fns is None:
            fns = host._ell_fns = {}
            _governed_host_cache(host, "_ell_fns", "batch.kernel",
                                 "host", lambda v: _KERNEL_NBYTES_EST)
        if key not in fns:
            fns[key] = make_ell_relax(dev, wts.dev, g.n, lanes, dtype, inf,
                                      rounds_cap)
        return fns[key], lkey


# -- per-snapshot kernel caches ----------------------------------------------

# one lock guards cache init/population on every snapshot: concurrent
# batch requests under ThreadingHTTPServer must not both build/upload the
# same ELL arrays (double HBM) or clobber each other's cache dicts
_cache_lock = locks.make_lock("batch.plan_cache")

# compiled recurse/step kernels are opaque closures; a nominal per-entry
# charge keeps the cache byte-governable with honest relative pressure
_KERNEL_NBYTES_EST = 64 << 10


def _governed_host_cache(host, attr_name: str, gov_name: str, kind: str,
                         sizer, cascade=None) -> None:
    """Register a per-snapshot cache dict (`host.<attr_name>`) with the
    memory governor, once per snapshot. Caller holds `_cache_lock`;
    the callbacks re-take it and close over a weakref so a dropped
    snapshot's caches fall out of the registry with it. Eviction pops
    the oldest-inserted entry (these dicts fill in first-use order, so
    oldest ≈ coldest)."""
    import weakref

    done = getattr(host, "_memgov_registered", None)
    if done is None:
        done = host._memgov_registered = set()
    if attr_name in done:
        return
    done.add(attr_name)
    ref = weakref.ref(host)

    def nbytes():
        h = ref()
        if h is None:
            return 0
        with _cache_lock:
            vals = list((getattr(h, attr_name, None) or {}).values())
        return sum(sizer(v) for v in vals)

    def evict_one():
        h = ref()
        if h is None:
            return 0
        with _cache_lock:
            d = getattr(h, attr_name, None)
            if not d:
                return 0
            k = next(iter(d))
            v = d.pop(k)
            if cascade is not None:
                cascade(h, k)   # drop dependents still pinning bytes
        return sizer(v)

    memgov.GOVERNOR.register(gov_name, kind, nbytes, evict_one,
                             owner=host)


def _drop_dependent_fns(host, dkey) -> None:
    """Evicting a device ELL must also drop the compiled kernels whose
    closures pin its arrays, or the HBM never actually frees. Caller
    holds `_cache_lock`."""
    fns = getattr(host, "_ell_fns", None)
    if not fns:
        return
    attr, reverse = dkey[:2]        # a facet's weights: (.., facet key)
    for fkey in [k for k in fns if k[1] == attr and k[2] == reverse]:
        del fns[fkey]


def _cache_host(store, attr: str, reverse: bool):
    """Where kernel caches live: the UNDERLYING immutable snapshot when
    the view's predicate data IS the snapshot's (routed/ACL wrappers are
    per-request throwaways — caching on them would rebuild/re-upload per
    batch); the view itself when the data is view-local (e.g. a faulted
    foreign tablet, whose version can change between requests)."""
    base = getattr(store, "_ell_host", store)
    if base is not store:
        pd_view = store.preds.get(attr)
        if pd_view is None or base.preds.get(attr) is not pd_view:
            return store
    return base


def _note_ell_cache(hit: bool) -> None:
    """ell_cache_hit feature bit: 1 only when EVERY ELL lookup of the
    request hit the snapshot cache — one cold build flips it to 0 for
    the whole record (a build dominates the cost)."""
    rec = costprofile.active()
    if rec is None:
        return
    if not hit:
        rec.note("ell_cache_hit", 0)
    elif "ell_cache_hit" not in rec.vals:
        rec.note("ell_cache_hit", 1)


def _ell_for(store, attr: str, reverse: bool):
    """EllGraph per (snapshot, predicate, direction) — built once,
    reused across batches until the snapshot changes (stores are
    immutable; rollup carries untouched predicates' entries forward,
    see carry_kernel_caches)."""
    from dgraph_tpu.ops.bfs import build_ell

    host = _cache_host(store, attr, reverse)
    key = (attr, reverse)
    cache = getattr(host, "_ell_cache", None)
    if cache is not None and key in cache:  # hot path: no lock
        _note_ell_cache(hit=True)
        return cache[key]
    with _cache_lock:
        cache = getattr(host, "_ell_cache", None)
        if cache is None:
            cache = host._ell_cache = {}
            _governed_host_cache(host, "_ell_cache", "batch.ell", "host",
                                 memgov.estimate_nbytes)
        if key in cache:
            _note_ell_cache(hit=True)
        else:
            rel = store.rel(attr, reverse)
            if rel.nnz == 0:
                cache[key] = None
            else:
                _note_ell_cache(hit=False)
                with tracing.span("batch.build_ell", phase=True,
                                  pred=attr, reverse=reverse) as sp:
                    g = build_ell(rel.indptr, rel.indices)
                    sp.attrs["edges"] = int(g.nnz)
                costprofile.add("build_us", sp.dur_us)
                costprofile.add_tablet_cost(attr, sp.dur_us)
                cache[key] = g
                # segment-CSR padding waste: padded slots / the real edges
                # they hold (the hub block's are in no slot)
                METRICS.set_gauge("ell_padding_ratio",
                                  g.padded_edges
                                  / max(g.nnz - g.dense_edges, 1) - 1.0,
                                  pred=attr, reverse=str(reverse))
                rows, cols = (g.dense[0].shape if g.dense is not None
                              else (0, 0))
                METRICS.set_gauge("ell_dense_rows", float(rows),
                                  pred=attr, reverse=str(reverse))
                METRICS.set_gauge("ell_dense_cols", float(cols),
                                  pred=attr, reverse=str(reverse))
                METRICS.set_gauge("ell_dense_edges", float(g.dense_edges),
                                  pred=attr, reverse=str(reverse))
        out = cache[key]
    memgov.GOVERNOR.maybe_evict("host")
    return out


def note_pulls(g, family: str, pulled: int) -> None:
    """Count a launch's pulled hops in in-edges: every stored in-edge of
    the relation answered once a pull, and the hub block's share of them
    (0 where the relation has no block: the series exist all the same)."""
    METRICS.inc("kernel_edges_pulled_total", float(pulled * g.nnz),
                family=family)
    METRICS.inc("kernel_edges_dense_total", float(pulled * g.dense_edges),
                family=family)


def _dev_for(store, attr: str, reverse: bool):
    """DeviceEll per (snapshot, pred, dir): the index blocks upload once
    and are shared by every lane width and kernel family."""
    from dgraph_tpu.ops.bfs import device_ell

    host = _cache_host(store, attr, reverse)
    g = _ell_for(store, attr, reverse)  # takes the lock itself
    if g is None:
        return None, None
    with _cache_lock:
        devs = getattr(host, "_ell_devs", None)
        if devs is None:
            devs = host._ell_devs = {}
            _governed_host_cache(host, "_ell_devs", "batch.ell_dev",
                                 "device", memgov.estimate_nbytes,
                                 cascade=_drop_dependent_fns)
        dkey = (attr, reverse)
        if dkey not in devs:
            import jax
            with tracing.span("batch.upload_ell", phase=True, pred=attr,
                              reverse=reverse) as sp:
                dev = devs[dkey] = device_ell(g)
                # device_put returns before the copy has ended
                jax.block_until_ready(vars(dev))
                sp.attrs["bytes"] = memgov.estimate_nbytes(dev)
        out = g, devs[dkey]
    memgov.GOVERNOR.maybe_evict("device")
    return out


def _recurse_for(store, attr: str, reverse: bool, W: int):
    """Compiled kernel per (snapshot, pred, dir, lane width)."""
    from dgraph_tpu.ops.bfs import make_ell_recurse
    from dgraph_tpu.ops.pallas_hop import pallas_enabled

    host = _cache_host(store, attr, reverse)
    # the hop implementation is baked in at prepare time: the flag is
    # part of the key, so an A/B toggle mid-process can't serve a stale
    # kernel under the other implementation's name
    key = ("recurse", attr, reverse, W, pallas_enabled())
    fns = getattr(host, "_ell_fns", None)
    if fns is not None and key in fns:  # hot path: no lock
        return fns[key]
    g, dev = _dev_for(store, attr, reverse)
    with _cache_lock:
        fns = getattr(host, "_ell_fns", None)
        if fns is None:
            fns = host._ell_fns = {}
            _governed_host_cache(host, "_ell_fns", "batch.kernel",
                                 "host", lambda v: _KERNEL_NBYTES_EST)
        if key not in fns:
            fns[key] = make_ell_recurse(dev, g.outdeg, g.n, W,
                                        count_edges=False)
        return fns[key]


def _dev_with_out(store, attr: str, reverse: bool):
    """_dev_for, with the relation's out-CSR in the ELL's row space beside
    the blocks (DeviceEll.out): what a pushed hop reads, in the lane step
    and in a recurse stage of the tree program alike. Built and uploaded
    once, the first time either is asked for, into the DeviceEll that
    `batch.ell_dev` governs."""
    import jax

    from dgraph_tpu.ops.bfs import out_csr

    g, dev = _dev_for(store, attr, reverse)
    if dev is None or dev.out is not None:  # hot path: no lock
        return g, dev
    with _cache_lock:
        if dev.out is None:
            rel = store.rel(attr, reverse)
            with tracing.span("batch.build_ell", phase=True, pred=attr,
                              reverse=reverse, part="out_csr"):
                out = out_csr(g, rel.indptr, rel.indices)
            with tracing.span("batch.upload_ell", phase=True, pred=attr,
                              reverse=reverse, part="out_csr") as sp:
                dev.out = jax.block_until_ready(jax.device_put(out))
                sp.attrs["bytes"] = memgov.estimate_nbytes(dev.out)
    memgov.GOVERNOR.maybe_evict("device")
    return g, dev


def _step_for(store, attr: str, reverse: bool, W: int,
              first_visit: bool):
    """Compiled resumable hop block per (snapshot, pred, dir, width,
    family) — the staged shortest path's kernel, donated carries. Its
    pushed hops read the relation's out-CSR (_dev_with_out)."""
    from dgraph_tpu.ops.bfs import make_ell_step
    from dgraph_tpu.ops.pallas_hop import pallas_enabled

    host = _cache_host(store, attr, reverse)
    key = ("step", attr, reverse, W, first_visit, pallas_enabled())
    fns = getattr(host, "_ell_fns", None)
    if fns is not None and key in fns:  # hot path: no lock
        return fns[key]
    g, dev = _dev_with_out(store, attr, reverse)
    with _cache_lock:
        fns = getattr(host, "_ell_fns", None)
        if fns is None:
            fns = host._ell_fns = {}
            _governed_host_cache(host, "_ell_fns", "batch.kernel",
                                 "host", lambda v: _KERNEL_NBYTES_EST)
        if key not in fns:
            fns[key] = make_ell_step(dev, g.n, W, SHORTEST_STAGE,
                                     first_visit=first_visit)
        return fns[key]


def carry_kernel_caches(old_store, new_store, touched) -> int:
    """Incremental rebuild on snapshot fold: predicates untouched by the
    folded layers rebuilt to IDENTICAL CSR content (same vocabulary ⇒
    same dense rank space), so the old snapshot's ELL blocks, device
    uploads, and compiled kernels stay valid — copy their cache entries
    to the new snapshot instead of rebuilding a 1M-node ELL from
    scratch. Returns how many (pred, direction) entries carried."""
    if old_store is new_store or old_store is None or new_store is None:
        return 0
    if getattr(old_store, "n_nodes", -1) != \
            getattr(new_store, "n_nodes", -2):
        return 0
    if not np.array_equal(old_store.uids, new_store.uids):
        return 0
    carry_mesh_residency(old_store, new_store, touched)
    carried = 0
    with _cache_lock:
        src_cache = getattr(old_store, "_ell_cache", None)
        if not src_cache:
            return 0
        dst_cache = getattr(new_store, "_ell_cache", None)
        if dst_cache is None:
            dst_cache = new_store._ell_cache = {}
            _governed_host_cache(new_store, "_ell_cache", "batch.ell",
                                 "host", memgov.estimate_nbytes)
        src_devs = getattr(old_store, "_ell_devs", {}) or {}
        src_fns = getattr(old_store, "_ell_fns", {}) or {}
        dst_devs = getattr(new_store, "_ell_devs", None)
        if dst_devs is None:
            dst_devs = new_store._ell_devs = {}
            _governed_host_cache(new_store, "_ell_devs", "batch.ell_dev",
                                 "device", memgov.estimate_nbytes,
                                 cascade=_drop_dependent_fns)
        dst_fns = getattr(new_store, "_ell_fns", None)
        if dst_fns is None:
            dst_fns = new_store._ell_fns = {}
            _governed_host_cache(new_store, "_ell_fns", "batch.kernel",
                                 "host", lambda v: _KERNEL_NBYTES_EST)
        for key, gval in src_cache.items():
            attr = key[0]
            if attr in touched or key in dst_cache:
                continue
            dst_cache[key] = gval
            if key in src_devs:
                dst_devs[key] = src_devs[key]
            # the slot-aligned weights go where the ELL goes
            for wkey, wts in src_devs.items():
                if len(wkey) == 3 and wkey[:2] == key:
                    dst_devs.setdefault(wkey, wts)
            for fkey, fn in src_fns.items():
                if fkey[1] == attr and fkey[2] == key[1]:
                    dst_fns.setdefault(fkey, fn)
            carried += 1
    if carried:
        METRICS.inc("ell_cache_carried_total", float(carried))
    return carried


def carry_mesh_residency(old_store, new_store, touched) -> int:
    """Sharded mesh tablets (store.sharded_rel cache) carry across a
    fold exactly like ELL/device blocks: a predicate the folded layers
    didn't touch rebuilds to identical CSR content, so the placed shard
    stack stays valid for the same mesh — the serving path never
    re-uploads a resident tablet because of an unrelated fold."""
    src = getattr(old_store, "_sharded", None)
    if not src:
        return 0
    mesh = getattr(old_store, "_sharded_mesh", None)
    with _cache_lock:
        dst = getattr(new_store, "_sharded", None)
        if dst is None or getattr(new_store, "_sharded_mesh",
                                  None) is not mesh:
            dst = new_store._sharded = {}
            new_store._sharded_mesh = mesh
        carried = 0
        for key, srel in src.items():
            if key[0] in touched or key in dst:
                continue
            dst[key] = srel
            carried += 1
    if carried:
        METRICS.inc("mesh_resident_carried_total", float(carried))
    return carried