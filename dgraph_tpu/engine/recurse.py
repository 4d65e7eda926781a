"""@recurse: iterative whole-frontier re-expansion until fixpoint/depth.

Reference parity: `query/recurse.go` (expandRecurse) — THE north-star
workload. The reference re-seeds the SubGraph with each hop's result and
re-runs ProcessGraph; here each depth is one batched expansion per followed
predicate over the union frontier, with the seen-set subtraction
(`loop: false`) done with sorted-set difference.

Semantics (documented, since the reference tree is unavailable to consult —
SURVEY provenance warning): with `loop: false` a node is expanded at most
once (its first visit); later appearances render without children. With
`loop: true`, expansion repeats up to `depth` regardless of revisits
(depth is required in that case to terminate).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from dgraph_tpu.engine.execute import _needs_facets
from dgraph_tpu.engine.ir import SubGraph
from dgraph_tpu.utils import deadline

MAX_RECURSE_DEPTH = 64  # guard when depth: 0 (fixpoint mode)


@dataclass
class RecurseData:
    """Per-predicate edge lists accumulated over all depths.

    `edges[pred_key]` = (parents, children) rank arrays; every parent rank
    appears in at most one depth (loop=false), so rows are unambiguous.
    For loop=true, per-depth matrices are kept separate.
    """

    edge_sgs: list[SubGraph] = field(default_factory=list)
    leaf_sgs: list[SubGraph] = field(default_factory=list)
    # loop=false: one global matrix per predicate
    edges: dict[int, tuple[np.ndarray, np.ndarray]] = field(default_factory=dict)
    # loop=true: per-depth list of matrices keyed by (depth, pred index)
    by_depth: list[dict[int, tuple[np.ndarray, np.ndarray]]] = field(default_factory=list)
    loop: bool = False
    all_nodes: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int32))
    # @msgpass binding (engine/feat.py): rank → f32[d] aggregate over
    # the visit-once edge set; None = unbound (the fused featprop
    # stage binds in-trace, the staged post-pass binds host-side)
    feat_vals: dict | None = None
    feat_key: str = ""


def split_children(ex, sg: SubGraph, data: RecurseData) -> RecurseData:
    """Partition a recurse block's children into edge predicates vs
    leaves — ONE rule shared by the host loop, the mesh paths, and the
    whole-query fused program (engine/fused.py), so the routing can
    never fork."""
    for c in sg.children:
        (data.edge_sgs if ex._expands(c) else data.leaf_sgs).append(c)
    return data


def expand_recurse(ex, root) -> None:
    """Run the recurse loop below an already-evaluated root LevelNode."""
    from dgraph_tpu.engine.execute import LevelNode  # noqa: F401 (doc)

    sg = root.sg
    args = sg.recurse
    depth = args.depth or MAX_RECURSE_DEPTH
    if args.loop and not args.depth:
        raise ValueError("@recurse(loop: true) requires depth")

    data = split_children(ex, root.sg, RecurseData(loop=args.loop))

    # Single-predicate depth-bounded visit-once recursions run on the mesh
    # as chained launches of ONE compiled SPMD hop program (_chain_recurse).
    # Filters/facet-filters/loop need per-hop host logic and fall back to
    # the loop below.
    if (ex.mesh is not None and not args.loop and args.depth
            and len(data.edge_sgs) == 1 and not data.edge_sgs[0].filters
            and not data.edge_sgs[0].facet_filter
            and len(root.nodes) > 0):
        _chain_recurse(ex, root, data, args.depth)
        _bind_recurse_vars(ex, root, data, sg)
        root.recurse_data = data
        return

    frontier = root.nodes
    seen = root.nodes.copy()
    for _d in range(depth):
        if len(frontier) == 0:
            break
        # per-hop cancellation point: a pathological @recurse stops
        # within one hop of its budget (utils/deadline.py)
        deadline.checkpoint("recurse")
        level: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        new_parts = []
        for i, esg in enumerate(data.edge_sgs):
            nbrs, seg, pos = ex.expand(
                esg.attr, esg.is_reverse, frontier,
                allow_remote=not _needs_facets(esg))
            nbrs, seg, pos = ex.filter_edges(esg.filters, nbrs, seg, pos)
            nbrs, seg, pos = ex.facet_filter_edges(esg, esg.attr, nbrs,
                                                   seg, pos)
            if not args.loop and len(nbrs):
                # visit-once: drop edges to already-seen nodes so the result
                # graph is a DAG by depth (first-visit tree semantics)
                keep = ~np.isin(nbrs, seen)
                nbrs, seg = nbrs[keep], seg[keep]
            if not len(nbrs):
                continue
            parents = frontier[seg]
            if data.loop:
                level[i] = (parents, nbrs)
            else:
                if i in data.edges:
                    p0, c0 = data.edges[i]
                    data.edges[i] = (np.concatenate([p0, parents]),
                                     np.concatenate([c0, nbrs]))
                else:
                    data.edges[i] = (parents, nbrs)
            new_parts.append(nbrs)
        if data.loop:
            data.by_depth.append(level)
        if not new_parts:
            break
        nxt = np.unique(np.concatenate(new_parts)).astype(np.int32)
        if not args.loop:
            nxt = np.setdiff1d(nxt, seen).astype(np.int32)
            seen = np.union1d(seen, nxt).astype(np.int32)
        frontier = nxt

    data.all_nodes = seen if not args.loop else np.unique(np.concatenate(
        [root.nodes] + [c for lv in data.by_depth for (_p, c) in lv.values()]
    )).astype(np.int32)
    _bind_recurse_vars(ex, root, data, sg)
    root.recurse_data = data


def _bind_recurse_vars(ex, root, data: RecurseData, sg: SubGraph) -> None:
    """Leaf value vars bind over every visited node; the block's uid var
    is the whole reachable set."""
    for leaf in data.leaf_sgs:
        if leaf.var_name:
            saved_nodes = root.nodes
            root.nodes = data.all_nodes
            ex._record_leaf_vars(leaf, root)
            root.nodes = saved_nodes
    if sg.var_name:
        ex.uid_vars[sg.var_name] = data.all_nodes


def _chain_recurse(ex, root, data: RecurseData, depth: int) -> None:
    """Depth-bounded mesh @recurse as `depth` launches of ONE compiled
    hop program (parallel.dhop.chain_hop). The hop's replicated
    out_specs are exactly the next launch's in_specs, so the frontier
    and seen set stay device-resident between hops — zero cross-device
    reshards on the steady path (mesh.reshard_guard armed around the
    loop; the pjit pitfall SNIPPETS calls out) — and the compile is
    depth-independent. The host only READS each hop's outputs (edge
    matrices + the input frontier's values, for rendering) and feeds the
    same device arrays back in. Semantics are the host loop's
    (visit-once, first-visit-tree), pinned by tests against it."""
    from dgraph_tpu.engine.execute import _bucket, pad_host
    from dgraph_tpu.ops.uidalgebra import SENTINEL32
    from dgraph_tpu.parallel.dhop import chain_hop
    from dgraph_tpu.parallel.mesh import host_np, reshard_guard
    from dgraph_tpu.utils import costprofile, tracing

    from dgraph_tpu.utils.metrics import METRICS
    METRICS.inc("mesh_route_total", route="chain")
    esg = data.edge_sgs[0]
    srel = ex.store.sharded_rel(esg.attr, esg.is_reverse, ex.mesh)
    seeds = np.sort(root.nodes).astype(np.int32)
    out_cap = _bucket(max(len(seeds), 1))
    seen_cap = _bucket(4 * out_cap, lo=256)
    edge_cap = _bucket(1, lo=1024)
    parts_p: list[np.ndarray] = []
    parts_c: list[np.ndarray] = []
    seen = None
    for _attempt in range(12):  # geometric cap growth, bounded
        fr = pad_host(seeds, out_cap)
        seen = pad_host(seeds, seen_cap)
        parts_p, parts_c = [], []
        overflowed = False
        with reshard_guard():
            for h in range(depth):
                deadline.checkpoint("recurse")
                with tracing.span("mesh.hop", pred=esg.attr, hop=h,
                                  shards=srel.n_shards) as sp:
                    (fr_next, seen_next, _edges, needs, nbrs_s, seg_s,
                     shard_edges, kept) = chain_hop(
                        ex.mesh, srel, fr, seen,
                        edge_cap, out_cap, seen_cap)
                    need_out, need_seen, need_edge = (
                        int(x) for x in host_np(needs))
                    if (need_out > out_cap or need_seen > seen_cap
                            or need_edge > edge_cap):
                        out_cap = _bucket(max(need_out, out_cap))
                        seen_cap = _bucket(max(need_seen, seen_cap),
                                           lo=256)
                        edge_cap = _bucket(max(need_edge, edge_cap),
                                           lo=1024)
                        overflowed = True
                        break
                    # render reads: the hop's INPUT frontier values map
                    # seg → parent ranks; the device fr/seen arrays feed
                    # the next launch unmoved
                    fr_h = host_np(fr)
                    nbrs_h = host_np(nbrs_s)
                    seg_h = host_np(seg_s)
                    per_shard = host_np(shard_edges)
                    sp.attrs["edges"] = int(host_np(kept))
                    for d in range(srel.n_shards):
                        row = nbrs_h[d]
                        m = row != SENTINEL32
                        if m.any():
                            parts_p.append(fr_h[seg_h[d][m]])
                            parts_c.append(row[m])
                        # modeled per-shard µs (the ~16 edges/µs host
                        # scale expand() charges tablets with) — the
                        # scheduler/placement signal for mesh work
                        if int(per_shard[d]):
                            costprofile.add_shard_cost(
                                d, int(per_shard[d]) // 16 + 1)
                    fr, seen = fr_next, seen_next
                    if need_out == 0:  # frontier emptied: fixpoint
                        break
        if not overflowed:
            break
    else:
        raise RuntimeError("recurse caps failed to converge")

    if parts_p:
        data.edges[0] = (np.concatenate(parts_p).astype(np.int32),
                         np.concatenate(parts_c).astype(np.int32))
    seen_h = host_np(seen)
    data.all_nodes = seen_h[seen_h != SENTINEL32].astype(np.int32)
