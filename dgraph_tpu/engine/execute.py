"""SubGraph execution: ProcessGraph the TPU-native way.

Reference parity: `query/query.go` (SubGraph.ProcessGraph — recursive
per-level expansion, filter application, pagination/order), `worker/task.go`
(processTask) and `query/outputnode.go` (JSON assembly lives in
outputnode.py).

Execution model (SURVEY §7): each level's expansion is ONE batched CSR
gather over the whole frontier — device path through `ops.expand_frontier`
(jitted, static bucket sizes) for large frontiers, numpy path for small
ones; both produce identical (neighbors, seg) pairs. Per-uid goroutines and
per-child RPC fan-out from the reference collapse into array programs.

A level's result is a `LevelNode`:
  nodes        sorted unique ranks at this level (the next frontier)
  matrix_seg   edge → position in parent.nodes   (pb.Result.UidMatrix rows)
  matrix_child edge → child rank (row-ordered: order/pagination applied)
Content is computed once per unique uid (as the reference does), while the
matrix preserves per-parent row structure for nested JSON.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from dgraph_tpu import ops
from dgraph_tpu.engine.funcs import (EMPTY, eval_func,
                                     eval_func_universe)
from dgraph_tpu.engine.ir import FilterNode, FuncNode, Order, SubGraph
from dgraph_tpu.store.store import Store
from dgraph_tpu.store.types import Kind
from dgraph_tpu.utils import costprofile, memgov
from dgraph_tpu.utils import deadline as dl
from dgraph_tpu.utils import tracing
from dgraph_tpu.utils.jitcache import jit_call
from dgraph_tpu.utils.metrics import METRICS


EMPTY64 = np.zeros(0, np.int64)


@dataclass
class LevelNode:
    sg: SubGraph
    nodes: np.ndarray                      # sorted unique int32 ranks
    matrix_seg: np.ndarray = field(default_factory=lambda: EMPTY)
    matrix_child: np.ndarray = field(default_factory=lambda: EMPTY)
    matrix_pos: np.ndarray = field(default_factory=lambda: EMPTY64)
    display: np.ndarray | None = None      # root blocks: ordered rank list
    children: list["LevelNode"] = field(default_factory=list)
    leaf_sgs: list[SubGraph] = field(default_factory=list)
    recurse_data: object | None = None     # engine.recurse.RecurseData
    path_data: object | None = None        # engine.shortest.PathData
    groups: object | None = None           # engine.groupby.GroupResult
    # a root block answered by a count alone: |nodes| where the nodes
    # were never named (engine/treebatch.py; count(uid) renders it)
    count: int | None = None
    # @msgpass binding (engine/feat.py): rank → f32[d] aggregate; None
    # means the level carries no binding (key "" likewise)
    feat_vals: dict | None = None
    feat_key: str = ""


def _bucket(n: int, lo: int = 64) -> int:
    b = lo
    # graftlint: allow(hot-loop-checkpoint): O(log n) shift arithmetic
    while b < n:
        b <<= 1
    return b


def pad_host(a: np.ndarray, size: int) -> np.ndarray:
    """Host twin of `ops.pad_to` for MESH launches: a host array is a
    first-hop upload; `ops.pad_to`'s result is committed to one device
    and would be re-laid across the mesh before the launch — the silent
    copy `mesh_hop_resharded_total` counts."""
    out = np.full(size, ops.SENTINEL32, np.int32)
    out[:len(a)] = a
    return out


def csr_rows(rel, frontier: np.ndarray):
    """Host CSR row gather for a frontier → (neighbors, seg, edge_pos).
    The one shared implementation of the per-uid posting walk (reference:
    posting.List.Uids per uid; here one vectorized gather) — used by the
    small-frontier expand path and the lane-batch mask rebuild."""
    starts = rel.indptr[frontier]
    deg = rel.indptr[frontier + 1] - starts
    total = int(deg.sum())
    if total == 0:
        return EMPTY, EMPTY, EMPTY64
    seg = np.repeat(np.arange(len(frontier), dtype=np.int32), deg)
    # edge e of row i sits at starts[i] + (e - the edges before row i):
    # one repeat and one add in place, two arrays of `total` where the
    # sum spelt out takes five (fresh pages are dear on a serving host)
    pos = np.repeat(starts.astype(np.int64) - (np.cumsum(deg) - deg), deg)
    pos += np.arange(total, dtype=np.int64)
    return rel.indices[pos], seg, pos


class Executor:
    """Executes SubGraph trees against a Store snapshot.

    `device_threshold`: frontiers at least this large expand via the jitted
    TPU kernel; smaller ones via numpy (dispatch overhead dominates tiny
    frontiers). Set to 0 to force the device path (tests do).
    """

    def __init__(self, store: Store, device_threshold: int = 512,
                 mesh=None):
        self.store = store
        self.device_threshold = device_threshold
        self.mesh = mesh  # jax.sharding.Mesh | None: SPMD expansion path
        # variable environments (reference: query var propagation)
        self.uid_vars: dict[str, np.ndarray] = {}
        self.val_vars: dict[str, dict[int, object]] = {}

    # -- frontier expansion (the hot op) ------------------------------------
    def expand(self, pred: str, reverse: bool, frontier: np.ndarray,
               allow_remote: bool = True):
        """Whole-frontier CSR expansion → (neighbors, seg, edge_pos) host
        arrays. `edge_pos` indexes the CSR of the expansion direction;
        facet consumers map reverse positions through facet_positions()
        (forward-aligned) AT USE so facet-free reverse hops — the hot
        distributed-task path — never pay for the rev→fwd table.

        On a routed view, a small-frontier hop over a foreign tablet may
        execute on the OWNER via ServeTask instead of faulting the whole
        tablet in (reference: ProcessTaskOverNetwork); remote results
        carry no edge positions, so callers needing facets pass
        allow_remote=False."""
        with tracing.span("ops.expand", pred=pred, reverse=reverse,
                          frontier=int(len(frontier))) as sp:
            t0 = time.perf_counter()
            out, path = self._expand_routed(pred, reverse, frontier,
                                            allow_remote)
            sp.attrs["path"] = path
            sp.attrs["edges"] = int(len(out[0]))
            if self.mesh is not None:
                # route-selector accounting: which path won while a
                # mesh was configured (the promotion A/B signal)
                METRICS.inc("mesh_route_total", route=path)
            if len(out[0]):
                # learned route costs: µs per 1k edges EMA per path —
                # the prior the selector consults to promote the mesh
                # route below the static threshold
                from dgraph_tpu.utils import costprior
                costprior.PRIORS.learn_route(
                    path, (time.perf_counter() - t0) * 1e6
                    / max(len(out[0]), 1) * 1000.0)
                # the north-star counter, labeled by execution path
                METRICS.inc("edges_traversed_total", float(len(out[0])),
                            path=path)
                costprofile.add("edges_traversed", int(len(out[0])))
                # gather-traffic model: neighbor + seg + position words
                costprofile.add("bytes_gathered", 16 * int(len(out[0])))
                # placement signal: modeled µs charged to this tablet
                # (~16 host edges per µs — the same order the bench's
                # CPU baseline measures)
                costprofile.add_tablet_cost(pred, len(out[0]) // 16 + 1)
            return out

    def _expand_routed(self, pred: str, reverse: bool,
                       frontier: np.ndarray, allow_remote: bool):
        """expand()'s dispatch body → ((nbrs, seg, pos), path) where
        `path` names the execution route (telemetry label)."""
        if allow_remote and len(frontier):
            rem = getattr(self.store, "remote_expand", None)
            if rem is not None:
                out = rem(pred, reverse, frontier)
                if out is not None:
                    return out, "remote"
        rel = self.store.rel(pred, reverse)
        # cost-model regressor: the largest tablet this request touched
        costprofile.note_max("tablet_rows", int(len(rel.indptr)) - 1)
        if len(frontier) == 0 or rel.nnz == 0:
            return (EMPTY, EMPTY, EMPTY64), "empty"
        if len(frontier) >= self.device_threshold:
            try:
                if self.mesh is not None:
                    return (self._expand_mesh(pred, reverse, frontier),
                            "mesh")
                return (self._expand_device(pred, reverse, frontier),
                        "device")
            except memgov.OomDegraded:
                # allocation failure survived its evict-retry (or the
                # shape is sticky-degraded): the host walk produces the
                # identical (nbrs, seg, pos) triple
                pass
        elif self.mesh is not None and self._mesh_promoted(len(frontier)):
            try:
                return self._expand_mesh(pred, reverse, frontier), "mesh"
            except memgov.OomDegraded:
                pass
        return csr_rows(rel, frontier), "numpy"

    # learned-promotion floor: below this many frontier rows, per-launch
    # dispatch overhead dominates any measured per-edge win, so the
    # numpy path keeps them regardless of what the route EMAs say
    mesh_floor = 64

    def _mesh_promoted(self, n: int) -> bool:
        """Cost-prior route promotion: frontiers below device_threshold
        still take the mesh route when the measured per-edge cost EMAs
        (utils/costprior.py, learned from every expansion) say the mesh
        is cheaper than the host walk. Before any data exists — or with
        priors disabled — the classic threshold routing is unchanged."""
        from dgraph_tpu.utils import costprior
        if n < self.mesh_floor or not costprior.enabled():
            return False
        m = costprior.PRIORS.route_cost("mesh")
        h = costprior.PRIORS.route_cost("numpy")
        return m is not None and h is not None and m < h

    def _note_mesh_shards(self, counts) -> None:
        """Shard-keyed accounting for one mesh-routed expansion: the
        shape component + shard-count feature the cost priors key on,
        and modeled per-shard µs into the shard cost sums (the
        scheduler/placement signal /debug/scheduler surfaces)."""
        counts = np.asarray(counts)
        costprofile.add_shape("mesh")
        costprofile.note_max("mesh_shards", int(len(counts)))
        for d, c in enumerate(counts.tolist()):
            if int(c):
                costprofile.add_shard_cost(d, int(c) // 16 + 1)

    def facet_positions(self, sg: SubGraph, pos: np.ndarray) -> np.ndarray:
        """Edge positions in the forward-CSR space facet columns key on
        (reference: facets live on the forward posting but render on
        reverse edges too)."""
        if sg.is_reverse:
            return self.store.rev_to_fwd_pos(sg.attr, pos)
        return pos

    def _shard_edge_cap(self, srel, frontier: np.ndarray,
                        deg: np.ndarray) -> int:
        """Per-shard edge-cap bucket: rows partition over shards, so each
        shard needs only ITS slab's degree sum."""
        shard_of = np.minimum(frontier // srel.rows_per_shard,
                              srel.n_shards - 1)
        per_shard = np.bincount(shard_of, weights=deg,
                                minlength=srel.n_shards)
        return _bucket(max(int(per_shard.max()), 1))

    @staticmethod
    def _stitch_edge_parts(parts):
        """Stitch per-shard edge slices into one global edge matrix:
        each frontier row's edges come from exactly one slice, so a
        stable sort by seg recovers global CSR row order. `parts` yields
        (nbrs, seg, local_pos, pos_lo) — pos offsets into the absolute
        facet position space."""
        parts_n, parts_s, parts_p = [], [], []
        for nbrs, seg, pos, pos_lo in parts:
            if not len(nbrs):
                continue
            parts_n.append(nbrs)
            parts_s.append(seg)
            parts_p.append(pos.astype(np.int64) + int(pos_lo))
        if not parts_n:
            return EMPTY, EMPTY, EMPTY64
        nbrs = np.concatenate(parts_n)
        seg = np.concatenate(parts_s)
        pos = np.concatenate(parts_p)
        order = np.argsort(seg, kind="stable")
        return nbrs[order], seg[order], pos[order]

    @classmethod
    def _reassemble_shards(cls, srel, nbrs_s, seg_s, pos_s, counts):
        from dgraph_tpu.parallel.mesh import host_np
        nbrs_s, seg_s, pos_s = (host_np(nbrs_s), host_np(seg_s),
                                host_np(pos_s))
        counts = host_np(counts)
        return cls._stitch_edge_parts(
            (nbrs_s[d, :int(counts[d])], seg_s[d, :int(counts[d])],
             pos_s[d, :int(counts[d])], srel.pos_lo[d])
            for d in range(srel.n_shards))

    # frontiers above this replicate poorly: shard them and ring-rotate
    # over ICI instead (the long-context analog, SURVEY §5). Tests lower
    # it to force the ring path on small fixtures.
    ring_threshold = 1 << 17

    def _expand_mesh(self, pred: str, reverse: bool, frontier: np.ndarray):
        """SPMD expansion over the device mesh: every device expands the
        row slab it owns, outputs stay sharded, the host reassembles the
        edge matrix (reference: ProcessTaskOverNetwork scatter/gather —
        SURVEY §3.1 — with gRPC replaced by residency + one shard_map).
        Frontiers past ring_threshold ride the sharded ring path."""
        from dgraph_tpu.parallel.dhop import matrix_hop

        if len(frontier) > self.ring_threshold:
            return self._expand_mesh_ring(pred, reverse, frontier)
        srel = self.store.sharded_rel(pred, reverse, self.mesh)
        fr = pad_host(frontier, _bucket(len(frontier)))
        deg = self.store.rel(pred, reverse).degree(frontier)
        edge_cap = self._shard_edge_cap(srel, frontier, deg)
        from dgraph_tpu.parallel.mesh import host_np

        def _launch():
            memgov.check_alloc_fault("mesh.matrix_hop")
            return matrix_hop(self.mesh, srel, fr, edge_cap)

        nbrs_s, seg_s, pos_s, totals, max_shard = memgov.oom_retry(
            "mesh.matrix_hop", (pred, reverse), _launch)
        max_shard = int(host_np(max_shard))
        assert max_shard <= edge_cap, (max_shard, edge_cap)
        totals = host_np(totals)
        self._note_mesh_shards(totals)
        return self._reassemble_shards(srel, nbrs_s, seg_s, pos_s, totals)

    def _expand_mesh_ring(self, pred: str, reverse: bool,
                          frontier: np.ndarray):
        """Sharded-frontier expansion: chunks rotate ring-wise (ppermute)
        while each device expands against its resident row slab — the
        engine route for frontiers too large to replicate (SURVEY §5
        long-context analog; structural cousin of ring attention)."""
        from dgraph_tpu.parallel.dhop import ring_matrix_hop
        from dgraph_tpu.parallel.pshard import shard_frontier

        srel = self.store.sharded_rel(pred, reverse, self.mesh)
        d = srel.n_shards
        per = -(-len(frontier) // d)
        f_cap = _bucket(max(per, 1))
        chunks = shard_frontier(frontier, d, f_cap)
        # per (origin chunk × shard) edge cap: a chunk meets every slab
        deg = self.store.rel(pred, reverse).degree(frontier)
        rows_per = srel.rows_per_shard
        shard_of = np.minimum(frontier // rows_per, d - 1)
        chunk_of = np.minimum(np.arange(len(frontier)) // per, d - 1)
        per_pair = np.zeros((d, d))
        np.add.at(per_pair, (chunk_of, shard_of), deg)
        edge_cap = _bucket(max(int(per_pair.max()), 1))
        from dgraph_tpu.parallel.mesh import host_np

        def _launch():
            memgov.check_alloc_fault("mesh.ring_matrix_hop")
            return ring_matrix_hop(self.mesh, srel, chunks, edge_cap)

        nbrs_a, seg_a, pos_a, totals, max_e = memgov.oom_retry(
            "mesh.ring_matrix_hop", (pred, reverse), _launch)
        assert int(host_np(max_e)) <= edge_cap, edge_cap
        nbrs_a, seg_a, pos_a = (host_np(nbrs_a), host_np(seg_a),
                                host_np(pos_a))
        totals = host_np(totals)
        self._note_mesh_shards(totals.sum(axis=1))
        nbrs, seg, pos = self._stitch_edge_parts(
            (nbrs_a[dev, i, :int(totals[dev, i])],
             seg_a[dev, i, :int(totals[dev, i])] + ((dev - i) % d) * per,
             pos_a[dev, i, :int(totals[dev, i])], srel.pos_lo[dev])
            for dev in range(d) for i in range(d))
        keep = seg < len(frontier)  # drop chunk padding rows
        return nbrs[keep], seg[keep], pos[keep]

    def _expand_device(self, pred: str, reverse: bool, frontier: np.ndarray):
        indptr, indices = self.store.device_rel(pred, reverse)
        fcap = _bucket(len(frontier))
        fr = ops.pad_to(frontier, fcap)
        deg = self.store.rel(pred, reverse).degree(frontier)
        ecap = _bucket(max(int(deg.sum()), 1))
        from dgraph_tpu.ops.hop import launch_key

        def _launch():
            memgov.check_alloc_fault("hop.gather_edges")
            with jit_call("hop.gather_edges",
                          launch_key(indptr, fr, ecap)):
                return ops.gather_edges(indptr, indices, fr, ecap)

        # OOM lifecycle: evict-to-low + one retry, then sticky degrade
        # of this predicate's device route (OomDegraded → numpy walk)
        nbrs, seg, pos, valid, total = memgov.oom_retry(
            "hop.gather_edges", (pred, reverse), _launch)
        valid = np.asarray(valid)
        return (np.asarray(nbrs)[valid], np.asarray(seg)[valid],
                np.asarray(pos)[valid].astype(np.int64))

    # -- filters ------------------------------------------------------------
    def apply_filter(self, tree: FilterNode | None, universe: np.ndarray) -> np.ndarray:
        """Evaluate a filter tree restricted to `universe` (sorted ranks).
        Reference: filter SubGraphs + algo.IntersectSorted/Difference.
        Comparison/has leaves evaluate AGAINST the universe (cost tracks
        the frontier); other funcs materialize their set and intersect."""
        if tree is None:
            return universe
        if tree.op == "leaf":
            f = tree.func
            if f.name != "uid" and not f.is_val_var and not f.is_count:
                sub = eval_func_universe(self.store, f, universe)
                if sub is not None:
                    return sub
            return np.intersect1d(universe, self._leaf_set(tree.func, universe))
        if tree.op == "not":
            return np.setdiff1d(universe, self.apply_filter(tree.children[0], universe))
        parts = [self.apply_filter(c, universe) for c in tree.children]
        out = parts[0]
        for p in parts[1:]:
            out = np.intersect1d(out, p) if tree.op == "and" else np.union1d(out, p)
        return out.astype(np.int32)

    def filter_set(self, tree: FilterNode | None) -> np.ndarray | None:
        """Evaluate a filter tree to its allowed set WITHOUT a universe —
        index lookups only, so host cost scales with the result, never with
        n_nodes (reference: index-backed filter SubGraphs). Returns None
        when the tree needs a complement (`not`), which only a universe can
        answer; callers then filter against gathered neighbors instead."""
        if tree is None:
            return None
        if tree.op == "leaf":
            return self._leaf_set(tree.func, EMPTY).astype(np.int32)
        if tree.op == "not":
            return None
        parts = [self.filter_set(c) for c in tree.children]
        if any(p is None for p in parts):
            return None
        out = parts[0]
        for p in parts[1:]:
            out = (np.intersect1d(out, p) if tree.op == "and"
                   else np.union1d(out, p))
        return out.astype(np.int32)

    def _var_ranks(self, name: str) -> np.ndarray:
        """uid(x): a uid var's ranks, or a val var's uid domain."""
        if name in self.uid_vars:
            return self.uid_vars[name]
        if name in self.val_vars:
            return np.array(sorted(self.val_vars[name]), np.int32)
        # reference: referencing an undefined variable is a request error,
        # not an empty result (gql validateResult var checks)
        raise ValueError(f"variable {name!r} is used but not defined")

    def filter_edges(self, filters: FilterNode | None, nbrs: np.ndarray,
                     seg: np.ndarray, pos: np.ndarray | None = None):
        """Apply a filter tree to a flattened edge list, re-masking rows.
        Shared by plain expansion, @recurse, and shortest-path hops."""
        if pos is None:
            pos = EMPTY64
        if filters is None or not len(nbrs):
            return nbrs, seg, pos
        allowed = self.apply_filter(filters, np.unique(nbrs).astype(np.int32))
        keep = np.isin(nbrs, allowed)
        return nbrs[keep], seg[keep], (pos[keep] if len(pos) else pos)

    def _bind_facet_vars(self, sg: SubGraph, nbrs, pos) -> None:
        """@facets(v as key): value var keyed by CHILD rank. A child
        reached over several edges sums numeric facet values (reference:
        facet-variable aggregation)."""
        cols = self.store.edge_facets(
            sg.attr, self.facet_positions(sg, pos),
            [k for _, k in sg.facet_vars])
        for var, key in sg.facet_vars:
            vals = cols.get(key)
            m: dict = {}
            if vals is not None:
                for c, v in zip(nbrs.tolist(), vals):
                    if v is None:
                        continue
                    prev = m.get(c)
                    if (prev is not None and not isinstance(v, bool)
                            and isinstance(v, (int, float))
                            and isinstance(prev, (int, float))):
                        m[int(c)] = prev + v
                    else:
                        m[int(c)] = v
            self.val_vars[var] = m

    def facet_filter_edges(self, sg: SubGraph, pred: str,
                           nbrs: np.ndarray, seg: np.ndarray,
                           pos: np.ndarray):
        """@facets(eq(k, v) ...) — drop edges whose facets fail the tree
        (reference: facets filtering in worker facetsFilter)."""
        if sg.facet_filter is None or not len(nbrs):
            return nbrs, seg, pos
        keep = self._eval_facet_tree(sg.facet_filter, pred,
                                     self.facet_positions(sg, pos))
        return nbrs[keep], seg[keep], pos[keep]

    def _eval_facet_tree(self, tree: FilterNode, pred: str,
                         pos: np.ndarray) -> np.ndarray:
        if tree.op == "leaf":
            f = tree.func
            fvals = self.store.edge_facets(pred, pos, [f.attr]).get(
                f.attr, [None] * len(pos))
            want0 = f.args[0] if f.args else None
            out = np.zeros(len(pos), bool)
            for i, v in enumerate(fvals):
                if v is None:
                    continue
                want = _coerce_to(want0, v)
                try:
                    if f.name == "eq":
                        out[i] = v == want or str(v) == str(want)
                    elif f.name == "le":
                        out[i] = v <= want
                    elif f.name == "lt":
                        out[i] = v < want
                    elif f.name == "ge":
                        out[i] = v >= want
                    elif f.name == "gt":
                        out[i] = v > want
                except TypeError:
                    pass
            return out
        if tree.op == "not":
            return ~self._eval_facet_tree(tree.children[0], pred, pos)
        parts = [self._eval_facet_tree(c, pred, pos) for c in tree.children]
        out = parts[0]
        for p in parts[1:]:
            out = (out & p) if tree.op == "and" else (out | p)
        return out

    def _leaf_set(self, f: FuncNode, universe: np.ndarray) -> np.ndarray:
        if f.name == "uid" and (f.args or not f.uids):
            # mixed literals and variables: union both
            parts = [self._var_ranks(a) for a in f.args]
            if f.uids:
                r = self.store.rank_of(np.array(f.uids, np.int64))
                parts.append(r[r >= 0].astype(np.int32))
            return (np.unique(np.concatenate(parts)).astype(np.int32)
                    if parts else EMPTY)
        if f.name == "similar_to":
            # routed k-NN seed: device/mesh brute-force top-k with host
            # fallback — bit-identical to funcs.host_similar on every
            # route (store/vec.py)
            from dgraph_tpu.store.vec import similar_ranks
            return similar_ranks(self.store, f, mesh=self.mesh,
                                 device_threshold=self.device_threshold)
        return eval_func(self.store, f, self.val_vars)

    # -- root evaluation ----------------------------------------------------
    def root_ranks(self, sg: SubGraph) -> np.ndarray:
        f = sg.func
        if f is None:
            return EMPTY
        return self._leaf_set(f, EMPTY)

    # -- ordering / pagination ----------------------------------------------
    def _value_keys(self, ranks: np.ndarray, order: Order):
        """Sort keys for ranks by a value predicate or val-var. Missing
        values get a placeholder key (they sort last via the has-key)."""
        if order.is_val_var:
            var = self.val_vars.get(order.attr, {})
            vals = [var.get(int(r)) for r in ranks]
        elif not order.lang and (col := self.store.value_col(order.attr)) is not None:
            # vectorised first-value lookup on the sorted columnar pair
            ranks_arr = np.asarray(ranks, np.int32)
            idx = np.searchsorted(col.subj, ranks_arr)
            idx_c = np.minimum(idx, max(len(col.subj) - 1, 0))
            hit = np.atleast_1d(
                (len(col.subj) > 0) & (col.subj[idx_c] == ranks_arr))
            keys = _column_keys(col, idx_c, hit)
            if keys is not None:
                return keys, hit
            vals = [col.vals[i] if h else None
                    for i, h in zip(idx_c.tolist(), hit.tolist())]
        else:
            vals = []
            for r in ranks:
                vs = self.store.values_for(order.attr, int(r), order.lang)
                vals.append(vs[0] if vs else None)
        has = np.array([v is not None for v in vals], bool)
        present = [_orderable(v) for v in vals if v is not None]
        placeholder = present[0] if present else 0
        keys = np.array([_orderable(v) if v is not None else placeholder
                         for v in vals])
        return keys, has

    def order_ranks(self, ranks: np.ndarray, orders: list[Order],
                    seg: np.ndarray | None = None):
        """Stable multi-key ordering, optionally within segments (rows).
        lexsort priority: seg (row) > first order > ... > uid tiebreak."""
        if not orders:
            return np.arange(len(ranks))
        keys = [np.asarray(ranks)]  # lowest priority: uid tiebreak
        for o in reversed(orders):
            k, has = self._value_keys(ranks, o)
            if o.desc:
                k = _negate_key(k)
            keys.append(k)
            keys.append(~has)  # missing values last, asc or desc
        if seg is not None:
            keys.append(seg)
        return np.lexsort(tuple(keys))

    def _facet_order(self, sg: SubGraph, nbrs: np.ndarray, seg: np.ndarray,
                     pos: np.ndarray) -> np.ndarray:
        """Row-internal ordering by facet values (@facets(orderasc: k));
        edges without the facet sort last."""
        keys = [np.asarray(nbrs)]
        fpos = self.facet_positions(sg, pos)
        for o in reversed(sg.facet_orders):
            fvals = self.store.edge_facets(sg.attr, fpos, [o.attr]).get(
                o.attr, [None] * len(pos))
            has = np.array([v is not None for v in fvals], bool)
            present = [_orderable(v) for v in fvals if v is not None]
            placeholder = present[0] if present else 0
            k = np.array([_orderable(v) if v is not None else placeholder
                          for v in fvals])
            if o.desc:
                k = _negate_key(k)
            keys.append(k)
            keys.append(~has)
        keys.append(seg)
        return np.lexsort(tuple(keys))

    def paginate(self, arr_len: int, sg: SubGraph, ranks: np.ndarray) -> np.ndarray:
        """Row slice per first/offset/after → index array into the row."""
        idx = np.arange(arr_len)
        if sg.after:
            after_rank = self.store.rank_of(np.array([sg.after], np.int64))[0]
            idx = idx[ranks > after_rank] if after_rank >= 0 else idx
        if sg.offset:
            idx = idx[sg.offset:]
        if sg.first > 0:
            idx = idx[:sg.first]
        elif sg.first < 0:
            idx = idx[sg.first:]
        return idx

    # -- block execution ----------------------------------------------------
    def run_block(self, sg: SubGraph) -> LevelNode:
        """Execute one root block (reference: Request.ProcessQuery per block)."""
        dl.checkpoint("block")
        with tracing.span("engine.block", block=sg.attr) as sp:
            is_knn = sg.func is not None and sg.func.name == "similar_to"
            t0 = time.perf_counter() if is_knn else 0.0
            node = self._run_block(sg)
            if is_knn:
                # the graphrag_read_p99 SLO watches this histogram: the
                # retrieval workload's per-block latency under whatever
                # route (fused/staged, host/device/mesh) actually served
                METRICS.observe("graphrag_latency_us",
                                (time.perf_counter() - t0) * 1e6)
            sp.attrs["nodes"] = int(len(node.nodes))
            return node

    def _run_block(self, sg: SubGraph) -> LevelNode:
        if sg.shortest is not None:
            from dgraph_tpu.engine.shortest import shortest_path
            data = shortest_path(self, sg)
            node = LevelNode(sg=sg, nodes=data.nodes, path_data=data)
            if sg.var_name:
                self.uid_vars[sg.var_name] = data.nodes
            return node
        # whole-query fusion (engine/fused.py): an eligible block tree
        # compiles into ONE jitted program — zero host round-trips
        # between levels. None → the staged path below, bit-identical.
        from dgraph_tpu.engine.fused import try_fused
        fused_node = try_fused(self, sg)
        if fused_node is not None:
            from dgraph_tpu.engine import feat
            if feat.needs_msgpass(sg):
                # the fused featprop stage binds recurse levels
                # in-trace; anything it didn't claim aggregates here
                feat.annotate_tree(self, fused_node)
            return fused_node
        return self._run_staged(sg)

    def _run_staged(self, sg: SubGraph) -> LevelNode:
        """A root block level by level on the host's side of the store
        (what _run_block does where no fused program takes the block)."""
        display = self.root_display(sg)
        nodes = np.unique(display).astype(np.int32)
        node = LevelNode(sg=sg, nodes=nodes, display=display.astype(np.int32))
        if sg.var_name:
            self.uid_vars[sg.var_name] = nodes
        if sg.groupby:
            from dgraph_tpu.engine.groupby import process_groupby
            node.groups = process_groupby(self, node)
            return node
        self._descend(node)
        from dgraph_tpu.engine import feat
        if feat.needs_msgpass(sg):
            feat.annotate_tree(self, node)
        return node

    def root_display(self, sg: SubGraph) -> np.ndarray:
        """Root evaluation through ordering + pagination → the block's
        ordered display list (run_block's root half; also the seed set
        the lane-batch planner packs into kernel lanes)."""
        ranks = self.root_ranks(sg)
        ranks = self.apply_filter(sg.filters, ranks)
        display = self._mesh_order_topk(sg, ranks)
        if display is None:
            order_idx = (self.order_ranks(ranks, sg.orders)
                         if sg.orders else np.arange(len(ranks)))
            display = ranks[order_idx]
        page = self.paginate(len(display), sg, display)
        return display[page].astype(np.int32)

    def _descend(self, parent: LevelNode) -> None:
        from dgraph_tpu.engine.recurse import expand_recurse
        if parent.sg.recurse is not None:
            expand_recurse(self, parent)
            return
        for child_sg in self._concrete_children(parent):
            if self._expands(child_sg):
                parent.children.append(self.run_child(child_sg, parent.nodes))
            else:
                parent.leaf_sgs.append(child_sg)
                self._record_leaf_vars(child_sg, parent)

    def run_child(self, sg: SubGraph, frontier: np.ndarray) -> LevelNode:
        """Expand one uid-predicate child level below `frontier`."""
        nbrs, seg, pos, processed = self._level_edges(sg, frontier)
        return self._finish_child(sg, nbrs, seg, pos, processed)

    def _level_edges(self, sg: SubGraph, frontier: np.ndarray):
        """One child level's filtered edge list → (nbrs, seg, pos,
        processed). `processed` means ordering/pagination were already
        applied (the fused device path, which is only eligible when no
        ordering exists). The lane-batch executor overrides this with
        mask-constrained CSR intersection (engine/treebatch.py)."""
        # per-level cancellation point — the acceptance granularity: a
        # deep tree stops within ONE level of its budget expiring
        dl.checkpoint("level")
        with tracing.span("engine.level", pred=sg.attr,
                          frontier=int(len(frontier))) as sp:
            fused = self._fused_level(sg, frontier)
            if fused is not None:
                sp.attrs["path"] = "fused"
                sp.attrs["edges"] = int(len(fused[0]))
                if len(fused[0]):
                    METRICS.inc("edges_traversed_total",
                                float(len(fused[0])), path="fused")
                    costprofile.add("edges_traversed",
                                    int(len(fused[0])))
                    costprofile.add("bytes_gathered",
                                    16 * int(len(fused[0])))
                return (*fused, True)
            nbrs, seg, pos = self.expand(
                sg.attr, sg.is_reverse, frontier,
                allow_remote=not _needs_facets(sg))
            nbrs, seg, pos = self.filter_edges(sg.filters, nbrs, seg, pos)
            nbrs, seg, pos = self.facet_filter_edges(sg, sg.attr, nbrs,
                                                     seg, pos)
            sp.attrs["edges"] = int(len(nbrs))
            return nbrs, seg, pos, False

    def _finish_child(self, sg: SubGraph, nbrs, seg, pos,
                      processed: bool) -> LevelNode:
        """Ordering, per-row pagination, node building, var binding and
        descent below one expanded level (run_child's second half)."""
        if not processed:
            # row-internal ordering (default: uid order from the CSR)
            if sg.orders or sg.facet_orders:
                if sg.facet_orders:
                    order_idx = self._facet_order(sg, nbrs, seg, pos)
                else:
                    order_idx = self._mesh_row_order(sg, nbrs, seg)
                    if order_idx is None:
                        order_idx = self.order_ranks(nbrs, sg.orders,
                                                     seg=seg)
                nbrs, seg = nbrs[order_idx], seg[order_idx]
                pos = pos[order_idx] if len(pos) else pos
            # per-row pagination (seg is nondecreasing: CSR construction
            # order, preserved by masking; lexsort keys on seg first)
            if sg.first or sg.offset or sg.after:
                rows = np.unique(seg)
                starts = np.searchsorted(seg, rows)
                ends = np.searchsorted(seg, rows, "right")
                keep_idx = []
                for s, e in zip(starts.tolist(), ends.tolist()):
                    row_idx = np.arange(s, e)
                    keep_idx.append(
                        row_idx[self.paginate(e - s, sg, nbrs[row_idx])])
                if keep_idx:
                    keep_idx = np.sort(np.concatenate(keep_idx))
                    nbrs, seg = nbrs[keep_idx], seg[keep_idx]
                    pos = pos[keep_idx] if len(pos) else pos
        nodes = np.unique(nbrs).astype(np.int32)
        node = LevelNode(sg=sg, nodes=nodes,
                         matrix_seg=seg.astype(np.int32),
                         matrix_child=nbrs.astype(np.int32),
                         matrix_pos=pos)
        if sg.var_name:
            self.uid_vars[sg.var_name] = nodes
        if sg.facet_vars:
            self._bind_facet_vars(sg, nbrs, pos)
        if sg.groupby:
            from dgraph_tpu.engine.groupby import process_groupby_rows
            node.groups = process_groupby_rows(self, node)
            return node
        self._descend(node)
        return node

    def _mesh_order_topk(self, sg: SubGraph, ranks: np.ndarray):
        """Order-by pushdown on the mesh (reference: SortOverNetwork):
        single-key `orderasc/orderdesc` runs as per-shard top-k + on-mesh
        merge — capped when `first` bounds the result, full-length
        otherwise (orderdesc+offset, no-first). String keys ride a
        rank-dictionary float column. Returns the ordered display list,
        or None → host ordering path."""
        if (self.mesh is None or len(sg.orders) != 1
                or sg.first < 0 or sg.after
                or len(ranks) < self.device_threshold):
            return None
        o = sg.orders[0]
        if o.is_val_var:
            return None
        from dgraph_tpu.parallel.dsort import mesh_topk
        k = (sg.first + max(sg.offset, 0)) if sg.first else len(ranks)
        return mesh_topk(self.mesh, self.store, o.attr, o.lang,
                         ranks, k, desc=o.desc)

    def _mesh_row_order(self, sg: SubGraph, nbrs: np.ndarray,
                        seg: np.ndarray):
        """Child-level (per-row) order-by on the mesh: the whole edge list
        sorts by (row, key, uid) in one SPMD program (reference:
        worker/sort.go per-group sort + coordinator merge). None → host
        lexsort path."""
        if (self.mesh is None or len(sg.orders) != 1 or sg.facet_orders
                or len(nbrs) < self.device_threshold):
            return None
        o = sg.orders[0]
        if o.is_val_var:
            return None
        from dgraph_tpu.parallel.dsort import mesh_row_sort
        return mesh_row_sort(self.mesh, self.store, o.attr, o.lang,
                             nbrs, seg, desc=o.desc)

    def _fused_level(self, sg: SubGraph, frontier: np.ndarray):
        """Large-frontier fast path: expand → filter → paginate → dedupe
        fused into ONE jitted program (ops.level.expand_level); the only
        host work is evaluating the filter tree to a sorted allowed set.
        Returns (nbrs, seg, pos) or None when ineligible (ordering, facet
        filters and `after` cursors need per-edge host logic)."""
        if (len(frontier) < self.device_threshold
                or sg.orders or sg.facet_orders or sg.after
                or sg.facet_filter is not None):
            return None
        rel = self.store.rel(sg.attr, sg.is_reverse)
        if len(frontier) == 0 or rel.nnz == 0:
            return None if rel.nnz else (EMPTY, EMPTY, EMPTY64)
        from dgraph_tpu.ops.level import NO_LIMIT, expand_level

        use_allowed = sg.filters is not None
        if use_allowed:
            # universe-free allowed set: index lookups only, so host cost
            # tracks the filter's selectivity, not n_nodes. Complement-
            # shaped trees (`not`) fall back to the gathered-neighbor path.
            allowed = self.filter_set(sg.filters)
            if allowed is None:
                return None
        else:
            allowed = EMPTY
        # host pads for a mesh launch (see pad_host), device pads for
        # the single-device program
        pad = pad_host if self.mesh is not None else ops.pad_to
        allowed_d = pad(allowed, _bucket(max(len(allowed), 1))
                        if use_allowed else 1)
        first = sg.first if sg.first else NO_LIMIT
        fr = pad(frontier, _bucket(len(frontier)))
        deg = rel.degree(frontier)
        if self.mesh is not None:
            return self._fused_level_mesh(sg, frontier, fr, deg, allowed_d,
                                          first, use_allowed)
        indptr, indices = self.store.device_rel(sg.attr, sg.is_reverse)
        ecap = _bucket(max(int(deg.sum()), 1))
        with jit_call("level.expand_level",
                      (int(indptr.shape[0]), int(fr.shape[0]),
                       int(allowed_d.shape[0]), ecap, use_allowed)):
            c_nbrs, c_seg, c_pos, n_kept, _nxt, _nu, total = expand_level(
                indptr, indices, fr, allowed_d,
                np.int32(sg.offset), np.int32(first),
                edge_cap=ecap, out_cap=ecap, use_allowed=use_allowed)
        n = int(n_kept)
        assert int(total) <= ecap, (int(total), ecap)
        return (np.asarray(c_nbrs)[:n], np.asarray(c_seg)[:n],
                np.asarray(c_pos)[:n].astype(np.int64))

    def _fused_level_mesh(self, sg: SubGraph, frontier, fr, deg, allowed_d,
                          first, use_allowed: bool):
        """Fused level on the mesh: expand+filter+paginate per shard in one
        SPMD program, host only reassembles row order (the served-mesh
        seam; reference: pushdown into each group's processTask)."""
        from dgraph_tpu.parallel.dhop import matrix_level

        srel = self.store.sharded_rel(sg.attr, sg.is_reverse, self.mesh)
        edge_cap = self._shard_edge_cap(srel, frontier, deg)
        from dgraph_tpu.parallel.mesh import host_np
        nbrs_s, seg_s, pos_s, kept, totals, max_shard = matrix_level(
            self.mesh, srel, fr, allowed_d, sg.offset, first,
            edge_cap, use_allowed)
        assert int(host_np(max_shard)) <= edge_cap, edge_cap
        self._note_mesh_shards(host_np(totals))
        METRICS.inc("mesh_route_total", route="fused")
        return self._reassemble_shards(srel, nbrs_s, seg_s, pos_s, kept)

    # -- leaves, vars, expand(_all_) ----------------------------------------
    def _concrete_children(self, parent: LevelNode) -> list[SubGraph]:
        """Resolve expand(_all_)/expand(Type) into concrete child blocks.
        Reference: query/expand.go semantics via type system."""
        out: list[SubGraph] = []
        for c in parent.sg.children:
            if not c.is_expand_all:
                out.append(c)
                continue
            if c.expand_arg and c.expand_arg != "_all_":
                preds = self.store.predicates_of_types([c.expand_arg])
            else:
                type_names: set[str] = set()
                for r in parent.nodes:
                    type_names.update(
                        self.store.values_for("dgraph.type", int(r)))
                preds = self.store.predicates_of_types(sorted(type_names))
            for p in preds:
                ps = self.store.schema.peek(p)
                if ps and ps.kind == Kind.UID:
                    out.append(SubGraph(attr=p, children=list(c.children)))
                else:
                    out.append(SubGraph(attr=p))
        return out

    def _expands(self, sg: SubGraph) -> bool:
        return expands(self.store.schema, sg)

    def _record_leaf_vars(self, sg: SubGraph, parent: LevelNode) -> None:
        """Bind value/count vars declared on leaves (a as age, c as count(p))."""
        if not sg.var_name:
            return
        if sg.is_uid_leaf and not sg.is_count:
            # `v as uid` binds the enclosing block's uid set (reference:
            # gql uid var on the uid field — the upsert-block idiom);
            # `c as count(uid)` stays a value var (the count branch below)
            self.uid_vars[sg.var_name] = parent.nodes
            return
        if sg.is_count:
            rel = self.store.rel(sg.attr, sg.is_reverse)
            deg = rel.degree(parent.nodes)
            self.val_vars[sg.var_name] = {
                int(r): int(d) for r, d in zip(parent.nodes, deg)}
        elif sg.math_expr is not None:
            from dgraph_tpu.engine.mathexpr import eval_math
            self.val_vars[sg.var_name] = eval_math(
                sg.math_expr, parent.nodes, self.val_vars)
        elif sg.is_val_leaf:
            src = self.val_vars.get(sg.attr, {})
            self.val_vars[sg.var_name] = {
                int(r): src[int(r)] for r in parent.nodes if int(r) in src}
        else:
            env: dict[int, object] = {}
            for r in parent.nodes:
                vs = self.store.values_for(sg.attr, int(r), sg.lang)
                if vs:
                    env[int(r)] = vs[0]
            self.val_vars[sg.var_name] = env


def _needs_facets(sg) -> bool:
    """Whether a block consumes edge positions (facet render/filter/order)
    — remote per-hop results carry none."""
    return (sg.facet_keys is not None or sg.facet_filter is not None
            or sg.facet_vars is not None or bool(sg.facet_orders))


def expands(schema, sg: SubGraph) -> bool:
    """Whether a child block triggers uid expansion (vs a value leaf).
    Schema-driven, as the reference routes by tablet type. Shared by the
    executor and the batch planner — the routing rule must never fork."""
    if (sg.is_count or sg.is_uid_leaf or sg.is_agg or sg.is_val_leaf
            or sg.math_expr is not None):
        return False
    if sg.is_reverse or sg.children or sg.recurse or sg.shortest:
        return True
    ps = schema.peek(sg.attr)
    return bool(ps and ps.kind == Kind.UID)


def _coerce_to(want, v):
    """Coerce a parsed (string) comparison arg to the facet value's type
    (reference: facets are typed per-posting; filter args convert to them)."""
    if not isinstance(want, str):
        return want
    try:
        if isinstance(v, (bool, np.bool_)):
            return want.strip().lower() in ("true", "1")
        if isinstance(v, (int, np.integer)):
            return int(want)
        if isinstance(v, (float, np.floating)):
            return float(want)
    except ValueError:
        pass
    return want


def _column_keys(col, idx: np.ndarray, has: np.ndarray):
    """Keys that order the rows `idx` of a typed column as `_value_keys`'
    value-by-value keys do, by whole arrays: what `_orderable` makes of
    each value (for a column of `str`, its place among the column's
    distinct strings: `ValueColumn.order_codes`), the first present
    value's key where `has` is false. None where the column's values are
    of a kind, or of mixed kinds, that only the value-by-value path
    settles."""
    vals = col.vals
    if not has.any() or vals.dtype.kind not in "iufMbO":
        return None
    if vals.dtype.kind == "O":
        codes = col.order_codes()
        if codes is None:
            return None
        keys = codes[idx]
    else:
        keys = vals[idx]
        if keys.dtype.kind == "M":
            keys = keys.astype("datetime64[us]").astype("int64")
        elif keys.dtype.kind == "b":
            keys = keys.astype(np.int64)
    if not has.all():
        keys[~has] = keys[has][0]
    return keys


def _orderable(v):
    import numpy as _np
    if isinstance(v, _np.datetime64):
        return v.astype("datetime64[us]").astype("int64")
    if isinstance(v, (bool, _np.bool_)):
        return int(v)
    return v


def _negate_key(k: np.ndarray) -> np.ndarray:
    if k.dtype.kind in "if":
        return -k
    # strings: lexsort can't negate; invert via rank mapping
    uniq, inv = np.unique(k, return_inverse=True)
    return (len(uniq) - 1 - inv).astype(np.int64)
