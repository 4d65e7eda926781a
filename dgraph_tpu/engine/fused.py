"""Whole-query fused compilation: ONE XLA program per query shape.

The ROADMAP fusion item (FeatGraph + "Fast Training of Sparse GNNs on
Dense Hardware", PAPERS): small-frontier queries are dominated by host
dispatch, not device work — the staged path launches a separate kernel
per level (hop, filter mask, merge), with host round-trips between
launches; PR 13's `kernel_launches`/`launch_gap_us` cost features
measure exactly that overhead. FeatGraph's kernel-template insight
applied to (hop × filter × aggregate): this module compiles an entire
parsed block tree into ONE jitted program per shape fingerprint —

* hop levels chain the PR-7 segment-CSR gather (`ops.hop.gather_edges`)
  and the fused filter+paginate body (`ops.level.filter_paginate`) as
  INLINED stages of one trace, each stage consuming the previous
  stage's on-device deduped frontier (`sort_unique_count`) — zero host
  round-trips between levels;
* `@filter(eq(...))`-style predicate trees evaluate host-side to a
  sorted allowed set (index lookups, `Executor.filter_set`) and fuse
  into the gather keep-mask;
* `@recurse` runs as a `lax.scan` over the PR-10 chain-hop body
  (`ops.recurse.masked_hop`: gather → allowed mask → visited-bitmap
  subtraction → dedupe), static depth, per-hop edge matrices kept for
  rendering;
* terminal `count(pred)` aggregation (`c as count(friend)`) is a final
  degree segment-reduce over the parent stage's nodes.

Compiled programs are cached per static signature riding the PR-7
`utils/jitcache.Memo`, with per-SHAPE-fingerprint hit/miss/compile-µs
accounting (`engine.shape_of` vocabulary — the same key the cost
digests use) surfaced at `/debug/costs` and `/debug/scheduler`. Route
selection is fused-first behind the default-on `DGRAPH_TPU_FUSED` flag
with a STICKY per-shape fail-safe (the Pallas-fallback pattern): a
shape whose program fails to trace/compile falls back to the staged
path forever (this process) and is counted, never served wrong or
slow-by-crash-loop. Fused requests record `shape="fused"` components
with `kernel_launches == 1`, so `utils/costprior.py` learns
per-PROGRAM cost for fused shapes and admission/batching predictions
sharpen for free.

Static caps ride the established overflow contract (ops.hop): edge
caps are estimated from root degrees + average-degree bounds, checked
against the true totals the program reports, and regrown geometrically
on overflow; the last good caps are memoized per signature so a warmed
shape is exactly one launch per query.

`STAGE_KINDS` is the fused-program inventory — ONE vocabulary, two
consumers (the `cost_record_fields` pattern): `analysis/facts.py`
re-exports it verbatim and `tests/test_lint.py` pins it against the
runtime stage-emitter registry (`_STAGE_EMITTERS`) in both directions.
This module keeps its imports jax-free at top level so the analysis
CLI can read the inventory without pulling the device stack.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field

import numpy as np

from dgraph_tpu.utils import costprofile, locks, memgov, tracing
from dgraph_tpu.utils import deadline as dl
from dgraph_tpu.utils.jitcache import Memo, jit_call
from dgraph_tpu.utils.metrics import MAX_LABEL_SETS, METRICS

__all__ = ["STAGE_KINDS", "FusedPlan", "enabled", "plan_block",
           "try_fused", "status", "reset"]

# the fused-program inventory: every stage kind the plan compiler can
# emit, with its one-liner. facts re-exports this verbatim; the
# runtime emitter registry (_STAGE_EMITTERS, below) is pinned to it
# both ways by tests/test_lint.py — a stage the compiler emits but the
# inventory doesn't name (or vice versa) fails tier-1.
STAGE_KINDS: dict[str, str] = {
    "hop": ("one child level: segment-CSR gather + fused filter mask "
            "+ on-device pagination + dedupe into the next frontier"),
    "recurse": ("depth-bounded visit-once @recurse as a lax.scan over "
                "the masked-hop body, per-hop edge matrices kept"),
    "count": ("terminal count(pred) aggregation: per-parent-node "
              "degree segment-reduce bound to the leaf's value var"),
    "knn": ("similar_to seed selection: scored matmul over the vector "
            "tablet + deterministic top-k (tie-break by uid) emitting "
            "the root frontier in-trace — the GraphRAG flagship shape "
            "(knn → recurse → filter → count) is ONE program"),
    "featprop": ("@msgpass feature propagation over a scanned recurse "
                 "stage: per-hop segment-combine (sum/mean/max) of the "
                 "kept edges' neighbour feature rows against the "
                 "resident vector tablet — GNN-style message passing "
                 "inside the same single dispatch"),
}

# depth bound for the scanned recurse stage (shares the host guard)
MAX_FUSED_DEPTH = 64
_MAX_ATTEMPTS = 16       # geometric cap growth, bounded


def enabled() -> bool:
    """Default-ON flag: DGRAPH_TPU_FUSED=0 pins every query to the
    staged path (an A/B toggles this in a child). Read per call
    so a subprocess A/B needs no re-import."""
    return os.environ.get("DGRAPH_TPU_FUSED", "1") != "0"


@dataclass(frozen=True)
class _Stage:
    kind: str            # STAGE_KINDS key
    attr: str
    reverse: bool
    parent: int          # producing stage index; -1 = the root frontier
    has_filter: bool = False
    depth: int = 0       # recurse only
    k: int = 0           # knn only: requested seed count
    agg: str = ""        # featprop only: sum | mean | max

    def sig(self) -> tuple:
        return (self.kind, self.attr, self.reverse, self.parent,
                self.has_filter, self.depth, self.k, self.agg)


@dataclass
class FusedPlan:
    """The compiled-plan IR: stages in DFS pre-order (parents before
    children — the order `Executor._descend` would have executed)."""

    stages: list[_Stage] = field(default_factory=list)
    stage_sgs: list = field(default_factory=list)   # SubGraph per stage
    children_of: dict[int, list[int]] = field(default_factory=dict)
    # parent stage idx → {id(leaf sg): count stage idx}
    counts_of: dict[int, dict[int, int]] = field(default_factory=dict)
    recurse: bool = False
    knn: bool = False    # stage 0 is a knn seed stage
    featprop: bool = False  # a @msgpass stage rides the recurse scan

    @property
    def sig(self) -> tuple:
        return tuple(st.sig() for st in self.stages)


class _Ineligible(Exception):
    pass


def _filter_fusable(tree) -> bool:
    """Whether a filter tree evaluates to a host allowed set that can
    fuse into the gather mask: no complement (`not` needs a universe),
    and no leaves reading variables that could be bound INSIDE this
    block (the staged path evaluates them mid-descent; the fused
    program evaluates every allowed set up front)."""
    if tree is None:
        return True
    if tree.op == "not":
        return False
    if tree.op == "leaf":
        f = tree.func
        if f.is_val_var:
            return False
        if f.name == "uid" and f.args:
            return False
        return True
    return all(_filter_fusable(c) for c in tree.children)


def _stage_ok(c) -> bool:
    """Per-child eligibility for a hop stage: everything needing
    per-edge host logic mid-descent stays staged."""
    return not (c.recurse is not None or c.shortest is not None
                or c.msgpass is not None
                or c.groupby or c.is_expand_all
                or c.orders or c.facet_orders or c.after
                or c.facet_vars is not None or c.facet_filter is not None
                or not _filter_fusable(c.filters))


def plan_block(store, sg) -> FusedPlan | None:
    """Walk one parsed root block into a FusedPlan, or None when any
    part needs the staged path (README "Whole-query fusion" documents
    the eligibility rules)."""
    from dgraph_tpu.engine.execute import expands

    if sg.shortest is not None or sg.groupby:
        return None

    knn_stage = _plan_knn(store, sg)

    if sg.recurse is not None:
        a = sg.recurse
        if a.loop or not a.depth or a.depth > MAX_FUSED_DEPTH:
            return None
        edge = [c for c in sg.children if expands(store.schema, c)]
        if len(edge) != 1:
            return None
        e = edge[0]
        if (e.is_expand_all or e.facet_filter is not None
                or e.msgpass is not None
                or not _filter_fusable(e.filters)):
            return None
        plan = FusedPlan(recurse=True, knn=knn_stage is not None)
        if knn_stage is not None:
            plan.stages.append(knn_stage)
            plan.stage_sgs.append(sg)
        root_parent = 0 if plan.knn else -1
        plan.stages.append(_Stage("recurse", e.attr, e.is_reverse,
                                  root_parent,
                                  e.filters is not None, a.depth))
        plan.stage_sgs.append(e)
        mp = sg.msgpass
        if mp is not None:
            fp = _plan_featprop(store, mp, len(plan.stages) - 1)
            if fp is None:
                return None   # staged serves (and raises user errors)
            plan.stages.append(fp)
            plan.stage_sgs.append(sg)
            plan.featprop = True
        return plan

    if sg.msgpass is not None:
        # plain-level @msgpass aggregates host-side after the staged
        # descent (the post-pass routes it like any other level)
        return None

    plan = FusedPlan(knn=knn_stage is not None)
    root_parent = -1
    if knn_stage is not None:
        plan.stages.append(knn_stage)
        plan.stage_sgs.append(sg)
        root_parent = 0

    def walk(node_sg, parent: int) -> None:
        for c in node_sg.children:
            if expands(store.schema, c):
                if not _stage_ok(c):
                    raise _Ineligible
                i = len(plan.stages)
                plan.stages.append(_Stage("hop", c.attr, c.is_reverse,
                                          parent,
                                          c.filters is not None))
                plan.stage_sgs.append(c)
                plan.children_of.setdefault(parent, []).append(i)
                walk(c, i)
            elif (c.is_count and not c.is_uid_leaf and c.var_name
                  and c.attr):
                i = len(plan.stages)
                plan.stages.append(_Stage("count", c.attr,
                                          c.is_reverse, parent))
                plan.stage_sgs.append(c)
                plan.counts_of.setdefault(parent, {})[id(c)] = i
            # other leaves (values, vars, aggregates) bind host-side

    try:
        walk(sg, root_parent)
    except _Ineligible:
        return None
    if not plan.knn and not any(st.kind == "hop" for st in plan.stages):
        return None    # nothing device-bound to fuse
    return plan


def _plan_knn(store, sg) -> _Stage | None:
    """A similar_to root compiles to an in-trace knn seed stage when
    the root level itself is plain: root filters/ordering/pagination
    reorder or trim the SEED SET host-side, so those shapes keep the
    staged (routed) seed and fuse only below it. k must be a static
    positive int at plan time; query-vector resolution stays at run
    time (_run_plan) where a structural empty can still fall back."""
    from dgraph_tpu.store.types import Kind

    f = sg.func
    if f is None or f.name != "similar_to":
        return None
    if (sg.filters is not None or sg.orders or sg.first or sg.offset
            or sg.after):
        return None
    ps = store.schema.peek(f.attr)
    if ps is None or ps.kind != Kind.VECTOR:
        return None
    try:
        k = int(f.args[0])
    except (IndexError, TypeError, ValueError):
        return None    # malformed: the staged route raises the error
    if k <= 0 or len(f.args) != 2:
        return None
    return _Stage("knn", f.attr, False, -1, False, 0, k)


def _plan_featprop(store, mp, recurse_idx: int) -> _Stage | None:
    """@msgpass on a fused recurse block compiles to a featprop stage
    when the feature predicate really is a vector and the agg is one
    the kernel family emits; anything else keeps the staged path
    (which raises the user-facing errors)."""
    from dgraph_tpu.store.types import Kind

    if mp.agg not in ("sum", "mean", "max"):
        return None
    ps = store.schema.peek(mp.pred)
    if ps is None or ps.kind != Kind.VECTOR:
        return None
    return _Stage("featprop", mp.pred, False, recurse_idx, False, 0, 0,
                  mp.agg)


# -- the program builder ------------------------------------------------------
# one emitter per STAGE_KINDS entry; the registry IS the runtime half
# of the inventory pin (tests/test_lint.py, both directions)

def _emit_hop(st: _Stage, caps: tuple, arrays, frontier, parent_out):
    """Emit one hop stage into the open trace; returns (outputs,
    next_frontier). Pure — runs under jax.jit."""
    from dgraph_tpu.ops.hop import gather_edges
    from dgraph_tpu.ops.level import filter_paginate
    from dgraph_tpu.ops.uidalgebra import sort_unique_count

    (indptr, indices), allowed, (offset, first) = arrays
    (edge_cap,) = caps
    nbrs, seg, pos, valid, total = gather_edges(
        indptr, indices, frontier, edge_cap)
    c_nbrs, c_seg, c_pos, n_kept, m_nbrs = filter_paginate(
        nbrs, seg, pos, valid, allowed, offset, first,
        frontier.shape[0], st.has_filter)
    # the next frontier dedupes the KEPT edges (post filter+page), the
    # exact set the staged path's np.unique(nbrs) would produce; it can
    # never overflow edge_cap, so out_cap == edge_cap is safe
    nxt, n_unique = sort_unique_count(m_nbrs, edge_cap)
    return (c_nbrs, c_seg, c_pos, n_kept, nxt, n_unique, total), nxt


def _emit_recurse(st: _Stage, caps: tuple, arrays, frontier, parent_out):
    """Emit the scanned visit-once @recurse stage: `depth` masked hops
    with the seen bitmap carried on device, per-hop edge matrices and
    input frontiers kept for host rendering."""
    import jax.numpy as jnp
    from jax import lax

    from dgraph_tpu.ops.recurse import masked_hop

    from dgraph_tpu.ops.uidalgebra import pad_to

    (indptr, indices), allowed, _page = arrays
    edge_cap, out_cap = caps
    n_nodes = indptr.shape[0] - 1
    if frontier.shape[0] < out_cap:
        # knn-fed: the seed stage's cap is narrower than the scan's
        # carry buffer — sentinel-pad in-trace (sorted sets keep their
        # sentinels trailing, so this is shape-only)
        frontier = pad_to(frontier, out_cap)

    def hop(carry, _):
        fr, seen = carry
        c_nbrs, c_seg, n_kept, nxt, n_unique, seen, total = masked_hop(
            indptr, indices, fr, allowed, seen, edge_cap, out_cap,
            st.has_filter)
        return (nxt, seen), (c_nbrs, c_seg, n_kept, fr, n_unique, total)

    seen0 = jnp.zeros((n_nodes,), jnp.int8).at[frontier].set(
        jnp.int8(1), mode="drop")
    (_last, _seen), ys = lax.scan(hop, (frontier, seen0), None,
                                  length=st.depth)
    nbrs_h, seg_h, kept_h, fr_h, uniq_h, tot_h = ys
    # tot_h/uniq_h are the [depth] per-hop true sizes: their maxima are
    # the overflow-contract needs, their sum the north-star edge count
    return (nbrs_h, seg_h, kept_h, fr_h, tot_h, uniq_h), None


def _emit_count(st: _Stage, caps: tuple, arrays, frontier, parent_out):
    """Emit the terminal aggregation stage: per-parent-node degree of
    the counted predicate — a segment-reduce over indptr aligned to the
    parent's padded node array."""
    from dgraph_tpu.ops.hop import frontier_degrees

    (indptr, _indices), _allowed, _page = arrays
    return (frontier_degrees(indptr, frontier),), None


def _emit_knn(st: _Stage, caps: tuple, arrays, frontier, parent_out):
    """Emit the similar_to seed stage: scored matmul over the resident
    [n, d] stack, deterministic top-k (score desc, uid asc — the exact
    numpy-lexsort order of the host reference), emitted as a SORTED
    sentinel-padded uid set so downstream stages consume it like any
    frontier. Ignores the program's root `frontier` input."""
    import jax.numpy as jnp

    from dgraph_tpu.ops.uidalgebra import SENTINEL32

    (subj, vecs), q, _page = arrays
    (out_cap,) = caps
    scores = vecs @ q
    # -scores is an exact f32 sign flip, so this is bit-identical to
    # the host np.lexsort((subj, -scores)) total order
    order = jnp.lexsort((subj, -scores))
    k = min(st.k, int(subj.shape[0]))    # static: k > n clamps
    top = subj[order[:k]]
    nxt = jnp.sort(jnp.concatenate(
        [top, jnp.full((out_cap - k,), SENTINEL32, jnp.int32)]))
    return (nxt, jnp.int32(k)), nxt


def _emit_featprop(st: _Stage, caps: tuple, arrays, frontier,
                   parent_out):
    """Emit the @msgpass stage: vmap the segment-combine kernel over
    the recurse scan's per-hop kept-edge matrices. `parent_out` is the
    recurse stage's output; each hop aggregates its kept edges'
    neighbour feature rows per input-frontier position — visit-once
    expansion puts every parent's whole edge set in exactly one hop,
    so the per-hop combine equals the staged global combine."""
    import jax
    import jax.numpy as jnp

    from dgraph_tpu.ops.feat import segment_combine

    (subj, vecs), _allowed, _page = arrays
    nbrs_h, seg_h, kept_h, fr_h, _tot, _uniq = parent_out
    edge_cap = nbrs_h.shape[1]
    out_cap = fr_h.shape[1]

    def one(nbrs, seg, kept):
        valid = jnp.arange(edge_cap, dtype=jnp.int32) < kept
        return segment_combine(subj, vecs, nbrs, seg, valid, out_cap,
                               st.agg)

    feats, cnt, ecnt = jax.vmap(one)(nbrs_h, seg_h, kept_h)
    return (feats, cnt, ecnt), None


_STAGE_EMITTERS = {
    "hop": _emit_hop,
    "recurse": _emit_recurse,
    "count": _emit_count,
    "knn": _emit_knn,
    "featprop": _emit_featprop,
}


def _build_program(stages: tuple, caps: tuple):
    """Close over the static plan and return ONE jitted callable whose
    trace chains every stage — the whole-query program. Inputs are
    pytrees aligned with `stages`: per-stage (indptr, indices) CSR
    pairs, the padded root frontier, per-stage padded allowed sets
    (1-wide dummies when unused), and per-stage (offset, first) int32
    pairs."""
    import jax

    def fused_program(rels, frontier, alloweds, pages):
        outs = []
        stage_frontier = [None] * len(stages)
        for i, st in enumerate(stages):
            fr = frontier if st.parent < 0 else stage_frontier[st.parent]
            p_out = outs[st.parent] if st.parent >= 0 else None
            out, nxt = _STAGE_EMITTERS[st.kind](
                st, caps[i], (rels[i], alloweds[i], pages[i]), fr,
                p_out)
            stage_frontier[i] = nxt
            outs.append(out)
        return tuple(outs)

    return jax.jit(fused_program)


# -- program + caps caches, per-shape accounting ------------------------------

# a compiled program's true footprint (host executable + reserved HBM)
# is opaque to python; this nominal per-entry charge makes the memo
# byte-bounded under the governor with honest RELATIVE pressure
_PROGRAM_NBYTES_EST = 256 << 10

_programs = Memo("fused.program", capacity=128, governed="fused.program")
_lock = locks.make_lock("fused.registry")
_caps_memo: dict = {}     # plan sig → last good caps (under _lock)
_shapes: dict = {}        # shape fingerprint → stats dict (under _lock)


def _shape_entry(shape: str) -> dict:
    """Per-shape accounting row (caller holds `_lock`); cardinality is
    bounded the metrics way — novel shapes past the cap collapse."""
    if shape not in _shapes and len(_shapes) >= MAX_LABEL_SETS:
        shape = costprofile.OVERFLOW_SHAPE
    e = _shapes.get(shape)
    if e is None:
        e = _shapes[shape] = {"hits": 0, "misses": 0, "compile_us": 0,
                              "disabled": False}
    return e


def _is_disabled(shape: str) -> bool:
    with _lock:
        return bool(_shapes.get(shape, {}).get("disabled"))


def _disable(shape: str) -> None:
    with _lock:
        _shape_entry(shape)["disabled"] = True
    METRICS.set_gauge("fused_degraded", 1.0)


def _program_for(shape: str, sig: tuple, caps: tuple):
    key = (sig, caps)
    fn = _programs.get(key)
    if fn is not None:
        with _lock:
            _shape_entry(shape)["hits"] += 1
        METRICS.inc("fused_program_hits_total")
        return fn
    METRICS.inc("fused_program_misses_total")
    t0 = time.perf_counter()
    fn = _build_program(tuple(_Stage(*s) for s in sig), caps)
    _programs.put(key, fn, nbytes=_PROGRAM_NBYTES_EST,
                  rebuild_us=(time.perf_counter() - t0) * 1e6)
    memgov.GOVERNOR.maybe_evict("host")
    with _lock:
        e = _shape_entry(shape)
        e["misses"] += 1
        e["compile_us"] += int((time.perf_counter() - t0) * 1e6)
    return fn


def _note_compile(shape: str, us: float) -> None:
    """Fold the first-dispatch trace+compile time (measured by the
    jit_call wrapper's span at the launch site) into the shape row —
    the builder's own time above is only closure construction."""
    with _lock:
        _shape_entry(shape)["compile_us"] += int(us)


def status() -> dict:
    """The /debug surface: per-shape program-cache rows + route totals
    (`fused_route_total{route=}` lives in the metrics registry; this is
    the cache's own view)."""
    with _lock:
        shapes = {s: dict(e) for s, e in _shapes.items()}
    return {"enabled": enabled(), "programs": len(_programs),
            "shapes": shapes}


def reset() -> None:
    """Test hook: forget programs, caps, and per-shape stats."""
    _programs.clear()
    with _lock:
        _caps_memo.clear()
        _shapes.clear()
    METRICS.set_gauge("fused_degraded", 0.0)


# -- runtime ------------------------------------------------------------------

def try_fused(ex, sg):
    """The engine hook (`Executor._run_block`): run one root block as
    a single fused program, or return None → staged path. Counts the
    route either way (`fused_route_total{route=fused|staged|fallback}`)
    and never lets a fused failure surface: the shape goes STICKY
    fallback (the Pallas pattern) and the staged path serves."""
    if not enabled():
        return None
    if ex.mesh is not None or \
            getattr(ex.store, "remote_expand", None) is not None:
        # the mesh/cluster serving universes have their own fused
        # routes (SPMD matrix_level, ServeTask); this path is the
        # single-device program
        return None
    from dgraph_tpu.engine import shape_of
    shape = shape_of([sg])
    if _is_disabled(shape):
        METRICS.inc("fused_route_total", route="fallback")
        return None
    try:
        plan = plan_block(ex.store, sg)
        if plan is not None:
            node = _run_plan(ex, sg, plan, shape)
            if node is not None:
                METRICS.inc("fused_route_total", route="fused")
                return node
    except (dl.DeadlineExceeded, dl.Cancelled):
        raise
    except memgov.OomDegraded:
        # allocation failure survived its one evict-retry: the shape is
        # sticky-degraded (gauge + flight event recorded by the
        # governor); the staged path serves bit-identically
        _disable(shape)
        METRICS.inc("fused_fallback_total")
        from dgraph_tpu.utils import logging as xlog
        xlog.get("fused").warning(
            "fused program for shape %s oom-degraded after one "
            "evict-retry; sticky fallback to the staged path", shape)
        METRICS.inc("fused_route_total", route="fallback")
        return None
    except Exception:  # noqa: BLE001 — optimization only, never fatal
        _disable(shape)
        METRICS.inc("fused_fallback_total")
        from dgraph_tpu.utils import logging as xlog
        xlog.get("fused").warning(
            "fused program for shape %s failed; sticky fallback to the "
            "staged path (results unaffected)", shape, exc_info=True)
        METRICS.inc("fused_route_total", route="fallback")
        return None
    METRICS.inc("fused_route_total", route="staged")
    return None


def _run_plan(ex, sg, plan: FusedPlan, shape: str):
    """Host shell around the single dispatch: seed + allowed-set
    evaluation, cap policy (overflow contract), launch, unpack.
    Returns the root LevelNode, or None when a runtime condition
    (empty tablet, complement-shaped filter) needs the staged path."""
    from dgraph_tpu import ops
    from dgraph_tpu.engine.execute import _bucket
    from dgraph_tpu.ops.level import NO_LIMIT

    store = ex.store
    rels, devs, alloweds, pages = [], [], [], []
    for st, ssg in zip(plan.stages, plan.stage_sgs):
        if st.kind == "knn":
            from dgraph_tpu.store import vec
            t = store.vec_tablet(st.attr)
            if t is None or not t.rows:
                return None   # structurally empty: staged serves EMPTY
            try:
                resolved = vec.resolve_query(store, sg.func)
            except ValueError:
                return None   # malformed query: staged raises it
            if resolved is None:
                return None   # unknown uid / uid without a vector
            costprofile.note_max("tablet_rows", t.rows)
            rels.append(t)
            devs.append(store.vec_device(st.attr))
            alloweds.append(resolved[2])   # f32 query vector
            pages.append((0, NO_LIMIT))
            continue
        if st.kind == "featprop":
            t = store.vec_tablet(st.attr)
            if t is None or not t.rows:
                return None   # empty tablet: the staged post-pass
                # serves (all-zero participation) without a device stack
            costprofile.note_max("tablet_rows", t.rows)
            rels.append(t)
            devs.append(store.vec_device(st.attr))
            alloweds.append(np.zeros(0, np.int32))
            pages.append((0, NO_LIMIT))
            continue
        rel = store.rel(st.attr, st.reverse)
        if rel.nnz == 0:
            return None           # staged short-circuits empties
        costprofile.note_max("tablet_rows", int(len(rel.indptr)) - 1)
        allowed = None
        if st.has_filter:
            allowed = ex.filter_set(ssg.filters)
            if allowed is None:
                return None       # complement-shaped at runtime
        rels.append(rel)
        devs.append(store.device_rel(st.attr, st.reverse))
        alloweds.append(allowed if allowed is not None
                        else np.zeros(0, np.int32))
        first = ssg.first if (st.kind == "hop" and ssg.first) \
            else NO_LIMIT
        offset = ssg.offset if st.kind == "hop" else 0
        pages.append((offset, first))

    if plan.knn:
        # the seed set is computed IN-TRACE; root display/nodes bind
        # from the program's own knn output after the launch
        display = nodes = np.zeros(0, np.int32)
    else:
        display = ex.root_display(sg)
        nodes = np.unique(display).astype(np.int32)

    with _lock:
        caps = _caps_memo.get(plan.sig)
    if caps is None:
        caps = _estimate_caps(plan, rels, nodes)
    if plan.knn:
        # memoized caps may predate tablet growth: the seed buffer
        # must hold this snapshot's min(k, rows)
        need = _bucket(max(min(plan.stages[0].k, rels[0].rows), 1))
        if caps[0][0] < need:
            lc = list(caps)
            lc[0] = (need,)
            caps = tuple(lc)
    if plan.recurse:
        ri = 1 if plan.knn else 0
        # memoized caps may come from a smaller seed set: the scan's
        # frontier carry buffer must fit this query's roots (for a knn
        # seed, the seed stage's own cap)
        floor = max(_bucket(max(len(nodes), 1)),
                    caps[0][0] if plan.knn else 0)
        if caps[ri][1] < floor:
            lc = list(caps)
            lc[ri] = (caps[ri][0], floor)
            caps = tuple(lc)

    f_cap = _bucket(max(len(nodes), 1))
    alloweds_d = tuple(
        a if (plan.knn and i == 0)   # f32 query vector: no int32 pad
        else ops.pad_to(a, _bucket(max(len(a), 1)))
        for i, a in enumerate(alloweds))
    pages_d = tuple((np.int32(o), np.int32(f)) for o, f in pages)
    # budget gate before the device is committed: past here the whole
    # query is one uninterruptible dispatch
    dl.checkpoint("kernel")
    with tracing.span("engine.fused", shape=shape,
                      stages=len(plan.stages)) as sp:
        t_exec = time.perf_counter()
        for _attempt in range(_MAX_ATTEMPTS):
            if plan.knn:
                # stage 0 computes the seed set itself and ignores
                # this input; 1-wide dummy keeps the pytree aligned
                fr = ops.pad_to(nodes, 1)
            elif plan.recurse:
                fr = ops.pad_to(nodes, caps[0][1])
            else:
                fr = ops.pad_to(nodes, f_cap)
            program = _program_for(shape, plan.sig, caps)
            key = (plan.sig, caps, int(fr.shape[0]),
                   tuple(int(d[0].shape[0]) for d in devs),
                   tuple(int(a.shape[0]) for a in alloweds_d))
            t_launch = time.perf_counter()

            def _launch():
                memgov.check_alloc_fault("fused.program")
                with jit_call("fused.program", key) as compiling:
                    got = program(tuple(devs), fr, alloweds_d, pages_d)
                    got = [tuple(np.asarray(o) for o in out)
                           for out in got]
                return got, compiling

            # OOM lifecycle: alloc failure → evict to low watermark,
            # retry ONCE, then sticky-degrade the shape (OomDegraded
            # propagates to try_fused → staged path, bit-identical)
            outs, compiling = memgov.oom_retry("fused.program", shape,
                                               _launch)
            if compiling:
                compile_us = (time.perf_counter() - t_launch) * 1e6
                _note_compile(shape, compile_us)
                _programs.reprice(key, compile_us)
            caps, overflowed = _grow_caps(plan, caps, outs, nodes)
            if not overflowed:
                break
        else:
            raise RuntimeError("fused caps failed to converge")
        with _lock:
            _caps_memo[plan.sig] = caps
            # graftlint: allow(hot-loop-checkpoint): bounded FIFO
            # eviction of an in-memory memo, at most one entry over
            while len(_caps_memo) > 4 * MAX_LABEL_SETS:
                _caps_memo.pop(next(iter(_caps_memo)))
        exec_us = (time.perf_counter() - t_exec) * 1e6
        edges = _edges_of(plan, outs)
        sp.attrs["edges"] = edges
        costprofile.add_shape("fused")
        costprofile.add_kernel("fused", execute_us=exec_us)
        if edges:
            METRICS.inc("edges_traversed_total", float(edges),
                        path="fused")
            costprofile.add("edges_traversed", edges)
            costprofile.add("bytes_gathered", 16 * edges)
        for st, rel, out in zip(plan.stages, rels, outs):
            if st.kind == "count":
                continue
            if st.kind in ("knn", "featprop"):
                n = rel.rows   # scored/gathered rows ≈ the scan's work
            else:
                n = (int(out[6]) if st.kind == "hop"
                     else int(out[4].sum()))
            # modeled per-tablet µs, the same ~16 edges/µs scale the
            # staged expand() charges (placement signal)
            costprofile.add_tablet_cost(st.attr, n // 16 + 1)
        if plan.featprop:
            # host-side route accounting for the in-trace aggregation
            # (R13: no metrics inside the jitted program)
            fi = next(i for i, st in enumerate(plan.stages)
                      if st.kind == "featprop")
            METRICS.inc("feat_route_total", route="fused")
            part = int(outs[fi][1].sum())
            if part:
                METRICS.inc("feat_bytes_total",
                            float(part * rels[fi].dim * 4))
            METRICS.observe("featprop_latency_us", exec_us)
        if plan.knn:
            # bind the root set from the program's own seed output:
            # sorted ascending with sentinels trailing, first k_true
            # entries are the seeds — the same sorted-unique set the
            # staged root_display yields for an order-free similar_to
            k_true = int(outs[0][1])
            nodes = np.asarray(outs[0][0][:k_true], np.int32)
            display = nodes
        return _unpack(ex, sg, plan, outs, display, nodes)


def _estimate_caps(plan: FusedPlan, rels, nodes) -> tuple:
    """First-launch cap guesses: the root-fed stages are exact (their
    frontier is known), deeper stages bound by parent-estimate ×
    average degree with headroom — the overflow contract corrects any
    miss and the corrected caps are memoized per signature."""
    from dgraph_tpu.engine.execute import _bucket

    caps = []
    est_nodes = {-1: max(len(nodes), 1)}
    for i, (st, rel) in enumerate(zip(plan.stages, rels)):
        if st.kind in ("count", "featprop"):
            # capless: count reduces over the parent's frontier,
            # featprop over the recurse scan's own static matrices
            caps.append(())
            continue
        if st.kind == "knn":
            # exact: the seed stage emits at most min(k, rows) uids
            # and can never overflow (rel is the VecTablet here)
            seeds = max(min(st.k, rel.rows), 1)
            caps.append((_bucket(seeds),))
            est_nodes[i] = seeds
            continue
        n_rows = max(int(len(rel.indptr)) - 1, 1)
        if st.parent == -1 and len(nodes):
            est = int(rel.degree(nodes).sum())
        else:
            avg = rel.nnz / n_rows
            est = int(est_nodes[st.parent] * (avg + 1.0) * 2.0)
        ecap = _bucket(max(est, 1))
        if st.kind == "recurse":
            out_floor = max(len(nodes), 1)
            if st.parent >= 0:   # knn-fed: carry must fit the seeds
                out_floor = max(out_floor, caps[st.parent][0])
            caps.append((ecap, _bucket(out_floor)))
        else:
            caps.append((ecap,))
        est_nodes[i] = max(1, min(est, n_rows))
    return tuple(caps)


def _grow_caps(plan: FusedPlan, caps: tuple, outs, nodes):
    """Check the program's reported true sizes against the static caps
    and regrow geometrically where they overflowed (a truncated parent
    makes deeper totals lower bounds — the re-run loop converges
    because caps only grow)."""
    from dgraph_tpu.engine.execute import _bucket

    new_caps = list(caps)
    overflowed = False
    for i, (st, out) in enumerate(zip(plan.stages, outs)):
        if st.kind == "hop":
            total = int(out[6])
            if total > caps[i][0]:
                new_caps[i] = (_bucket(max(total, 2 * caps[i][0])),)
                overflowed = True
        elif st.kind == "recurse":
            need_edge, need_out = int(out[4].max()), int(out[5].max())
            ecap, ocap = caps[i]
            if need_edge > ecap or need_out > ocap:
                new_caps[i] = (
                    _bucket(max(need_edge, ecap)),
                    _bucket(max(need_out, ocap, len(nodes), 1)))
                overflowed = True
    return tuple(new_caps), overflowed


def _edges_of(plan: FusedPlan, outs) -> int:
    """Raw gathered edges across stages — the north-star count, the
    same pre-filter semantics `Executor.expand` charges."""
    edges = 0
    for st, out in zip(plan.stages, outs):
        if st.kind == "hop":
            edges += int(out[6])
        elif st.kind == "recurse":
            edges += int(out[4].sum())
    return edges


def _unpack(ex, sg, plan: FusedPlan, outs, display, nodes):
    """Rebuild the LevelNode tree from the program's outputs, binding
    variables in EXACTLY the order `Executor._descend` would have
    (child order within each level, whole subtrees before later
    siblings) — the bit-identity contract with the staged path."""
    from dgraph_tpu.engine.execute import LevelNode

    root = LevelNode(sg=sg, nodes=nodes,
                     display=display.astype(np.int32))
    if sg.var_name:
        ex.uid_vars[sg.var_name] = nodes
    root_idx = 0 if plan.knn else -1
    if plan.recurse:
        _unpack_recurse(ex, root, plan, outs)
        return root
    _attach(ex, plan, outs, root_idx, root)
    return root


def _attach(ex, plan: FusedPlan, outs, parent_idx: int, parent_node):
    from dgraph_tpu.engine.execute import LevelNode, expands

    hop_iter = iter(plan.children_of.get(parent_idx, ()))
    counts = plan.counts_of.get(parent_idx, {})
    for c in parent_node.sg.children:
        if expands(ex.store.schema, c):
            si = next(hop_iter)
            c_nbrs, c_seg, c_pos, n_kept, nxt, n_unique, _total = \
                outs[si]
            n = int(n_kept)
            node = LevelNode(
                sg=c,
                nodes=nxt[:int(n_unique)].astype(np.int32),
                matrix_seg=c_seg[:n].astype(np.int32),
                matrix_child=c_nbrs[:n].astype(np.int32),
                matrix_pos=c_pos[:n].astype(np.int64))
            if c.var_name:
                ex.uid_vars[c.var_name] = node.nodes
            parent_node.children.append(node)
            _attach(ex, plan, outs, si, node)
        else:
            parent_node.leaf_sgs.append(c)
            si = counts.get(id(c))
            if si is not None:
                # the fused degree reduce, aligned to the parent's
                # padded node array — same values the staged
                # _record_leaf_vars computes from rel.degree
                (deg,) = outs[si]
                ex.val_vars[c.var_name] = {
                    int(r): int(d)
                    for r, d in zip(parent_node.nodes,
                                    deg[:len(parent_node.nodes)])}
            else:
                ex._record_leaf_vars(c, parent_node)


def _unpack_recurse(ex, root, plan: FusedPlan, outs) -> None:
    """RecurseData from the scanned stage's per-hop matrices — the host
    loop's visit-once first-visit-tree semantics, hop order preserved."""
    from dgraph_tpu.engine.recurse import (RecurseData, _bind_recurse_vars,
                                           split_children)

    ri = 1 if plan.knn else 0
    nbrs_h, seg_h, kept_h, fr_h, _need_e, _need_o = outs[ri]
    data = split_children(ex, root.sg, RecurseData(loop=False))
    parts_p, parts_c = [], []
    for h in range(nbrs_h.shape[0]):
        k = int(kept_h[h])
        if not k:
            continue
        parts_p.append(fr_h[h][seg_h[h][:k]].astype(np.int32))
        parts_c.append(nbrs_h[h][:k].astype(np.int32))
    if parts_p:
        data.edges[0] = (np.concatenate(parts_p),
                         np.concatenate(parts_c))
        data.all_nodes = np.union1d(
            root.nodes, np.concatenate(parts_c)).astype(np.int32)
    else:
        data.all_nodes = root.nodes.copy()
    if plan.featprop:
        # bind the in-trace aggregation: per hop, every input-frontier
        # position with ≥ 1 kept edge carries its [d] f32 combine —
        # keyed by rank, the exact entries the staged post-pass builds
        from dgraph_tpu.engine.feat import feat_key
        feats, _cnt, ecnt = outs[ri + 1]
        fv: dict = {}
        for h in range(nbrs_h.shape[0]):
            if not int(kept_h[h]):
                continue
            fr = fr_h[h]
            for p in np.nonzero(ecnt[h] > 0)[0].tolist():
                fv[int(fr[p])] = np.asarray(feats[h][p], np.float32)
        data.feat_vals = fv
        data.feat_key = feat_key(root.sg.msgpass)
    _bind_recurse_vars(ex, root, data, root.sg)
    root.recurse_data = data
