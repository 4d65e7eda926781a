"""Batched bitmap BFS vs per-query numpy oracle.

Reference parity model: the behavior under test is expandRecurse's
loop=false frontier evolution (query/recurse.go), applied to B independent
queries at once (SURVEY §4: property-style random-graph checks as in
algo/uidlist_test.go).
"""

import functools

import numpy as np
import pytest

from dgraph_tpu.models.synthetic import powerlaw_rel, uniform_rel
from dgraph_tpu.ops.bfs import (
    build_ell, ell_recurse, pack_seed_masks, unpack_masks)


def oracle_recurse(rel, seeds, depth):
    frontier = np.unique(seeds)
    seen = frontier.copy()
    edges = 0
    for _ in range(depth):
        if not len(frontier):
            break
        parts = [rel.row(int(r)) for r in frontier]
        edges += sum(len(p) for p in parts)
        nxt = np.unique(np.concatenate(parts)) if parts else np.zeros(0, np.int64)
        frontier = np.setdiff1d(nxt, seen)
        seen = np.union1d(seen, frontier)
    return frontier, seen, edges


def lanes_recurse(rel, seed_lists, depth):
    """ell_recurse over the seed lists, padded to a whole mask word with
    empty lanes. Returns (g, last, seen, edges), the masks as the device
    left them."""
    g = build_ell(rel.indptr, rel.indices)
    lanes = list(seed_lists) + [[]] * (-len(seed_lists) % 32)
    last, seen, edges = ell_recurse(g, pack_seed_masks(g, lanes), depth)
    return g, np.asarray(last), np.asarray(seen), np.asarray(edges)


@pytest.mark.parametrize("maker,n,deg", [(powerlaw_rel, 300, 3.0),
                                         (uniform_rel, 200, 4)])
def test_ell_recurse_matches_oracle(maker, n, deg):
    rel = maker(n, deg, 3)
    rng = np.random.default_rng(0)
    B = 8
    seed_lists = [rng.integers(0, n, rng.integers(1, 6)) for _ in range(B)]

    g, last, seen, edges = lanes_recurse(rel, seed_lists, 3)
    last_l, seen_l = unpack_masks(g, last), unpack_masks(g, seen)
    for q in range(B):
        of, os_, oe = oracle_recurse(rel, seed_lists[q], 3)
        assert np.array_equal(last_l[q], of), f"query {q} frontier"
        assert np.array_equal(seen_l[q], os_), f"query {q} seen"
        assert int(edges[q]) == oe, f"query {q} edges"


def test_ell_hop_single():
    rel = uniform_rel(64, 2, 1)
    g, last, seen, _edges = lanes_recurse(rel, [[0, 5]], 1)
    want = np.unique(np.concatenate([rel.row(0), rel.row(5)]))
    # one hop's fresh set is the neighbour set less the seeds themselves
    assert np.array_equal(unpack_masks(g, last)[0],
                          np.setdiff1d(want, [0, 5]))
    assert np.array_equal(unpack_masks(g, seen)[0], np.union1d(want, [0, 5]))


def test_empty_seed_lane():
    rel = uniform_rel(32, 2, 5)
    g, _last, seen, edges = lanes_recurse(rel, [[], [3]], 2)
    assert int(edges[0]) == 0
    assert not len(unpack_masks(g, seen)[0])
    assert int(edges[1]) == oracle_recurse(rel, [3], 2)[2]


class TestEllRecurse:
    """ELL pull kernel == numpy walk (identical useful-edge counts and
    visited sets)."""

    def _graph(self, n=512, avg=6.0, seed=3):
        from dgraph_tpu.models.synthetic import powerlaw_rel
        return powerlaw_rel(n, avg, seed=seed)

    def test_matches_push_kernel_and_numpy(self):
        rel = self._graph()
        n = rel.indptr.shape[0] - 1
        rng = np.random.default_rng(11)
        B = 64
        seeds = [rng.integers(0, n, 3) for _ in range(B)]

        g, _last, seen, edges = lanes_recurse(rel, seeds, 3)
        assert g.nnz == rel.nnz
        want = [oracle_recurse(rel, s, 3) for s in seeds]
        assert np.array_equal(edges, [w[2] for w in want])

        seen_lists = unpack_masks(g, seen)
        for q in range(0, B, 7):
            assert np.array_equal(seen_lists[q], want[q][1].astype(np.int32))

    def test_single_query_deep(self):
        import numpy as np
        from dgraph_tpu.ops.bfs import (
            build_ell, ell_recurse, pack_seed_masks, unpack_masks)

        rel = self._graph(n=256, avg=3.0, seed=9)
        n = rel.indptr.shape[0] - 1
        g = build_ell(rel.indptr, rel.indices)
        seeds = [[5]] + [[0]] * 31  # pad to a full word
        mask0 = pack_seed_masks(g, seeds)
        _l, seen, edges = ell_recurse(g, mask0, depth=8)

        # numpy loop=false walk
        frontier = np.array([5])
        seen_np = {5}
        total = 0
        for _ in range(8):
            if not len(frontier):
                break
            nxt = set()
            for v in frontier:
                row = rel.indices[rel.indptr[v]:rel.indptr[v + 1]]
                total += len(row)
                nxt.update(int(x) for x in row)
            frontier = np.array(sorted(nxt - seen_np))
            seen_np |= nxt
        assert int(np.asarray(edges)[0]) == total
        assert list(unpack_masks(g, seen)[0]) == sorted(seen_np)


class TestSegmentCsr:
    """Degree-bucketed dense-lane + segment-CSR templates == numpy walk,
    across shapes that exercise every template: powerlaw (mixed), star
    (one all-heavy hub), chain (deg ≤ 1 + indeg-0 head), all-heavy
    uniform, and degree-gapped graphs (absent buckets)."""

    def _assert_identity(self, rel, B=32, depth=3, seed=0):
        from dgraph_tpu.ops.bfs import (build_ell, ell_recurse,
                                        pack_seed_masks, unpack_masks)
        n = rel.indptr.shape[0] - 1
        rng = np.random.default_rng(seed)
        seeds = [rng.integers(0, n, rng.integers(1, 4)) for _ in range(B)]
        g = build_ell(rel.indptr, rel.indices)
        assert g.nnz == rel.nnz
        mask0 = pack_seed_masks(g, seeds)
        _last, seen, edges = ell_recurse(g, mask0, depth)
        seen_lists = unpack_masks(g, seen)
        for q in range(B):
            of, os_, oe = oracle_recurse(rel, seeds[q], depth)
            assert np.array_equal(seen_lists[q], os_), f"query {q} seen"
            assert int(np.asarray(edges)[q]) == oe, f"query {q} edges"
        return g

    def test_powerlaw_mixed(self):
        g = self._assert_identity(powerlaw_rel(500, 8.0, seed=4))
        assert g.seg_rows > 0, "powerlaw must exercise the heavy tail"
        assert any(k == 0 for k in g.ks), "and the indeg-0 class"

    def test_star_all_heavy_hub(self):
        """Star: hub with in-degree n-1 — a single segment-CSR row whose
        tile count forces the wide (reduce-form) level-2 combine."""
        from dgraph_tpu.store.store import _csr_from_pairs
        n = 600
        src = np.concatenate([np.arange(1, n), np.zeros(n - 1)])
        dst = np.concatenate([np.zeros(n - 1), np.arange(1, n)])
        rel = _csr_from_pairs(src.astype(np.int32), dst.astype(np.int32),
                              n)
        g = self._assert_identity(rel, depth=2, seed=1)
        assert g.seg_rows == 1
        assert g.lvl2 and g.lvl2[-1].shape[1] > 32, \
            "hub tile count must take the reduce-form combine"

    def test_chain_zero_and_one_indeg(self):
        from dgraph_tpu.store.store import _csr_from_pairs
        n = 200
        rel = _csr_from_pairs(np.arange(n - 1, dtype=np.int32),
                              np.arange(1, n, dtype=np.int32), n)
        g = self._assert_identity(rel, depth=5, seed=2)
        assert g.seg_rows == 0 and set(g.ks) == {0, 1}
        assert g.padded_edges == g.nnz, "chain ELL must be padding-free"

    def test_all_heavy_tail(self):
        rel = uniform_rel(64, 48, seed=3)
        g = self._assert_identity(rel, depth=2, seed=3)
        assert g.seg_rows >= 40, "uniform deg-48 is mostly tail"

    def test_degree_gap_buckets_absent(self):
        """Only the degree classes PRESENT get blocks — a gapped degree
        distribution must not materialize empty buckets."""
        from dgraph_tpu.ops.bfs import build_ell
        from dgraph_tpu.store.store import _csr_from_pairs
        # nodes 0..9 each receive exactly 4 edges; the rest receive 0
        src = np.tile(np.arange(10, 50, dtype=np.int32), 1)
        dst = np.repeat(np.arange(10, dtype=np.int32), 4)
        rel = _csr_from_pairs(src[:40], dst, 64)
        g = build_ell(rel.indptr, rel.indices)
        assert set(g.ks) == {0, 4}
        self._assert_identity(rel, depth=2, seed=5)

    def test_padding_bound_on_powerlaw(self):
        """The tentpole's padding claim: level-1 slots stay within
        seg_tile-1 per heavy row of the true edge count (was up to 4x
        under the power-of-4 ladder)."""
        from dgraph_tpu.ops.bfs import SEG_TILE, build_ell
        rel = powerlaw_rel(2000, 10.0, seed=6)
        g = build_ell(rel.indptr, rel.indices)
        assert g.padded_edges - g.nnz <= g.seg_rows * (SEG_TILE - 1)
        assert g.padded_edges < 1.25 * g.nnz

    def test_u64_words_match_u32(self):
        """uint64 lane words produce bit-identical traversals and edge
        counts to the uint32 default."""
        import jax

        from dgraph_tpu.ops.bfs import (build_ell, device_ell,
                                        make_ell_recurse,
                                        pack_seed_masks, unpack_masks)
        rel = powerlaw_rel(300, 6.0, seed=7)
        n = rel.indptr.shape[0] - 1
        rng = np.random.default_rng(7)
        seeds = [rng.integers(0, n, 3) for _ in range(64)]
        g = build_ell(rel.indptr, rel.indices)
        m32 = pack_seed_masks(g, seeds, word_bits=32)
        _l, seen32, edges32 = ell_recurse_local(g, m32, 3)
        with jax.enable_x64(True):
            m64 = pack_seed_masks(g, seeds, word_bits=64)
            dev = device_ell(g)
            fn = make_ell_recurse(dev, g.outdeg, g.n, m64.shape[1],
                                  count_edges=True, word_bits=64)
            _last64, seen64, edges64 = fn(jax.device_put(m64), 3)
            edges64 = np.asarray(edges64)
            s64 = unpack_masks(g, np.asarray(seen64), word_bits=64)
        s32 = unpack_masks(g, np.asarray(seen32), word_bits=32)
        assert np.array_equal(np.asarray(edges32), edges64)
        for a, b in zip(s32, s64):
            assert np.array_equal(a, b)


def ell_recurse_local(g, mask0, depth):
    from dgraph_tpu.ops.bfs import ell_recurse
    return ell_recurse(g, mask0, depth)


# -- the lane step that stops itself (ops/bfs.py make_ell_step) --------------

STEP_LEVELS = 8


def _scan_levels(prepared, mask0, depth, W, first_visit):
    """The plain form: a scan of `depth` hops of _ell_hop with no exit.
    Returns every hop's (fresh, seen)."""
    import jax
    from jax import lax

    from dgraph_tpu.ops.bfs import _ell_hop

    def hop(carry, _):
        f, s = carry
        nxt = _ell_hop(prepared, f, W)
        if first_visit:
            fresh = nxt & ~s
            s = s | fresh
        else:
            fresh = nxt
        return (fresh, s), (fresh, s)

    _, (fs, ss) = jax.jit(lambda m: lax.scan(hop, (m, m), None,
                                             length=depth))(mask0)
    return np.asarray(fs), np.asarray(ss)


def _closes(lvl, n, rows, q, first_visit):
    """Whether lane q closes at this level: nothing of it is left, or
    (first-visit lanes) its bit shows in one of `rows`."""
    wq, bq = q // 32, np.uint32(1 << (q % 32))
    return bool(first_visit and (lvl[rows, wq] & bq).any()) or \
        not (np.bitwise_or.reduce(lvl[:n, wq]) & bq)


def _host_rule(levels, n, near_rows, unresolved, first_visit):
    """engine/batch.py's scan of one launch's levels: pops the lanes it
    closes from `unresolved`, returns the hop that closed the last one
    (the count of levels when some stay open). A first-visit lane closes
    a hop AHEAD of its target, when the level reaches one of the
    target's in-neighbours (`near_rows[q]`)."""
    for h, lvl in enumerate(levels):
        for q in list(unresolved):
            if _closes(lvl, n, near_rows[q], q, first_visit):
                unresolved.discard(q)
        if not unresolved:
            return h + 1
    return len(levels)


def _target_rule(levels, n, dst_rows, unresolved):
    """The rule the look-ahead replaced, kept to be compared with: a
    first-visit lane closes at the level that shows its target's own
    row. Same returns as _host_rule."""
    return _host_rule(levels, n, {q: [r] for q, r in dst_rows.items()},
                      unresolved, True)


def _packed(lanes_set, W):
    m = np.zeros(W, np.uint32)
    for q in lanes_set:
        m[q // 32] |= np.uint32(1 << (q % 32))
    return m


# (row cap, slot cap, slots a turn) of the pushed hop, small enough that
# the 156-node case pushes some hops and pulls others ("mixed": a turn
# of 24 slots, so a pushed hop takes several turns and rows straddle
# them), never pushes ("pull") or always does ("push")
STEP_CAPS = {"mixed": (40, 160, 24), "pull": (0, 0, 1),
             "push": (156, 600, 64)}


def _device_ell_with_out(rel):
    """The relation's ELL on the device with its out-CSR beside it, as
    engine/batch.py _dev_with_out leaves it."""
    import jax

    from dgraph_tpu.ops.bfs import build_ell, device_ell, out_csr
    g = build_ell(rel.indptr, rel.indices)
    dev = device_ell(g)
    dev.out = jax.device_put(out_csr(g, rel.indptr, rel.indices))
    return g, dev


def _fits(rows, deg, caps):
    """The rule of a pushed hop: the frontier's `rows` with an out-edge,
    the sum of their out-degrees and the largest of them against the
    caps."""
    act = rows & (deg > 0)
    return bool(caps[0]) and act.sum() <= caps[0] and \
        deg[act].sum() <= caps[1] and deg[act].max(initial=0) <= caps[2]


def _slots(rows, deg, caps):
    """The slots a hop pushes: the out-edges of its frontier's `rows`
    where the caps hold them, 0 where it pulls."""
    return int(deg[rows].sum()) if _fits(rows, deg, caps) else 0


def _pushes(frontier, dev, n, caps):
    """(whether, over how many slots) the step pushes this frontier
    (packed, in the ELL's row space)."""
    rows, deg = (frontier[:n] != 0).any(axis=1), np.asarray(dev.out[2])
    return _fits(rows, deg, caps), _slots(rows, deg, caps)


@functools.lru_cache(maxsize=None)
def _step_case(lanes, first_visit, acyclic, caps="mixed"):
    """One graph, its lanes, the step program and the plain scan's levels.
    The hop limit is a traced argument, so both limits share all of it."""
    import types

    import jax

    from dgraph_tpu.ops.bfs import make_ell_step, prepare_parts
    from dgraph_tpu.store.store import _csr_from_pairs

    # a random core, and a few nodes no edge touches. With cycles a
    # level-DAG lane never dies; without (edges run to higher nodes, 25
    # to 59 on: no path has over 6 edges) every lane does, in both modes
    rng = np.random.default_rng(1000 * lanes + 2 * first_visit + acyclic)
    core, lone = 150, 6
    n = core + lone
    src = np.repeat(np.arange(core, dtype=np.int32), 4)
    if acyclic:
        dst = np.minimum(src + rng.integers(25, 60, src.size), core - 1)
        src, dst = src[src < dst], dst[src < dst].astype(np.int32)
    else:
        dst = rng.integers(0, core, src.size).astype(np.int32)
    rel = _csr_from_pairs(src, dst, n)
    rrel = _csr_from_pairs(dst, src, n)
    g, dev = _device_ell_with_out(rel)
    W = lanes // 32
    B = lanes - 5                       # the last five lanes are padding
    srcs = rng.integers(0, core, B)
    dsts = rng.integers(0, core, B)
    dsts[0] = core                      # unreachable: the search dies out
    srcs[1] = core + 1                  # nothing to expand: dies at hop 1
    # as engine/batch.py opens them: not lane 2, not a pair one edge
    # apart (settled before the launch). Lane 0's target has no in-edge:
    # kept open here, as the lane whose `near` rows are none
    active = {q for q in range(B) if q != 2 and srcs[q] != dsts[q]
              and srcs[q] not in rrel.row(dsts[q])}
    mask0 = np.zeros((n + 1, W), np.uint32)
    near = np.zeros((n + 1, W), np.uint32)
    dst_rows, near_rows, near2_rows = {}, {}, {}
    for q in active:
        wq, bq = q // 32, np.uint32(1 << (q % 32))
        mask0[g.new_of_old[srcs[q]], wq] |= bq
        dst_rows[q] = int(g.new_of_old[dsts[q]])
        near_rows[q] = g.new_of_old[rrel.row(dsts[q])]
        near[near_rows[q], wq] |= bq
        # both levels of engine/batch.py's look-ahead: the target's
        # in-neighbours and theirs
        near2_rows[q] = np.concatenate(
            [near_rows[q]] + [g.new_of_old[rrel.row(int(p))]
                              for p in rrel.row(dsts[q])])
    assert not len(near_rows[0]) and not near[n].any()

    want_f, want_s = _scan_levels(prepare_parts(dev, W),
                                  jax.device_put(mask0), 3 * STEP_LEVELS,
                                  W, first_visit)
    step = make_ell_step(dev, n, W, STEP_LEVELS, first_visit=first_visit,
                         caps=STEP_CAPS[caps])
    return types.SimpleNamespace(
        n=n, W=W, mask0=mask0, active=active, dst_rows=dst_rows,
        near=near, near_rows=near_rows, near2_rows=near2_rows, step=step,
        want_f=want_f,
        want_s=want_s, dev=dev)


@pytest.mark.parametrize("limit", [2, STEP_LEVELS])
@pytest.mark.parametrize("first_visit,acyclic", [
    (True, False), (False, False), (False, True)])
@pytest.mark.parametrize("lanes", [32, 64, 128])
@pytest.mark.parametrize("caps", list(STEP_CAPS))
def test_step_stops_where_the_host_rule_closes_the_last_lane(
        caps, lanes, first_visit, acyclic, limit):
    """Every hop's level, `seen`, `ran` and the open lanes equal the plain
    scan of pulls, whichever of its hops the step pushes; and it pushes
    exactly the hops whose frontier its caps hold, and counts their
    out-edges."""
    import jax

    c = _step_case(lanes, first_visit, acyclic, caps)
    n, W, step, want_f, want_s, dev = c.n, c.W, c.step, c.want_f, c.want_s, \
        c.dev
    unresolved = set(c.active)
    frontier = seen = c.mask0
    done = pushed_all = 0
    for call, lim in enumerate((limit, STEP_LEVELS, STEP_LEVELS)):  # resumed
        open_before = _packed(unresolved, W)
        closing = _host_rule(want_f[done:done + lim], n, c.near_rows,
                             unresolved, first_visit)
        f, s, hops, ran, open_after, pushed, slots = step(
            jax.device_put(frontier), jax.device_put(seen),
            c.near if first_visit else None, open_before, np.int32(lim))
        ran = int(ran)
        assert ran == closing, (call, ran, closing)
        assert len(hops) == STEP_LEVELS
        for h in range(ran):
            assert np.array_equal(np.asarray(hops[h]), want_f[done + h])
        expanded = [frontier] + list(want_f[done:done + ran - 1])
        assert (int(pushed), int(slots)) == tuple(map(sum, zip(*(
            _pushes(fr, dev, n, STEP_CAPS[caps]) for fr in expanded))))
        pushed_all += int(pushed)
        done += ran
        frontier, seen = np.asarray(f), np.asarray(s)
        assert np.array_equal(frontier, want_f[done - 1])
        assert np.array_equal(seen, want_s[done - 1])
        assert np.array_equal(np.asarray(open_after),
                              _packed(unresolved, W))
        if call == 0 and limit < STEP_LEVELS:
            assert ran == limit and unresolved, \
                "the limit must cut the first call short of its exit"
        if not unresolved:
            break
    if first_visit or acyclic:
        assert not unresolved, "every such search over 150 nodes ends"
    else:
        assert 0 in unresolved, "a level-DAG lane over cycles never dies"
    if caps == "pull":
        assert pushed_all == 0
    elif caps == "push":
        assert pushed_all == done
    elif first_visit or acyclic:        # frontiers that shrink again
        assert 0 < pushed_all < done, "the case must mix both kinds of hop"


@pytest.mark.parametrize("near", ["given", "two-levels", "none",
                                  "level-dag"])
@pytest.mark.parametrize("lanes", [32, 64, 128])
@pytest.mark.parametrize("caps", list(STEP_CAPS))
def test_the_look_ahead_spares_a_launch_its_last_hop(caps, lanes, near):
    """A launch whose every open lane finds its target runs one hop fewer
    than the rule that waited for the target's own row: the lanes close
    at the level that reaches the target's in-neighbours, and the levels
    up to there are the plain scan's, pushed or pulled. Given the rows
    two edges before the target in the same mask, the same program runs
    two hops fewer (one hop at least: here a lane two edges long is
    opened, which engine/batch.py settles before its launch). A lane
    with no row in `near` closes when it dies out, as does every lane of
    the level-DAG program, which makes no use of the argument."""
    import jax

    first_visit = near != "level-dag"
    c = _step_case(lanes, first_visit, not first_visit, caps)
    n, W = c.n, c.W
    finders = {q for q in c.active
               if (c.want_f[:STEP_LEVELS, c.dst_rows[q], q // 32]
                   >> np.uint32(q % 32) & 1).any()}
    assert len(finders) > (lanes // 4 if first_visit else 0)

    def run(near_mask):
        _f, _s, hops, ran, open_after, _pushed, _slots = c.step(
            jax.device_put(c.mask0), jax.device_put(c.mask0), near_mask,
            _packed(finders, W), np.int32(STEP_LEVELS))
        for h in range(int(ran)):
            assert np.array_equal(np.asarray(hops[h]), c.want_f[h])
        return int(ran), np.asarray(open_after)

    levels = c.want_f[:STEP_LEVELS]
    dies_out = _host_rule(levels, n, {q: [] for q in finders},
                          set(finders), first_visit)
    if near == "given":
        at_target = _target_rule(levels, n, c.dst_rows, set(finders))
        ahead = _host_rule(levels, n, c.near_rows, set(finders), True)
        assert 1 <= ahead == at_target - 1 < dies_out
        ran, open_after = run(c.near)
        assert ran == ahead and not open_after.any()
    elif near == "two-levels":
        at_target = _target_rule(levels, n, c.dst_rows, set(finders))
        ahead2 = _host_rule(levels, n, c.near2_rows, set(finders), True)
        assert 1 <= ahead2 == max(at_target - 2, 1) < dies_out
        both = c.near.copy()
        for q, rows in c.near2_rows.items():
            both[rows, q // 32] |= np.uint32(1 << (q % 32))
        ran, open_after = run(both)
        assert ran == ahead2 and not open_after.any()
    else:
        ran, open_after = run(np.zeros_like(c.mask0) if first_visit
                              else None)
        assert ran == dies_out
        assert dies_out == STEP_LEVELS or not open_after.any()
        if not first_visit:             # given a mask, it reads none of it
            again, open_again = run(c.near)
            assert again == ran and np.array_equal(open_again, open_after)


def _one_hop(dev, n, W, mask0, caps, first_visit=True):
    """One hop of a fresh step program under `caps`, every lane open and
    none with a row to look ahead to: (level, seen, pushed, slots)."""
    import jax

    from dgraph_tpu.ops.bfs import make_ell_step
    step = make_ell_step(dev, n, W, 1, first_visit=first_visit, caps=caps)
    _f, s, hops, ran, _open, pushed, slots = step(
        jax.device_put(mask0), jax.device_put(mask0),
        np.zeros_like(mask0) if first_visit else None,
        np.full(W, 0xFFFFFFFF, np.uint32), np.int32(1))
    assert int(ran) == 1
    return np.asarray(hops[0]), np.asarray(s), int(pushed), int(slots)


@pytest.mark.parametrize("first_visit", [True, False])
@pytest.mark.parametrize("over", ["fits", "one_row", "one_edge",
                                  "one_slot"])
def test_a_frontier_over_a_cap_takes_the_pull(over, first_visit):
    """Caps that hold the frontier exactly push it; one row or one edge
    less, or a turn one slot short of a row's out-edges, and the hop
    pulls. The level is the same each way."""
    c = _step_case(64, first_visit, False, "pull")
    n, W, mask0, want_f, want_s, dev = c.n, c.W, c.mask0, c.want_f, \
        c.want_s, c.dev
    deg = np.asarray(dev.out[2])
    act = (mask0[:n] != 0).any(axis=1) & (deg > 0)
    rows, edges = int(act.sum()), int(deg[act].sum())
    assert rows > 1 and edges > rows
    widest = int(deg[act].max())
    caps = {"fits": (rows, edges, widest),
            "one_row": (rows - 1, edges, widest),
            "one_edge": (rows, edges - 1, widest),
            "one_slot": (rows, edges, widest - 1)}[over]
    level, seen, pushed, slots = _one_hop(dev, n, W, mask0, caps,
                                          first_visit)
    assert (pushed, slots) == ((1, edges) if over == "fits" else (0, 0))
    assert np.array_equal(level, want_f[0])
    assert np.array_equal(seen, want_s[0])


@functools.lru_cache(maxsize=None)
def _wide_rows_graph():
    """A relation whose every row has 40 to 56 out-edges, so that its own
    caps (push_caps of its structure) bind by their slots, not their
    rows: a row of PUSH_FANOUT out-edges or more is what the slot cap
    was priced for."""
    from dgraph_tpu.store.store import _csr_from_pairs
    rng = np.random.default_rng(44)
    n = 2000
    src = np.repeat(np.arange(n, dtype=np.int32), rng.integers(40, 57, n))
    dst = rng.integers(0, n, src.size).astype(np.int32)
    rel = _csr_from_pairs(src, dst, n)
    return (n, *_device_ell_with_out(rel))


@pytest.mark.parametrize("first_visit", [True, False])
@pytest.mark.parametrize("frontier", ["just_under", "just_over"])
def test_the_relations_own_caps_push_up_to_the_break_even(frontier,
                                                          first_visit):
    """Under push_caps of the relation (no caps passed), a frontier whose
    out-edges number the slot cap at most is pushed and counted, one row
    more is pulled, and the level is the pull-only program's each way."""
    from dgraph_tpu.ops.bfs import PUSH_FANOUT, push_caps
    n, g, dev = _wide_rows_graph()
    f_cap, e_cap, chunk = push_caps(g)
    assert push_caps(dev) == (f_cap, e_cap, chunk)
    deg = np.asarray(dev.out[2])
    assert deg.min() >= PUSH_FANOUT and deg.max() <= chunk
    # rows in the ELL's order while their out-edges fit the slot cap
    rows = int(np.searchsorted(np.cumsum(deg), e_cap, side="right"))
    assert 4 <= rows < f_cap and e_cap - deg.max() < deg[:rows].sum()
    rows += frontier == "just_over"
    W = 2
    mask0 = np.zeros((n + 1, W), np.uint32)
    lane = np.arange(rows) % 64
    mask0[np.arange(rows), lane // 32] = np.uint32(1) << (lane % 32
                                                         ).astype(np.uint32)
    level, seen, pushed, slots = _one_hop(dev, n, W, mask0, None,
                                          first_visit)
    assert (pushed, slots) == ((1, int(deg[:rows].sum()))
                               if frontier == "just_under" else (0, 0))
    want, want_seen, *_ = _one_hop(dev, n, W, mask0, (0, 0, 1),
                                   first_visit)
    assert np.array_equal(level, want) and level.any()
    assert np.array_equal(seen, want_seen)


@pytest.mark.parametrize("n,cap,share,step", [
    (1000, 64, 0.01, 4096), (50000, 700, 0.01, 256), (50000, 700, 0.5, 256),
    (300001, 5000, 0.1, 1024), (129, 200, 0.9, 64), (70000, 100, 0.0, 32),
    (70000, 1000, 1.0, 300)])
def test_set_rows_lists_the_first_set_rows_a_turn_at_a_time(n, cap, share,
                                                            step):
    """The first `cap` set rows ascending, padded with n: fewer rows than
    a turn, many turns, more set rows than the cap, a cap that is no
    whole number of turns, none set, all set."""
    import jax

    from dgraph_tpu.ops.bfs import _set_rows
    act = np.random.default_rng(cap).random(n) < share
    got = np.asarray(jax.jit(lambda a: _set_rows(a, n, cap, step))(act))
    want = np.nonzero(act)[0][:cap]
    assert got.tolist() == want.tolist() + [n] * (cap - len(want))


@pytest.mark.parametrize("chunk", [5, 64, 1024])
def test_a_pushed_hop_leaves_the_sentinel_row_zero(chunk):
    """The slots of a turn beyond the frontier's last edge are dropped,
    never written to row n: every padded gather of a later pull reads
    that row as zero."""
    c = _step_case(64, True, False, "pull")
    n, W, mask0, want_f, dev = c.n, c.W, c.mask0, c.want_f, c.dev
    deg = np.asarray(dev.out[2])
    edges = int(deg[(mask0[:n] != 0).any(axis=1)].sum())
    assert edges % chunk, "the last turn must hold slots with no edge"
    level, seen, pushed, slots = _one_hop(dev, n, W, mask0,
                                          (n, 600, chunk))
    assert (pushed, slots) == (1, edges)
    assert not level[n].any() and not seen[n].any()
    assert np.array_equal(level, want_f[0])


@pytest.mark.parametrize("chunk", [5, 64])
@pytest.mark.parametrize("lanes", [32, 64])
def test_a_hub_ors_the_lane_bits_of_its_many_sources(lanes, chunk):
    """Forty sources carrying the same lane bit reach one hub: the hub's
    mask holds that bit once (an add would carry 40 = 0b101000 into
    bits 3 and 5), and the bits of the other lanes beside it."""
    from dgraph_tpu.store.store import _csr_from_pairs
    fans, hub, n = 40, 40, 44
    src = np.concatenate([np.arange(fans), [hub, hub]]).astype(np.int32)
    dst = np.concatenate([np.full(fans, hub), [41, 42]]).astype(np.int32)
    g, dev = _device_ell_with_out(_csr_from_pairs(src, dst, n))
    W = lanes // 32
    mask0 = np.zeros((n + 1, W), np.uint32)
    mask0[g.new_of_old[np.arange(fans)], 0] |= np.uint32(1)       # lane 0
    mask0[g.new_of_old[np.arange(3)], 0] |= np.uint32(2)          # lane 1
    mask0[g.new_of_old[7], W - 1] |= np.uint32(1 << 31)           # the last
    pulled, _s, *p0 = _one_hop(dev, n, W, mask0, (0, 0, 1))
    pushed, _s, *p1 = _one_hop(dev, n, W, mask0, (n, fans + 2, chunk))
    assert (p0, p1) == ([0, 0], [1, fans])
    want = np.zeros((n + 1, W), np.uint32)
    want[g.new_of_old[hub], 0] = 3
    want[g.new_of_old[hub], W - 1] |= np.uint32(1 << 31)
    assert np.array_equal(pushed, want)
    assert np.array_equal(pulled, want)


# -- the tree program's recurse stage (ops/bfs.py make_ell_tree) -------------

TREE_DEPTH = 3
TREE_N, TREE_EDGES = 3000, 20000  # push_caps: 31 rows, 1,015 slots, a turn


@functools.lru_cache(maxsize=None)
def _tree_graph():
    """A random relation in which a row has 2 to 11 out-edges, big enough
    that push_caps holds a few rows, with a few nodes no edge leaves."""
    from dgraph_tpu.store.store import _csr_from_pairs
    rng = np.random.default_rng(5)
    src = rng.integers(0, TREE_N - 40, TREE_EDGES).astype(np.int32)
    dst = rng.integers(0, TREE_N, TREE_EDGES).astype(np.int32)
    rel = _csr_from_pairs(src, dst, TREE_N)
    g, dev = _device_ell_with_out(rel)
    return rel, g, dev


def _unpacked(mask, n):
    """bool[n, lanes] of a packed uint32 mask [n+1, W]."""
    m = np.asarray(mask)[:n]
    return ((m[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1
            ).astype(bool).reshape(n, -1)


def _plain_recurse(rel, seeds, depth, allowed):
    """A breadth-first search a lane, in numpy sets: (seen bool[n, lanes],
    hops bool[depth, n, lanes], edges a lane). A filter keeps a hop's
    fresh rows inside `allowed`; the seeds are seen whatever it says."""
    n, lanes = len(rel.indptr) - 1, len(seeds)
    deg = np.diff(rel.indptr)
    seen = np.zeros((n, lanes), bool)
    hops = np.zeros((depth, n, lanes), bool)
    edges = []
    for q, s in enumerate(seeds):
        fresh = np.unique(s)
        seen[fresh, q] = True
        for h in range(depth):
            expanded = seen[:, q].copy()         # all but the last fresh
            nxt = np.unique(np.concatenate(
                [rel.row(int(r)) for r in fresh] + [np.zeros(0, np.int32)]))
            fresh = nxt[~seen[nxt, q]]
            if allowed is not None:
                fresh = fresh[allowed[fresh]]
            seen[fresh, q] = True
            hops[h, fresh, q] = True
        edges.append(int(deg[expanded].sum()))
    return seen, hops, edges


def _tree_pushes(frontiers, deg, caps):
    """Which of a stage's hops the caps make a push: one flag a hop, from
    the frontier each hop expands (bool[n, lanes])."""
    return [_fits(f.any(axis=1), deg, caps) for f in frontiers]


@functools.lru_cache(maxsize=None)
def _tree_run(caps_name, lanes, filtered, keep_hops):
    """One launch of a one-stage tree program under the named caps:
    (outputs as numpy, the seeds, the caps)."""
    import jax

    from dgraph_tpu.ops.bfs import make_ell_tree, prepare_parts, push_caps
    rel, g, dev = _tree_graph()
    n, W = TREE_N, lanes // 32
    deg = np.diff(rel.indptr)
    rng = np.random.default_rng(lanes + 2 * filtered)
    starts = np.nonzero((deg > 0) & (deg <= 8))[0]
    if caps_name == "default":
        # the relation's own caps hold 31 rows: the lanes share as many
        # seeds as the row cap, so hop 1 just fits and hop 2 does not
        rows = push_caps(g)[0]
        pool = rng.choice(starts, rows, replace=False)
        seeds = [pool[q % rows::lanes - 3] for q in range(lanes - 3)]
        assert len(np.unique(np.concatenate(seeds))) == rows >= 4
    else:
        seeds = [rng.choice(starts, 1 + q % 2, replace=False)
                 for q in range(lanes - 3)]      # the last three: padding
    seeds += [np.zeros(0, np.int64)] * 3
    first = np.unique(np.concatenate(seeds))
    rows1, slots1, widest1 = len(first), int(deg[first].sum()), \
        int(deg[first].max())
    caps = {
        "pull": (0, 0, 1),                       # none hold a row
        "push": (TREE_N, TREE_EDGES, int(deg.max())),    # every hop fits
        "default": push_caps(g),
        "hop1": (rows1, slots1, widest1),        # hop 2 is over the slots
        "turn": (TREE_N, TREE_EDGES, widest1 - 1),   # a seed over a turn
    }[caps_name]
    allowed = None
    if filtered:
        allowed = rng.random(n) < 0.6
    seed_mask = np.zeros((n + 1, W), np.uint32)
    for q, s in enumerate(seeds):
        seed_mask[s, q // 32] |= np.uint32(1 << (q % 32))
    filt_mask = np.zeros((n + 1, W), np.uint32)
    if filtered:
        filt_mask[:n][allowed] = 0xFFFFFFFF
    stage = {
        "kind": "recurse", "prepared": prepare_parts(dev, W),
        "perm_in": jax.device_put(
            np.concatenate([g.perm_order, [n]]).astype(np.int32)),
        "out_idx": jax.device_put(
            np.concatenate([g.new_of_old, [n]]).astype(np.int32)),
        "out": dev.out, "caps": None if caps_name == "default" else caps,
        "parent": ("seed", 0), "filt": 0 if filtered else None,
        "depth": TREE_DEPTH, "keep_hops": keep_hops}
    fn = make_ell_tree([stage], n, W)
    (seen, count, edges, pushed, slots, hops), = fn(
        (jax.device_put(seed_mask),),
        (jax.device_put(filt_mask),) if filtered else ())
    got = (_unpacked(seen, n)[g.new_of_old], np.asarray(count),
           np.asarray(edges), (int(pushed), int(slots)),
           None if hops is None else
           np.stack([_unpacked(h, n) for h in np.asarray(hops)]))
    assert not np.asarray(seen)[n].any(), "the sentinel row stays zero"
    return got, seeds, allowed, caps


@pytest.mark.parametrize("keep_hops", [False, True])
@pytest.mark.parametrize("filtered", [False, True])
@pytest.mark.parametrize("lanes", [32, 64])
@pytest.mark.parametrize("caps_name", ["pull", "push", "default", "hop1",
                                       "turn"])
def test_tree_recurse_stage_pushes_what_its_caps_hold(caps_name, lanes,
                                                      filtered, keep_hops):
    """Whichever of its hops a recurse stage pushes, `seen`, `count`,
    `edges` and `hops` equal the pull-only program's and a plain
    breadth-first search's, and `pushed` counts the hops whose frontier
    the caps hold: rows with a bit and an out-edge, their out-degrees'
    sum (`slots` adds it up over those hops), and the largest of them
    against a turn."""
    rel, _g, _dev = _tree_graph()
    deg = np.diff(rel.indptr)
    (seen, count, edges, pushed, hops), seeds, allowed, caps = _tree_run(
        caps_name, lanes, filtered, keep_hops)
    want_seen, want_hops, want_edges = _plain_recurse(
        rel, seeds, TREE_DEPTH, allowed)
    assert np.array_equal(seen, want_seen)
    assert count.tolist() == want_seen.sum(axis=0).tolist()
    assert edges.tolist() == want_edges
    assert (hops is None) == (not keep_hops)
    if keep_hops:
        assert np.array_equal(hops, want_hops)

    first = np.zeros_like(want_seen)
    for q, s in enumerate(seeds):
        first[s, q] = True
    frontiers = [first, *want_hops[:-1]]
    flags = _tree_pushes(frontiers, deg, caps)
    assert pushed == (sum(flags), sum(_slots(f.any(axis=1), deg, caps)
                                      for f in frontiers))
    if caps_name == "turn":
        # rows and slots fit: the widest seed alone sends hop 1 to the pull
        assert not flags[0] and _tree_pushes(
            [first], deg, (caps[0], caps[1], caps[2] + 1)) == [True]
    else:
        assert flags == {"pull": [False] * 3, "push": [True] * 3,
                         "default": [True, False, False],
                         "hop1": [True, False, False]}[caps_name]

    # the pull-only program of the same seeds (the default caps' lanes
    # share four seeds: its own numpy search above is its witness)
    if caps_name not in ("pull", "default"):
        (p_seen, p_count, p_edges, p_pushed, p_hops), *_ = _tree_run(
            "pull", lanes, filtered, keep_hops)
        assert p_pushed == (0, 0)
        assert np.array_equal(seen, p_seen)
        assert np.array_equal(count, p_count)
        assert np.array_equal(edges, p_edges)
        if keep_hops:
            assert np.array_equal(hops, p_hops)


# -- the dense hub block beside the ELL (ops/bfs.py _choose_dense) -----------

# the rule's (cells an edge, byte cap, edge floor) for a graph of 700 nodes:
# no break-even to speak of, room for one block of 256 x 128, and a floor a
# core of a few hundred edges passes
HUB_RULE = (1e9, 256 * 128, 50)
NO_BLOCK = (1.0, 0, 1 << 62)
# caps of the pushed hop that hold 64 rows of the periphery (three out-edges
# each) and no frontier after them
HUB_CAPS = {"pull": (0, 0, 1), "mixed": (80, 300, 24)}


@pytest.mark.parametrize("relation", ["no_block", "hub_block", "tiny"])
def test_push_caps_are_where_a_push_costs_what_a_pull_costs(relation):
    """The slot cap is the relation's pull, priced by the chip's readings
    (its list slots, its hub block's cells), over a pushed slot's price:
    about a twentieth of the slots. A hub block lowers it, since the
    edges it holds are in no list; a graph of a few hundred edges keeps a
    pull side, and one of a few dozen never pushes."""
    from dgraph_tpu.ops import bfs
    from dgraph_tpu.store.store import _csr_from_pairs
    if relation == "tiny":
        rng = np.random.default_rng(3)
        rel = _csr_from_pairs(rng.integers(0, 30, 60).astype(np.int32),
                              rng.integers(0, 30, 60).astype(np.int32), 30)
        g = build_ell(rel.indptr, rel.indices)
    else:
        rel = _hub_rel()
        g = build_ell(rel.indptr, rel.indices,
                      dense=HUB_RULE if relation == "hub_block"
                      else NO_BLOCK)
    slots = g.padded_edges
    cells = 0 if g.dense is None else g.dense[0].size
    assert (cells > 0) == (relation == "hub_block")
    f_cap, e_cap, chunk = bfs.push_caps(g)
    assert e_cap == int((slots * bfs.PULL_SLOT_NS
                         + cells * bfs.DENSE_CELL_NS) / bfs.PUSH_SLOT_NS)
    assert (f_cap, chunk) == (e_cap // bfs.PUSH_FANOUT,
                              min(bfs.PUSH_CHUNK, max(e_cap, 1)))
    # a pull side whatever the graph: at most a tenth of its edges pushed
    assert slots >= g.nnz - g.dense_edges and e_cap <= g.nnz // 10
    if relation == "tiny":
        assert f_cap == 0, "caps that hold no row compile no push"
    else:
        assert f_cap >= 1 and e_cap >= slots // 25
    if relation == "hub_block":
        plain = bfs.push_caps(build_ell(rel.indptr, rel.indices,
                                        dense=NO_BLOCK))
        assert e_cap < plain[1]
        # by what the block took out of the lists, less its own price
        assert plain[1] - e_cap >= (g.dense_edges - len(g.dense[0])) // 25


@functools.lru_cache(maxsize=None)
def _hub_pairs():
    """(n, edges [E, 2]) of a skewed relation: 60 sources of high
    out-degree S, 40 targets of high in-degree T, the core S x T filled to
    seven tenths, and a periphery of low degree. Three rows of T take
    every source: one takes nothing else (nothing of it is left to the
    ELL), one takes 5 more in-edges (what is left falls to the dense
    body's degrees), one 55 more (it stays heavy)."""
    rng = np.random.default_rng(38)
    n = 700
    S, T, F, L = (np.arange(0, 60), np.arange(60, 100), np.arange(100, 160),
                  np.arange(160, 700))
    pairs = [(s, t) for s in S for t in T[3:] if rng.random() < 0.7]
    pairs += [(s, t) for s in S for t in T[:3]]
    pairs += [(f, T[1]) for f in F[:5]] + [(f, T[2]) for f in F[5:]]
    for s in S:
        pairs += [(s, x) for x in rng.choice(L, 10, replace=False)]
    for x in L:
        pairs += [(x, d) for d in rng.choice(n, 3, replace=False)]
    return n, np.unique(np.array(pairs, np.int32), axis=0)


def _hub_rel(names=None):
    """The relation of _hub_pairs, node i named names[i]."""
    from dgraph_tpu.store.store import _csr_from_pairs
    n, pairs = _hub_pairs()
    if names is not None:
        pairs = np.asarray(names, np.int32)[pairs]
    return _csr_from_pairs(pairs[:, 0], pairs[:, 1], n)


def _block_rows(g):
    """Old ranks of the block's rows, in the block's order: row j of the
    product is in-neighbour n + 1 + j of its row, and of no other."""
    rows, off = {}, 0

    def note(e, row_of):
        at, k = np.nonzero(e > g.n)
        for i, v in zip(at, e[at, k]):
            assert int(v) - g.n - 1 not in rows
            rows[int(v) - g.n - 1] = int(g.perm_order[row_of[i]])

    for _kind, e, count in g.parts:
        if e is not None:
            note(e, np.arange(off, off + count))
        off += count
    # a tile's row: the second level lists each heavy row's tiles
    M = g.tiles.shape[0]
    tile_row = np.zeros(M, np.int64)
    for t2 in g.lvl2:
        at, k = np.nonzero(t2 < M)
        tile_row[t2[at, k]] = off + at
        off += len(t2)
    note(g.tiles, tile_row)
    assert sorted(rows) == list(range(len(rows)))
    return np.array([rows[j] for j in range(len(rows))])


def _pull_reference(rel, mask):
    """next[v] = OR of mask[u] over the stored edges u -> v, in numpy and
    in the relation's own row space."""
    out = np.zeros_like(mask)
    src = np.repeat(np.arange(rel.indptr.shape[0] - 1),
                    np.diff(rel.indptr))
    np.bitwise_or.at(out, rel.indices, mask[src])
    return out


def _random_lanes(n, W, dt, seed, per_lane=4):
    """[n, W] packed mask of `per_lane` random rows a lane."""
    rng = np.random.default_rng(seed)
    bits = 8 * np.dtype(dt).itemsize
    m = np.zeros((n, W), dt)
    for q in range(W * bits):
        m[rng.integers(0, n, per_lane), q // bits] |= dt(1 << (q % bits))
    return m


def test_the_block_holds_the_core_and_the_ell_the_rest():
    from dgraph_tpu.ops.bfs import DENSE_PAD, SEG_MIN_DEG, out_csr
    rel = _hub_rel()
    n = rel.indptr.shape[0] - 1
    g = build_ell(rel.indptr, rel.indices, dense=HUB_RULE)
    block, cols = g.dense
    assert block.dtype == np.int8 and cols.dtype == np.int32
    assert block.shape[0] % DENSE_PAD == 0 == block.shape[1] % DENSE_PAD
    assert block.shape == (256, 128) and cols.shape == (128,)
    real = cols < n
    assert (np.diff(cols[real]) > 0).all() and (cols[~real] == n).all()
    # whole degree classes on both sides
    indeg = np.bincount(rel.indices, minlength=n)
    outdeg = np.diff(rel.indptr)
    R, C = _block_rows(g), g.perm_order[cols[real]]
    assert set(R) == set(np.nonzero(indeg >= indeg[R].min())[0])
    assert set(C) == set(np.nonzero(outdeg >= outdeg[C].min())[0])
    # the block is the adjacency matrix between them, and nothing else
    adj = np.zeros((n, n), np.int8)
    adj[rel.indices, np.repeat(np.arange(n), outdeg)] = 1
    assert np.array_equal(block[:len(R), :len(C)], adj[np.ix_(R, C)])
    assert not block[len(R):].any() and not block[:, len(C):].any()
    assert g.dense_edges == int(block.sum()) and 0 < g.dense_edges < rel.nnz
    # and the ELL holds every other in-edge, once
    # and the lists hold every other in-edge once, and one in-neighbour
    # more for each row of the block: its row of the product
    slots = np.concatenate([e.ravel() for kind, e, _ in g.parts
                            if kind == "ell"] + [g.tiles.ravel()])
    assert (slots < n).sum() == rel.nnz - g.dense_edges
    assert (slots > n).sum() == len(R)
    # a row of the block of which nothing else is left (a list of one),
    # one left in the dense body's degrees, one still in the tail
    left = indeg[R] - adj[np.ix_(R, C)].sum(axis=1)
    assert (left == 0).any() and (left >= SEG_MIN_DEG).any()
    assert ((left > 0) & (left < SEG_MIN_DEG)).any()
    assert ((g.new_of_old[R] >= n - g.seg_rows) == (left >= SEG_MIN_DEG)
            ).all()
    assert 1 in g.ks
    # what the push, the walk-back and the probe read is the whole relation
    assert g.nnz == rel.nnz
    assert np.array_equal(np.sort(g.perm_order), np.arange(n))
    assert np.array_equal(g.perm_order[g.new_of_old], np.arange(n))
    assert np.array_equal(g.outdeg, outdeg[g.perm_order])
    ptr, idx, deg = out_csr(g, rel.indptr, rel.indices)
    assert np.array_equal(deg, outdeg[g.perm_order])
    assert np.array_equal(
        g.perm_order[idx],
        np.concatenate([rel.row(int(r)) for r in g.perm_order]))


@pytest.mark.parametrize("W", [1, 2])
@pytest.mark.parametrize("word_bits", [32, 64])
def test_a_pull_with_the_block_equals_one_without(word_bits, W):
    import contextlib

    import jax
    import jax.numpy as jnp

    from dgraph_tpu.ops.bfs import _ell_hop, device_ell, prepare_parts
    rel = _hub_rel()
    n = rel.indptr.shape[0] - 1
    dt = np.uint32 if word_bits == 32 else np.uint64
    mask = _random_lanes(n, W, dt, seed=word_bits + W)
    want = _pull_reference(rel, mask)
    with (jax.enable_x64(True) if word_bits == 64
          else contextlib.nullcontext()):
        for rule in (HUB_RULE, NO_BLOCK):
            g = build_ell(rel.indptr, rel.indices, dense=rule)
            assert (g.dense is None) == (rule is NO_BLOCK)
            prepared = prepare_parts(device_ell(g), W)
            frontier = np.zeros((n + 1, W), dt)
            frontier[g.new_of_old] = mask
            got = np.asarray(_ell_hop(prepared, jnp.asarray(frontier), W,
                                      jnp.dtype(dt).type))
            assert got.dtype == dt and not got[n].any()
            assert np.array_equal(got[g.new_of_old], want)


@pytest.mark.parametrize("seed", [1, 2])
def test_the_same_structure_under_other_names_builds_the_same_shapes(seed):
    n, _pairs = _hub_pairs()
    names = np.random.default_rng(seed).permutation(n)
    a, b = (build_ell(rel.indptr, rel.indices, dense=HUB_RULE)
            for rel in (_hub_rel(), _hub_rel(names)))

    def shapes(g):
        return ([(kind, rows, None if e is None else e.shape)
                 for kind, e, rows in g.parts], g.tiles.shape,
                [t.shape for t in g.lvl2], g.seg_rows, g.ks,
                g.dense[0].shape, g.dense[1].shape, g.dense_edges,
                g.padded_edges)

    assert shapes(a) == shapes(b)
    # the same rows and columns, under their other names
    assert set(names[_block_rows(a)]) == set(_block_rows(b))
    real = a.dense[1] < n
    assert set(names[a.perm_order[a.dense[1][real]]]) == set(
        b.perm_order[b.dense[1][real]])


def _ell_digest(g):
    import hashlib
    h = hashlib.sha1()

    def put(a):
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())

    for kind, e, rows in g.parts:
        h.update(f"{kind}{rows}".encode())
        if e is not None:
            put(e)
    h.update(f"{g.n} {g.seg_rows} {g.ks}".encode())
    for a in [g.tiles] + list(g.lvl2) + [g.outdeg, g.perm_order,
                                         g.new_of_old]:
        if a is not None:
            put(a)
    return h.hexdigest()


# (relation, the digest of what PR 37's build_ell made of it, its
# padded_edges): a graph under the edge floor builds what it always did
UNDER_THE_FLOOR = {
    "powerlaw": (lambda: powerlaw_rel(500, 8.0, seed=4),
                 "6041cb8e1c55a1b2bc72b05802ebf08c5e400ac3", 2551),
    "uniform": (lambda: uniform_rel(64, 48, seed=3),
                "16df1c837bd7f06ae9cc5de368d2e1ec2feac67e", 2380),
    "powerlaw2000": (lambda: powerlaw_rel(2000, 10.0, seed=6),
                     "95ac5efed6fe0eab767ec9d0f1b00f3ba17306ed", 17077),
    "hub": (_hub_rel, None, None),
}


@pytest.mark.parametrize("name", sorted(UNDER_THE_FLOOR))
def test_a_graph_under_the_edge_floor_builds_no_block(name):
    import dataclasses
    maker, digest, padded = UNDER_THE_FLOOR[name]
    rel = maker()
    g = build_ell(rel.indptr, rel.indices)
    assert g.dense is None and g.dense_edges == 0
    if digest:
        assert (_ell_digest(g), g.padded_edges) == (digest, padded)
    off = build_ell(rel.indptr, rel.indices, dense=NO_BLOCK)
    for f in dataclasses.fields(g):
        a, b = getattr(g, f.name), getattr(off, f.name)
        if f.name == "parts":
            assert [(k, r) for k, _e, r in a] == [(k, r) for k, _e, r in b]
            a, b = [e for _k, e, _r in a], [e for _k, e, _r in b]
        if isinstance(a, list):
            assert len(a) == len(b)
            for x, y in zip(a, b):
                assert np.array_equal(x, y), f.name
        else:
            assert np.array_equal(a, b), f.name


@pytest.mark.parametrize("caps", ["mixed", "pull"])
@pytest.mark.parametrize("lanes", [32, 64])
def test_the_step_with_the_block_runs_the_hops_of_one_without(lanes, caps):
    """make_ell_step over the hub relation, `near` given, hops pushed and
    pulled: with the block and without, the same levels, the same seen,
    the same count of hops run, pushed and of slots pushed, the same
    lanes left open."""
    import jax

    from dgraph_tpu.ops.bfs import device_ell, make_ell_step, out_csr
    from dgraph_tpu.store.store import _csr_from_pairs
    rel = _hub_rel()
    n = rel.indptr.shape[0] - 1
    rrel = _csr_from_pairs(rel.indices, np.repeat(
        np.arange(n, dtype=np.int32), np.diff(rel.indptr)), n)
    W = lanes // 32
    rng = np.random.default_rng(lanes)
    # sources of the periphery, three out-edges each: hop 1 fits the caps
    srcs, dsts = rng.integers(160, n, lanes), rng.integers(0, n, lanes)
    runs = []
    for rule in (HUB_RULE, NO_BLOCK):
        g = build_ell(rel.indptr, rel.indices, dense=rule)
        dev = device_ell(g)
        dev.out = jax.device_put(out_csr(g, rel.indptr, rel.indices))
        mask0 = np.zeros((n + 1, W), np.uint32)
        near = np.zeros((n + 1, W), np.uint32)
        for q in range(lanes):
            wq, bq = q // 32, np.uint32(1 << (q % 32))
            mask0[g.new_of_old[srcs[q]], wq] |= bq
            near[g.new_of_old[rrel.row(int(dsts[q]))], wq] |= bq
        step = make_ell_step(dev, n, W, 4, caps=HUB_CAPS[caps])
        f, s, hops, ran, open_, pushed, slots = step(
            jax.device_put(mask0), jax.device_put(mask0),
            jax.device_put(near), _packed(range(lanes), W), 4)
        ran, pushed = int(ran), (int(pushed), int(slots))
        runs.append((ran, pushed, np.asarray(open_),
                     np.asarray(s)[g.new_of_old],
                     [np.asarray(h)[g.new_of_old] for h in hops[:ran]]))
    (ran, pushed, open_, seen, hops), other = runs
    assert ran >= 2 and (ran, pushed) == other[:2]
    # a pushed hop of the periphery's sources: three out-edges a row
    assert pushed == (0, 0) if caps == "pull" else \
        0 < pushed[0] < ran and pushed[1] >= 3
    assert np.array_equal(open_, other[2])
    assert np.array_equal(seen, other[3])
    for a, b in zip(hops, other[4]):
        assert np.array_equal(a, b)
    # and a level is what a plain pull of the one before makes of it
    seeds = np.zeros((n, W), np.uint32)
    for q in range(lanes):
        seeds[srcs[q], q // 32] |= np.uint32(1 << (q % 32))
    assert np.array_equal(hops[0], _pull_reference(rel, seeds) & ~seeds)


@pytest.mark.parametrize("keep_hops", [False, True])
@pytest.mark.parametrize("caps_name", ["pull", "mixed"])
def test_the_tree_with_the_block_counts_what_one_without_counts(caps_name,
                                                                keep_hops):
    """make_ell_tree: a recurse stage of three hops and a hop stage over
    its set, both over the hub relation. Counts, traversed edges, pushed
    hops and their slots, sets and hop masks equal with the block and
    without."""
    import jax

    from dgraph_tpu.ops.bfs import (device_ell, make_ell_tree, out_csr,
                                    prepare_parts)
    rel = _hub_rel()
    n = rel.indptr.shape[0] - 1
    W = 2
    seeds = np.zeros((n + 1, W), np.uint32)
    seeds[160:n] = _random_lanes(n - 160, W, np.uint32, seed=5, per_lane=1)
    runs = []
    for rule in (HUB_RULE, NO_BLOCK):
        g = build_ell(rel.indptr, rel.indices, dense=rule)
        dev = device_ell(g)
        common = {
            "prepared": prepare_parts(dev, W),
            "perm_in": jax.device_put(np.concatenate(
                [g.perm_order, [n]]).astype(np.int32)),
            "out_idx": jax.device_put(np.concatenate(
                [g.new_of_old, [n]]).astype(np.int32)),
            "filt": None}
        tree = make_ell_tree([
            {**common, "kind": "recurse", "parent": ("seed", 0), "depth": 3,
             "keep_hops": keep_hops, "caps": HUB_CAPS[caps_name],
             "out": jax.device_put(out_csr(g, rel.indptr, rel.indices))},
            {**common, "kind": "hop", "parent": ("stage", 0)}], n, W)
        (seen, count, edges, pushed, slots, hops), mask = tree(
            (jax.device_put(seeds),), ())
        runs.append((np.asarray(seen)[np.append(g.new_of_old, n)],
                     np.asarray(count), np.asarray(edges),
                     (int(pushed), int(slots)),
                     None if hops is None else np.asarray(hops),
                     np.asarray(mask)))
    with_block, without = runs
    for a, b in zip(with_block, without):
        assert np.array_equal(a, b)
    seen, count, _edges, pushed, hops, mask = with_block
    # hop 1: the out-edges of the periphery's sources that carry a lane
    first = int(np.diff(rel.indptr)[(seeds[:n] != 0).any(axis=1)].sum())
    assert pushed == ((0, 0) if caps_name == "pull" else (1, first))
    assert (hops is not None) == keep_hops
    assert np.array_equal(mask[:n], _pull_reference(rel, seen[:n]))
    assert count.sum() == sum(bin(int(w)).count("1")
                              for w in seen[:n].ravel())
