"""Batched traversal over lane-packed frontier bitmaps — the throughput path.

Reference parity: the reference serves concurrent queries with goroutines,
each walking posting lists independently (worker/task.go, one goroutine per
`ProcessTaskOverNetwork`; LDBC SNB IC mixes in BASELINE.json run many
queries at once). The TPU-native equivalent batches B concurrent traversals
into the *lanes* of a dense frontier bitmap, word_bits lanes a mask word:

    mask[n_nodes + 1, W] uint32    bit q % 32 of mask[v, q // 32] is set
                                   iff node v is in query q's set

One hop for ALL queries is next[v] = OR of mask[u] over the edges u→v.

The point is access *width*: a random row gather on the v5e costs its
access, not its bytes (6.2 ns a gather of an 8-byte mask row: 45 M of them
in 279 ms, the pull of make_ell_step on the chip, PR 30; the ledger reads
the same since PR 23), so widening each access to a B-bit lane row
amortises the irregular-memory tax across B queries — the same shape the
reference can't reach because its per-query goroutines share nothing.
What a pull need not gather at all is cheaper still: the in-edges inside a
relation's hub core are one 0/1 matrix product (the dense hub block below:
a third of Graph500 scale 22's in-edges in 4.8 ms, the cell's pull 439 →
298 ms on the chip, PR 38).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["EllGraph", "build_ell", "ell_recurse",
           "DeviceEll", "device_ell", "prepare_parts", "make_ell_recurse",
           "out_csr", "push_caps",
           "make_ell_step", "make_ell_tree",
           "ell_weights", "relax_dtype", "make_ell_relax", "gather_pairs",
           "pack_seed_masks", "unpack_masks"]


# ---------------------------------------------------------------------------
# ELL pull-hop: the access-amortised form of the batched traversal.
#
# A hop over the COO edge list (gather mask[src], scatter-max into dst)
# pays one random row-gather AND one random row-scatter per edge, and on
# the v5e the scatter is the dear one: XLA:TPU applies a scatter's
# updates one after another, about 120 ns a slot for a gather of the
# source's row and a scatter-max of 64 lane bytes (PR 30, _push_hop: the
# same at 32 K, 64 K and 128 K slots a turn), against 6.2 ns a gather;
# and it sorts the updates first once they number over 2^16 to
# 2^17, a sort that takes the compiler longer than the whole pull. So for a
# frontier that covers the graph the winning shape is: (1) eliminate the
# scatter entirely by pulling over in-neighbor lists, and (2) amortise each
# access over as many concurrent queries as fit in the row (bit-packed
# lanes: W words = word_bits·W queries per access). One hop is then pure
# gathers + bitwise ORs — no scatter, no sort, fully static shapes — and
# costs one gather for every stored in-edge whatever the frontier holds.
# For a frontier of a few rows that is the waste: make_ell_step and the
# recurse stages of make_ell_tree therefore push, hop by hop, while the
# frontier's out-edges cost less pushed than a pull of this relation costs
# (push_caps: about a twentieth of its list slots), and pull otherwise
# (_pull_or_push, PR 30, 35 and 44); make_ell_recurse and a tree's hop
# stages pull always. And a pull need not gather every in-edge: the
# in-edges between the relation's high-out-degree and high-in-degree rows,
# a quarter to a third of a Kronecker or follower graph's, are a 0/1 matrix
# product (the dense hub block below, PR 38).
#
# Layout (PR 7, FeatGraph-style degree buckets): nodes are RENUMBERED by
# in-degree class so each class's output is a contiguous slice and the
# next-frontier mask is rebuilt by concatenation, not scatter. Two kernel
# templates:
#   * dense-lane ELL for the low-degree body (indeg ≤ SEG_MIN_DEG): one
#     [n_b, K] int32 block per EXACT degree K — zero padding — evaluated
#     as an unrolled gather-OR chain (fuses into one pass on CPU, one
#     VMEM-resident loop on TPU);
#   * segment-CSR for the heavy tail (indeg > SEG_MIN_DEG): neighbor
#     lists split into SEG_TILE-wide tiles ([M, SEG_TILE] int32, padded
#     only in each row's LAST tile), tile partials OR-reduced, then a
#     tiny second-level gather combines each heavy row's tiles (rows
#     bucketed by power-of-two tile count).
# Padding is bounded by SEG_TILE-1 slots per heavy row instead of the old
# power-of-4 ladder's up-to-4x blowup (BENCH r05: 58% of device edges
# were ELL padding; this layout measures <5% on the same graph).
# Reference: this plays codec/'s role of making posting data compact AND
# the UidPack role of block iteration — shaped for the MXU/VPU.

SEG_MIN_DEG = 32      # dense-lane ELL up to this in-degree; heavier → tiles
SEG_TILE = 8          # segment-CSR tile width (max padding per heavy row)
CHAIN_MAX = 32        # widest unrolled gather-OR chain; beyond → reduce

# The dense hub block (PR 38). The in-edges that run from the relation's
# high-out-degree rows (columns C) to its high-in-degree rows (rows R) leave
# the lists and are answered by one 0/1 matrix product a pulled hop
# (_dense_hit): a gathered in-edge costs the chip its access, 5.8-6.2 ns and
# its share of the second level, a cell of the block a byte of HBM and 64
# multiply-adds of an MXU nothing else in the hop uses, 1.8-2.3 ps (on the
# v5e, PR 38: 16,384 x 65,536 int8 against [65,536, 64] in 2.5 ms, 32,768 x
# 65,536 in 3.8 ms; bf16 operands the same). The two cross near 3,000 cells
# an edge. _choose_dense takes the block that saves most, an edge inside
# counted as one gather saved, a row of the block as one spent and a cell
# as 1/DENSE_CELLS_PER_EDGE of one: a sixth of the break-even, so that a
# block the rule takes cannot cost more than it saves (a Kronecker or
# follower graph's core stands at 100-130 cells an edge; LDBC's `knows`,
# whose best block holds 6 % of the edges at 530, gets none). The int8
# block may hold DENSE_MAX_BYTES, and is taken only if it saves
# DENSE_MIN_EDGES gathers a pull: a product has a fixed price (a gather of
# C rows, an unpack, a pack), and a graph of a few thousand edges builds
# the ELL it always did. Rows and columns are padded to DENSE_PAD.
DENSE_CELLS_PER_EDGE = 512
DENSE_MAX_BYTES = 2 << 30
DENSE_MIN_EDGES = 1 << 20
DENSE_PAD = 128

# What the v5e charges for a hop, each way (PR 44). _pull_or_push takes the
# cheaper way by these, so they are the chip's readings and not knobs:
#   * PULL_SLOT_NS, a list slot of a pull (a cell of a degree class's block
#     or of a tile, padding included), with its share of the second level
#     and of the mask's rebuild: a pull's device time over the relation's
#     slots, 5.8 ns on the Kronecker graph (298 ms less the block's product
#     over 51.8 M slots, PR 38), 6.2 on the follower graph (209.6 ms, 33.8
#     M), 6.15-6.17 on LDBC's `knows` (431.5-432.9 ms over 70.19 M, PR 37
#     and 44);
#   * DENSE_CELL_NS, a cell of the hub block in a pull's one product: 1.8
#     to 2.3 ps (PR 38, the readings above DENSE_CELLS_PER_EDGE);
#   * PUSH_SLOT_NS, a slot of a pushed hop (an out-edge of the frontier: a
#     gather of the source's row, 64 lane bytes, a scatter-max): 114-123
#     ns where the byte accumulator is 83 MB (the follower graph's 1.3 M
#     rows, PR 30), the same at 32 K, 64 K and 128 K slots a turn; 85-94
#     on `knows` (40 MB, 200 out-edges a row: 141.4 ms for 1.61 M slots,
#     PR 44). One price for every relation, so the dearer reading: a cap
#     a little under a relation's own break-even never costs a pull.
PULL_SLOT_NS = 6.1
DENSE_CELL_NS = 0.002
PUSH_SLOT_NS = 120.0


@dataclass
class EllGraph:
    """Degree-bucketed in-neighbor blocks over a permuted rank space.

    `parts` lists the dense-lane blocks in permuted row order:
    ("zero", None, rows) for the indeg-0 class, ("ell", [rows, K] int32,
    rows) per present degree K ≤ seg_min. `tiles`/`lvl2` hold the heavy
    tail's segment-CSR (tile matrix + per-tile-count combine indices);
    heavy rows sit after all dense rows in the permutation.

    `dense` is the hub block, or None where the relation has no core worth
    one (_choose_dense): (block [R, C] int8 0/1, cols int32[C]), block[j,
    c] set iff the edge cols[c] → row j of the block is stored, cols the
    block's columns in the permuted space, ascending, padded with the zero
    sentinel row n. Those `dense_edges` in-edges are in no list; in their
    place row j of the block lists ONE in-neighbour more, n + 1 + j: the
    row of the block's product that _ell_hop appends to the frontier
    after its sentinel, so that a row's share of the block is ORed in by
    the gather that ORs its other in-neighbours."""

    n: int                                  # node count
    parts: list                             # dense blocks, permuted order
    tiles: object                           # [M, seg_tile] int32 | None
    lvl2: list                              # [h_b, K2] int32 tile combines
    seg_rows: int                           # heavy (tail) row count
    outdeg: object                          # [n] f32, permuted space
    perm_order: object                      # new rank -> old rank
    new_of_old: object                      # old rank -> new rank
    ks: list = field(default_factory=list)  # dense widths present
    dense: object = None                    # (block, cols) | None
    dense_edges: int = 0                    # in-edges the block holds

    @functools.cached_property
    def nnz(self) -> int:
        """The relation's stored edges (exact: summed in float64)."""
        return int(self.outdeg.sum(dtype="float64"))

    @property
    def padded_edges(self) -> int:
        """Total level-1 gather slots (real edges + padding) — the device
        edge traffic per hop; `ell_padding_ratio` derives from it."""
        return _list_slots(self)


def _list_slots(rel) -> int:
    """The level-1 slots a pull of `rel` (an EllGraph or a DeviceEll)
    gathers: every cell of its degree classes' blocks and of its tiles."""
    return sum(int(e.size) for kind, e, _ in rel.parts if kind == "ell") \
        + (int(rel.tiles.size) if rel.tiles is not None else 0)


def _degree_ladder(deg, largest: int):
    """Candidate sets of rows by a degree THRESHOLD: (thresholds
    descending, set sizes ascending). For each target size DENSE_PAD·2^k
    and half-way between, up to `largest`, the lowest threshold d whose set
    {deg >= d} is no larger: whole degree classes, so a set is a function
    of the degree sequence and not of the nodes' names."""
    import numpy as np
    d, cnt = np.unique(deg[deg > 0], return_counts=True)
    atleast = np.cumsum(cnt[::-1])[::-1]            # rows of degree >= d[i]
    doubles = DENSE_PAD << np.arange(
        max(int(largest // DENSE_PAD), 1).bit_length(), dtype=np.int64)
    targets = np.sort(np.concatenate([doubles, doubles + doubles // 2]))
    at = np.searchsorted(-atleast, -targets)
    at = np.unique(at[at < len(d)])[::-1]           # sizes ascending
    return d[at], atleast[at]


def _padded(size):
    return -(-size // DENSE_PAD) * DENSE_PAD


def _choose_dense(indeg, outdeg, src, dst, cells_per_edge: float,
                  max_bytes: int, min_edges: int):
    """The hub block's thresholds (d_R, d_C), or None: rows R = {indeg >=
    d_R}, columns C = {outdeg >= d_C}. Of every pair of the two degree
    ladders' sets whose padded int8 block fits `max_bytes`, the one that
    saves most: an edge inside is one gather saved, a row of the block one
    gather spent (its row of the product, one in-neighbour more) and a
    cell 1 / `cells_per_edge` of one; None where the best saves under
    `min_edges`. One pass over the edges counts the inside of every pair
    (a 2-D histogram over the ladders, summed cumulatively). Everything it
    reads is unchanged by a renaming of the nodes."""
    import numpy as np
    if len(dst) < min_edges:            # cannot save what it does not hold
        return None
    thr_r, size_r = _degree_ladder(indeg, max_bytes // DENSE_PAD)
    thr_c, size_c = _degree_ladder(outdeg, max_bytes // DENSE_PAD)
    if not len(thr_r) or not len(thr_c):
        return None

    def rung(deg, thr):
        """The smallest set of the ladder that holds each row (len(thr):
        none does); a ladder has under a hundred sets."""
        return (len(thr) - np.searchsorted(thr[::-1], deg, side="right")
                ).astype(np.uint8)

    # one small key an edge: the tables it gathers from fit a cache
    stride = len(thr_c) + 1
    key = rung(indeg, thr_r)[dst].astype(np.uint16) * np.uint16(stride)
    key += rung(outdeg, thr_c)[src]
    inside = np.bincount(key, minlength=(len(thr_r) + 1) * stride
                         ).reshape(len(thr_r) + 1, stride)[:-1, :-1]
    inside = inside.cumsum(axis=0).cumsum(axis=1)
    cells = np.outer(_padded(size_r), _padded(size_c))
    saved = np.where(cells <= max_bytes,
                     inside - size_r[:, None] - cells / cells_per_edge,
                     -np.inf)
    i, j = np.unravel_index(np.argmax(saved), saved.shape)
    if saved[i, j] < min_edges:
        return None
    return int(thr_r[i]), int(thr_c[j])


def build_ell(indptr, indices, seg_min: int = SEG_MIN_DEG,
              seg_tile: int = SEG_TILE, dense: tuple | None = None
              ) -> EllGraph:
    """Build the bucketed ELL + segment-CSR blocks from a CSR relation,
    and the dense hub block beside them where the relation has a core
    (`dense`: _choose_dense's (cells an edge, byte cap, edge floor); None
    for the module's constants, tests pass their own).

    Host-side, once per (snapshot, predicate, direction) — every array is
    produced by whole-graph vectorized passes (one stable argsort for the
    CSR transpose plus O(E) fills), not per-node Python loops: the PR-7
    rewrite took the 1M-node bench build from ~9 s to ~4 s, and the
    amortization story (engine/batch plan + ELL caches) makes even that a
    once-per-snapshot cost."""
    import numpy as np

    n = indptr.shape[0] - 1
    deg_out = np.diff(indptr).astype(np.int64)
    src = np.repeat(np.arange(n, dtype=np.int32), deg_out)
    dst = indices
    indeg = (np.bincount(dst, minlength=n).astype(np.int64) if n
             else np.zeros(0, np.int64))
    hub = _choose_dense(indeg, deg_out, src, dst, *(dense or (
        DENSE_CELLS_PER_EDGE, DENSE_MAX_BYTES, DENSE_MIN_EDGES)))
    hub_rows = np.zeros(0, np.int64)
    dense_edges = 0
    if hub is not None:
        # the block's edges leave the lists; in their place each row of
        # the block has ONE in-neighbour more, row n + 1 + j of the
        # frontier as _ell_hop extends it: its own row of the product
        in_r, in_c = indeg >= hub[0], deg_out >= hub[1]
        inside = in_r[dst] & in_c[src]
        hub_rows = np.nonzero(in_r)[0]
        hub_dst, hub_src = dst[inside], src[inside]
        dense_edges = len(hub_dst)
        np.logical_not(inside, out=inside)          # now: the edges kept
        src = np.concatenate([src[inside], n + 1 + np.arange(
            len(hub_rows), dtype=np.int32)])
        dst = np.concatenate([dst[inside], hub_rows.astype(dst.dtype)])
        indeg = np.bincount(dst, minlength=n).astype(np.int64)
    # CSR transpose: in-neighbors grouped by destination, sources
    # ascending within each group (stable sort keeps src order)
    order = np.argsort(dst, kind="stable")
    csrc = src[order]
    cindptr = np.concatenate([[0], np.cumsum(indeg)])

    small = indeg <= seg_min
    ks = sorted(int(k) for k in np.unique(indeg[small])) if n else [0]
    bucket = np.full(n, len(ks), np.int64)
    bucket[small] = np.searchsorted(np.array(ks), indeg[small])
    heavy = ~small
    ntiles = np.zeros(n, np.int64)
    ntiles[heavy] = -(-indeg[heavy] // seg_tile)
    # permutation: dense degree classes ascending, then the heavy tail by
    # tile count; first-neighbor secondary order gives consecutive rows
    # nearby gather targets (cache-line sharing on CPU, DMA locality on
    # TPU) at zero extra cost
    first_nbr = np.full(n, n, np.int64)
    nz = indeg > 0
    first_nbr[nz] = csrc[cindptr[:-1][nz]]
    sort_key = np.where(heavy, len(ks) + ntiles, bucket)
    perm_order = np.lexsort((first_nbr, sort_key))
    new_of_old = np.empty(n, np.int64)
    new_of_old[perm_order] = np.arange(n)
    # a source's place in the frontier the lists read: a node's permuted
    # rank and, after the sentinel n, the rows of the block's product
    place = np.concatenate([new_of_old,
                            np.arange(n, n + 1 + len(hub_rows))])
    cnew = place[csrc] if len(csrc) else csrc.astype(np.int64)

    def fill_rows(nodes, K):
        """[len(nodes), K] in-neighbor block (pad=n), one vector pass."""
        nb = np.full((len(nodes), K), n, np.int32)
        deg = indeg[nodes]
        total = int(deg.sum())
        if total:
            cum = np.cumsum(deg)
            base = np.repeat(cum - deg, deg)
            ar = np.arange(total)
            flat = np.repeat(cindptr[nodes], deg) + ar - base
            nb[np.repeat(np.arange(len(nodes)), deg), ar - base] = \
                cnew[flat]
        return nb

    counts = np.bincount(bucket, minlength=len(ks) + 1)
    parts = []
    off = 0
    for i, K in enumerate(ks):
        nodes = perm_order[off:off + counts[i]]
        off += counts[i]
        if K == 0:
            parts.append(("zero", None, len(nodes)))
        else:
            parts.append(("ell", fill_rows(nodes, K), len(nodes)))
    heavy_nodes = perm_order[off:]
    seg_rows = len(heavy_nodes)
    tiles = None
    lvl2 = []
    if seg_rows:
        hdeg = indeg[heavy_nodes]
        hnt = -(-hdeg // seg_tile)
        M = int(hnt.sum())
        tiles = np.full((M, seg_tile), n, np.int32)
        total = int(hdeg.sum())
        cum = np.cumsum(hdeg)
        base = np.repeat(cum - hdeg, hdeg)
        ar = np.arange(total)
        within = ar - base
        tile_start = np.concatenate([[0], np.cumsum(hnt)])[:-1]
        flat = np.repeat(cindptr[heavy_nodes], hdeg) + within
        slot = np.repeat(tile_start * seg_tile, hdeg) + within
        tiles[slot // seg_tile, slot % seg_tile] = cnew[flat]
        # second level: combine each heavy row's tile partials; rows are
        # already ntile-sorted, so power-of-two buckets are contiguous
        k2s = sorted(set(int(1 << max(int(t - 1).bit_length(), 0))
                         for t in np.unique(hnt)))
        b2 = np.searchsorted(np.array(k2s), hnt)
        c2 = np.bincount(b2, minlength=len(k2s))
        off2 = 0
        for i, K2 in enumerate(k2s):
            rows = np.arange(off2, off2 + c2[i])
            off2 += c2[i]
            t2 = np.full((len(rows), K2), M, np.int32)  # M = zero partial
            d2 = hnt[rows]
            tot2 = int(d2.sum())
            if tot2:
                cum2 = np.cumsum(d2)
                base2 = np.repeat(cum2 - d2, d2)
                ar2 = np.arange(tot2)
                t2[np.repeat(np.arange(len(rows)), d2), ar2 - base2] = \
                    np.repeat(tile_start[rows], d2) + ar2 - base2
            lvl2.append(t2)
    block = None
    if hub is not None:
        # rows in the nodes' own order, columns ascending in the permuted
        # space; the padding rows are empty, the padding columns read the
        # zero sentinel row
        row_of = np.zeros(n, np.int64)
        row_of[hub_rows] = np.arange(len(hub_rows))
        cols = np.sort(new_of_old[in_c])
        cells = np.zeros((_padded(len(hub_rows)), _padded(len(cols))),
                         np.int8)
        cells[row_of[hub_dst],
              np.searchsorted(cols, new_of_old[hub_src])] = 1
        block = (cells, np.concatenate(
            [cols, np.full(cells.shape[1] - len(cols), n)]
        ).astype(np.int32))
    return EllGraph(n=n, parts=parts, tiles=tiles, lvl2=lvl2,
                    seg_rows=seg_rows,
                    outdeg=deg_out[perm_order].astype(np.float32),
                    perm_order=perm_order, new_of_old=new_of_old, ks=ks,
                    dense=block, dense_edges=dense_edges)


def pack_seed_masks(g: EllGraph, rank_lists,
                    word_bits: int = 32) -> "jnp.ndarray":
    """B seed rank lists (OLD rank space) → [n+1, B/word_bits] packed mask
    in the permuted space, sentinel zero row last. B must be a multiple of
    `word_bits` (32 for the serving default, 64 under jax.enable_x64)."""
    import numpy as np
    B = len(rank_lists)
    assert B % word_bits == 0, "lane count must pack into mask words"
    dt = np.uint32 if word_bits == 32 else np.uint64
    m = np.zeros((g.n + 1, B // word_bits), dt)
    for q, ranks in enumerate(rank_lists):
        r = g.new_of_old[np.asarray(ranks, np.int64)]
        m[r, q // word_bits] |= dt(1 << (q % word_bits))
    return m


def unpack_masks(g: EllGraph, mask, word_bits: int = 32) -> list:
    """[n+1, W] packed mask → list of B sorted OLD-rank arrays."""
    import numpy as np
    m = np.asarray(mask)[:g.n]
    dt = m.dtype.type
    out = []
    for q in range(m.shape[1] * word_bits):
        rows = np.nonzero(
            (m[:, q // word_bits] >> dt(q % word_bits)) & dt(1))[0]
        out.append(np.sort(g.perm_order[rows]).astype(np.int32))
    return out


# bytes a reduce-form gather may NOMINALLY materialise before row-chunking
# ([rows, K, W] for chains wider than CHAIN_MAX — only the widest lvl2
# combine buckets take this path, and their row counts shrink as K2 grows,
# so chunking is a guard rail for adversarial degree distributions, not a
# tuned path)
GATHER_BUDGET = 12 << 30


@dataclass
class DeviceEll:
    """EllGraph's index arrays resident on device, word-dtype independent
    (indices are int32 whatever the mask word width)."""

    n: int
    parts: list            # ("zero", None, rows) | ("ell", dev, rows)
    tiles: object          # device [M, seg_tile] | None
    lvl2: list             # device [h_b, K2] blocks
    seg_rows: int
    dense: object = None   # device (block, cols) of EllGraph.dense | None
    # the same edges by SOURCE row, for the pushed hops of make_ell_step
    # and of make_ell_tree's recurse stages (out_csr, on the device; its
    # third array is the rows' out-degrees). None until one of the two is
    # asked for (engine/batch.py _dev_with_out builds it once, for both),
    # so the pull-only programs (make_ell_recurse, a tree of hop stages)
    # never pay for it
    out: object = None


def device_ell(g: EllGraph) -> DeviceEll:
    parts = [(kind, jax.device_put(e) if e is not None else None, rows)
             for kind, e, rows in g.parts]
    return DeviceEll(
        n=g.n, parts=parts,
        tiles=jax.device_put(g.tiles) if g.tiles is not None else None,
        lvl2=[jax.device_put(t) for t in g.lvl2], seg_rows=g.seg_rows,
        dense=jax.device_put(g.dense) if g.dense is not None else None)


def out_csr(g: EllGraph, indptr, indices) -> tuple:
    """The relation's out-edges in `g`'s permuted row space, for
    DeviceEll.out once on the device: (indptr int32[n+1], indices
    int32[E], deg int32[n]). Row r is old row perm_order[r], its targets
    relabelled by new_of_old and kept in their stored order."""
    import numpy as np
    deg = np.diff(indptr).astype(np.int64)[g.perm_order]
    ptr = np.zeros(g.n + 1, np.int64)
    np.cumsum(deg, out=ptr[1:])
    # permuted position p of row r reads old position
    # indptr[perm_order[r]] + (p - ptr[r])
    old_pos = np.repeat(
        np.asarray(indptr[:-1], np.int64)[g.perm_order] - ptr[:-1], deg)
    old_pos += np.arange(len(old_pos))
    idx = g.new_of_old[np.asarray(indices)[old_pos]].astype(np.int32)
    return ptr.astype(np.int32), idx, deg.astype(np.int32)


def prepare_parts(dev: DeviceEll, W: int):
    """Pre-shape the device blocks for a hop at lane width W. The XLA
    path uses the blocks as-is (the gather-OR chain never materialises a
    [rows, K, W] intermediate); under DGRAPH_TPU_PALLAS=1 dense blocks
    and the tile matrix are row-padded for the Pallas DMA-ring hop
    (ops/pallas_hop.py) instead — at the widths the compiled kernel
    moves (whole 128-word rows); narrower masks keep the XLA hop. `caps`
    is push_caps of the relation, for the programs that may push a hop."""
    import os
    use_pallas = os.environ.get("DGRAPH_TPU_PALLAS", "") == "1"
    if use_pallas:
        # import only under the flag: the default XLA path must not
        # couple to the experimental pallas namespace
        from dgraph_tpu.ops.pallas_hop import BLOCK_ROWS, LANE_WORDS
        use_pallas = W % LANE_WORDS == 0

    def pad_rows(e):
        n_b = e.shape[0]
        padded = -(-n_b // BLOCK_ROWS) * BLOCK_ROWS
        if padded == n_b:
            return jnp.asarray(e, jnp.int32), n_b
        pad = jnp.full((padded - n_b, e.shape[1]), dev.n, jnp.int32)
        return jnp.concatenate([jnp.asarray(e, jnp.int32), pad]), n_b

    parts = []
    for kind, e, rows in dev.parts:
        if kind == "zero" or rows == 0:
            parts.append(("zero", None, rows))
        elif use_pallas:
            parts.append(("pallas", *pad_rows(e)))
        else:
            parts.append(("chain", e, rows))
    tiles = None
    if dev.tiles is not None and dev.seg_rows:
        if use_pallas and dev.tiles.shape[0]:
            tiles = ("pallas", *pad_rows(dev.tiles))
        else:
            tiles = ("chain", dev.tiles, dev.tiles.shape[0])
    return {"parts": parts, "tiles": tiles, "lvl2": list(dev.lvl2),
            "dense": dev.dense, "seg_rows": dev.seg_rows, "n": dev.n,
            "caps": push_caps(dev)}


# Sticky fail-safe: the first bucket_hop_pallas that fails to trace or
# compile flips this and every pallas bucket (this one included) falls
# back to the XLA gather hop — a failed Mosaic compile must degrade a
# perf experiment, never take the serving path down. Counted
# (`pallas_fallback_total`, `pallas_degraded`): chip_smoke.py requires
# both to stay zero.
_pallas_failed = False


def _chain_or(frontier, e, dtype):
    """out[i] = OR_k frontier[e[i, k]] as an unrolled gather chain —
    XLA fuses the K gathers into one output pass (no [rows, K, W]
    intermediate; measured ~3x the lax.reduce form on the CPU backend).
    Chains wider than CHAIN_MAX fall back to the reduce form, chunked
    when the nominal intermediate would blow GATHER_BUDGET."""
    rows, K = e.shape
    W = frontier.shape[1]
    if K <= CHAIN_MAX:
        acc = frontier[e[:, 0]]
        for k in range(1, K):
            acc = acc | frontier[e[:, k]]
        return acc
    row_bytes = K * W * frontier.dtype.itemsize
    if rows * row_bytes <= GATHER_BUDGET:
        return lax.reduce(frontier[e], dtype(0), lax.bitwise_or, (1,))
    ch = max(1, min(GATHER_BUDGET // row_bytes, rows))
    nch = -(-rows // ch)
    pad = jnp.full((nch * ch - rows, K), frontier.shape[0] - 1, jnp.int32)
    e3 = jnp.concatenate([e, pad]).reshape(nch, ch, K)
    out = lax.map(
        lambda c: lax.reduce(frontier[c], dtype(0), lax.bitwise_or, (1,)),
        e3)
    return out.reshape(-1, W)[:rows]


def _pallas_bucket_part(e, n_b, frontier):
    """One pallas block's hop with XLA-gather fallback. The padded rows
    index frontier's all-zero sentinel row, so the gather form is exact
    on the same padded input."""
    global _pallas_failed
    from dgraph_tpu.utils.metrics import METRICS
    if not _pallas_failed:
        try:
            from dgraph_tpu.ops.pallas_hop import bucket_hop_pallas
            return bucket_hop_pallas(e, frontier)[:n_b]
        except Exception:  # noqa: BLE001 — any trace/compile failure
            _pallas_failed = True
            METRICS.set_gauge("pallas_degraded", 1.0)
            from dgraph_tpu.utils import logging as xlog
            xlog.get("ops").warning(
                "pallas hop failed to trace/compile; falling back to "
                "the XLA gather hop for every bucket (perf experiment "
                "degraded, results unaffected)", exc_info=True)
    # counted per fallback BUCKET TRACE (this body runs at trace time,
    # once per compiled program, not per execution): the sticky
    # degradation stays visible in /debug/prometheus_metrics instead of
    # one log line scrolling away
    METRICS.inc("pallas_fallback_total")
    return lax.reduce(frontier[e], frontier.dtype.type(0),
                      lax.bitwise_or, (1,))[:n_b]


def _dense_hit(dense, frontier, W, dtype):
    """The hub block's share of a pull: [R, W] packed rows, bit q of row j
    set iff some column of row j is a row of `frontier` with bit q. One
    gather of the block's C columns, unpacked to one int8 a lane, and one
    0/1 matrix product on the MXU: exact, an int32 sum of at most C ones."""
    block, cols = dense
    word_bits = jnp.dtype(dtype).itemsize * 8
    shifts = jnp.arange(word_bits, dtype=dtype)
    lanes = ((frontier[cols][:, :, None] >> shifts) & dtype(1)).astype(
        jnp.int8).reshape(cols.shape[0], W * word_bits)
    hit = lax.dot_general(block, lanes, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.int32) > 0
    return lax.reduce(
        hit.reshape(-1, W, word_bits).astype(dtype) << shifts,
        dtype(0), lax.bitwise_or, (2,))


def _ell_hop(prepared, frontier, W, dtype=jnp.uint32):
    """next[v] = OR of frontier[u] over in-neighbors u — gathers, and
    first one matrix product where the relation has a hub block
    (_dense_hit: its rows ride behind the frontier's sentinel, one more
    in-neighbour of each row of the block). Dense degree classes run as
    gather-OR chains; the heavy tail runs tile partials + the tiny
    second-level combine; "pallas" blocks ride the explicit DMA-ring
    kernel (ops/pallas_hop.py), falling back to the gather if it fails to
    trace/compile (_pallas_bucket_part)."""
    if prepared.get("dense") is not None:
        # rows n + 1 + j: what the lists of the block's rows read of it
        frontier = jnp.concatenate(
            [frontier, _dense_hit(prepared["dense"], frontier, W, dtype)])
    outs = []
    for kind, e, rows in prepared["parts"]:
        if kind == "zero":
            outs.append(jnp.zeros((rows, W), dtype))
        elif kind == "pallas":
            outs.append(_pallas_bucket_part(e, rows, frontier))
        else:
            outs.append(_chain_or(frontier, e, dtype))
    tiles = prepared["tiles"]
    if tiles is not None:
        tkind, te, trows = tiles
        if tkind == "pallas":
            acc = _pallas_bucket_part(te, trows, frontier)
        else:
            acc = _chain_or(frontier, te, dtype)
        partials = jnp.concatenate([acc, jnp.zeros((1, W), dtype)])
        for t2 in prepared["lvl2"]:
            outs.append(_chain_or(partials, t2, dtype))
    outs.append(jnp.zeros((1, W), dtype))       # sentinel row
    return jnp.concatenate(outs, axis=0)


COUNT_BLK = 1 << 15   # edge-counter node-block rows (bounds unpack memory)


def _lane_sums(mask, weights, n, W, word_bits):
    """int32[lanes]: per lane, the sum of `weights` (int32[n]; None for
    all ones, the lane's population count) over the rows of mask[:n] that
    carry the lane's bit. Unpacked and accumulated in integers, a block
    of COUNT_BLK rows a turn, so nothing of n x lanes is ever held and
    the sum is exact wherever it fits int32. (A float32 matvec on the MXU
    is exact only while a lane's total stays under 2^24, and a lane of a
    3-hop over a Kronecker graph sums out-degrees past 4*10^7; on the
    v5e both sums of a 2.4 M-row mask take 0.4 ms, PR 34.)"""
    pad = -max(n, 1) % COUNT_BLK
    fpad = jnp.concatenate([mask[:n], jnp.zeros((pad, W), mask.dtype)])
    if weights is not None:
        weights = jnp.concatenate(
            [weights.astype(jnp.int32), jnp.zeros((pad,), jnp.int32)])
    shifts = jnp.arange(word_bits, dtype=mask.dtype)

    def body(i, acc):
        sl = lax.dynamic_slice_in_dim(fpad, i * COUNT_BLK, COUNT_BLK, 0)
        bits = ((sl[:, :, None] >> shifts) & mask.dtype.type(1)
                ).astype(jnp.int32).reshape(COUNT_BLK, W * word_bits)
        if weights is not None:
            bits = bits * lax.dynamic_slice_in_dim(
                weights, i * COUNT_BLK, COUNT_BLK, 0)[:, None]
        return acc + bits.sum(axis=0, dtype=jnp.int32)

    return lax.fori_loop(0, fpad.shape[0] // COUNT_BLK, body,
                         jnp.zeros((W * word_bits,), jnp.int32))


def _as_arguments(blocks):
    """(held, rebuild) of a pytree of index blocks: `held` its device
    arrays, for a jitted program to take as an argument, and
    rebuild(arrays) the pytree with `arrays` in their places. A device
    array that a jitted function closes over is a constant of its
    program: fetched to the host, compiled in and uploaded again, 15 s of
    the first call for the out-CSR's 180 MB on the chip, and a second
    copy on the device (PR 30); a hub block is ten times that."""
    leaves, tree = jax.tree_util.tree_flatten(blocks)
    held = [x for x in leaves if isinstance(x, jax.Array)]

    def rebuild(arrays):
        it = iter(arrays)
        return tree.unflatten([next(it) if isinstance(x, jax.Array) else x
                               for x in leaves])

    return held, rebuild


def make_ell_recurse(dev: DeviceEll, outdeg, n: int, W: int,
                     count_edges: bool = True, word_bits: int = 32):
    """Compile a depth-parameterised loop=false @recurse over a DeviceEll
    already resident on device. Returns fn(mask0, depth[, keep_hops]) →
    (last[n+1,W], seen[n+1,W], edges[B] int32[, hops]). The seed mask is
    DONATED: the scan reuses its buffer for the frontier carry instead of
    holding seed + frontier + seen live (callers re-put per launch). The
    index blocks ride as arguments (_as_arguments)."""
    dtype = jnp.uint32 if word_bits == 32 else jnp.uint64
    held, rebuild = _as_arguments((
        prepare_parts(dev, W),
        jnp.asarray(outdeg).astype(jnp.int32) if count_edges else None))

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("depth", "keep_hops"))
    def recurse(arrays, mask0, depth: int, keep_hops: bool = False):
        prepared, outdeg = rebuild(arrays)

        def hop(carry, _):
            frontier, seen = carry
            nxt = _ell_hop(prepared, frontier, W, dtype)
            fresh = nxt & ~seen
            seen = seen | fresh
            return (fresh, seen), (fresh if keep_hops else None)

        (last, seen), hops = lax.scan(
            hop, (mask0, mask0), None, length=depth)
        if count_edges:
            # exact per-lane counters from the final masks — identical
            # integers to the per-hop accumulation because first-visit
            # frontiers partition seen \ last
            edges = _lane_sums(seen & ~last, outdeg, n, W, word_bits)
        else:
            edges = jnp.zeros((W * word_bits,), jnp.int32)
        if keep_hops:
            # hops[h] = the FRESH mask after hop h+1 (first-visit sets) —
            # what tree reconstruction needs (engine batch path)
            return last, seen, edges, hops
        return last, seen, edges

    return functools.partial(recurse, held)


# A hop is pushed when a push is the cheaper way (PR 44): while the
# frontier's out-edges number at most the slot cap, what a pull of THIS
# relation costs (its list slots and, where it has one, its hub block's
# cells, at the prices above) over a pushed slot's price. That is about a
# twentieth of the list slots, less for a relation whose hub block makes
# its pull cheaper than its edge count says, and scaled from the relation
# so that a small graph keeps a pull side. (Until PR 44 the cap stood at a
# 128th of the edges whatever the relation, a sixth of the break-even: a
# `knows` launch pulled a hop of 1.2-1.9 M out-edges for 431.5 ms that a
# push answers in 140-230.) The frontier's rows with an out-edge may number
# a PUSH_FANOUT-th of the slot cap. A push expands them PUSH_CHUNK slots a
# turn: XLA:TPU sorts the updates of a scatter of over 2^16 to 2^17, and
# that sort alone takes longer to compile than the whole pull; so a row of
# over PUSH_CHUNK out-edges sends its hop to the pull, whatever the caps. A
# turn takes at most a PUSH_TURN_FANOUT-th as many rows as slots (a row of
# the follower cell's frontiers has 25 to 50 out-edges).
PUSH_FANOUT = 32
PUSH_CHUNK = 1 << 15
PUSH_TURN_FANOUT = 8


def push_caps(rel) -> tuple:
    """(row cap, slot cap, slots a turn) of the pushed hop over `rel`, an
    EllGraph or a DeviceEll: the slot cap is the frontier at which a push
    costs what a pull of `rel` costs."""
    pull_ns = _list_slots(rel) * PULL_SLOT_NS
    if rel.dense is not None:
        pull_ns += int(rel.dense[0].size) * DENSE_CELL_NS
    e_cap = int(pull_ns / PUSH_SLOT_NS)
    return e_cap // PUSH_FANOUT, e_cap, min(PUSH_CHUNK, max(e_cap, 1))


ROWS_BLK = 128        # _set_rows' block: one row gather finds a row's place


def _set_rows(act, n: int, cap: int, step: int):
    """The first `cap` set positions of act[n], ascending, padded with n:
    jnp.nonzero(act, size=cap, fill_value=n) without its cumsum and
    scatter over all n (12.5 ms on the v5e at 1.3 M rows, and seconds of
    compile). Two levels: a search of the per-block counts finds the
    block of the k-th set row, a prefix sum of that block's flags (a
    matmul with a triangle: 0/1 inputs, exact) its place inside. `step`
    positions a turn of a loop, for as many turns as the set rows fill:
    it costs the rows it finds, not the cap, so a hop of 64 seeds pays
    for one turn whatever the relation's row cap (PR 44)."""
    step = min(step, cap)
    nb = -(-n // ROWS_BLK)
    blk = jnp.concatenate(
        [act, jnp.zeros((nb * ROWS_BLK - n,), bool)]).reshape(nb, ROWS_BLK)
    cnt = blk.sum(axis=1, dtype=jnp.int32)
    ends = jnp.cumsum(cnt)
    tri = jnp.tri(ROWS_BLK, dtype=jnp.float32).T

    def some(i, rows):
        k = i * step + jnp.arange(step, dtype=jnp.int32)
        b = jnp.minimum(jnp.searchsorted(ends, k, side="right"),
                        nb - 1).astype(jnp.int32)
        nth = (k - (ends[b] - cnt[b])).astype(jnp.float32)  # in the block
        upto = jnp.dot(blk[b].astype(jnp.float32), tri)
        place = (upto <= nth[:, None]).sum(axis=1, dtype=jnp.int32)
        return lax.dynamic_update_slice(
            rows, jnp.where(k < ends[-1], b * ROWS_BLK + place, n),
            (i * step,))

    rows = lax.fori_loop(
        0, -(-jnp.minimum(ends[-1], cap) // step), some,
        jnp.full((-(-cap // step) * step,), n, jnp.int32))
    return rows[:cap]


def _push_hop(out, f, act, n, W, dtype, word_bits, f_cap, chunk):
    """next[v] = OR of f[u] over the out-edges u→v of the frontier's own
    rows: the same array _ell_hop gives, from as many slots as the
    frontier has out-edges. `act` marks the rows of f[:n] that have a bit
    and an out-edge; the caller has checked that they are at most f_cap
    and that none has over `chunk` out-edges.

    The rows are taken in ascending order, as many a turn as have
    `chunk` out-edges between them and at most `win` (ops/hop.py
    gather_edges expands them into flat slots), for as many turns as the
    frontier takes. A slot's lanes travel one byte each, so that the OR
    over the slots of one destination is a scatter-max. Slots beyond a
    turn's last edge carry an out-of-range destination and are dropped,
    so the sentinel row n stays zero."""
    from dgraph_tpu.ops.hop import gather_edges
    indptr, indices, deg = out
    win = min(f_cap, max(chunk // PUSH_TURN_FANOUT, 1))
    rows = _set_rows(act, n, f_cap, win)
    ends = jnp.cumsum(jnp.take(deg, rows, mode="fill", fill_value=0))
    # (both bounds hold by the caller's check; the loop ends without it)
    count = jnp.minimum(act.sum(dtype=jnp.int32), f_cap)
    # padding row n has no out-edge (indptr[n + 1] clips to indptr[n])
    rows = jnp.concatenate([rows, jnp.full((win,), n, jnp.int32)])
    shifts = jnp.arange(word_bits, dtype=dtype)

    def turn(carry):
        r0, acc = carry
        before = jnp.where(r0 > 0, ends[r0 - 1], 0)
        r1 = jnp.clip(jnp.searchsorted(ends, before + chunk, side="right"),
                      r0 + 1, r0 + win).astype(jnp.int32)
        mine = lax.dynamic_slice_in_dim(rows, r0, win)
        mine = jnp.where(r0 + jnp.arange(win) < r1, mine, n)
        dst, seg, _pos, _valid, _total = gather_edges(indptr, indices,
                                                      mine, chunk)
        bits = ((f[mine][seg][:, :, None] >> shifts) & dtype(1)).astype(
            jnp.uint8).reshape(chunk, W * word_bits)
        return r1, acc.at[dst].max(bits, mode="drop")

    _r, acc = lax.while_loop(
        lambda carry: carry[0] < count, turn,
        (jnp.int32(0), jnp.zeros((n + 1, W * word_bits), jnp.uint8)))
    return lax.reduce(
        acc.reshape(n + 1, W, word_bits).astype(dtype) << shifts,
        dtype(0), lax.bitwise_or, (2,))


def _pull_or_push(prepared, out, f, caps, n, W, dtype, word_bits):
    """One hop of a lane program, computed one of two exact ways chosen
    on the device from the frontier `f` it is handed: (next mask [n+1, W],
    whether it was pushed, the slots it pushed: 0 for a pull). A PUSH over
    the frontier's own out-edges (`out`, _push_hop) when `caps` (row cap,
    slot cap, slots a turn) hold its rows with a bit and an out-edge, the
    sum of their out-degrees (its slots) and the largest of them, else the
    PULL over every stored in-edge (_ell_hop). Caps that hold no row
    compile no push: every hop pulls."""
    f_cap, e_cap, chunk = caps
    pull = functools.partial(_ell_hop, prepared, f, W, dtype)
    if not f_cap:
        return pull(), False, 0
    outdeg = out[2]
    act = (f[:n] != 0).any(axis=1) & (outdeg > 0)
    degs = jnp.where(act, outdeg, 0)
    slots = degs.sum(dtype=jnp.int32)
    fits = ((act.sum(dtype=jnp.int32) <= f_cap) & (slots <= e_cap)
            & (degs.max() <= chunk))
    nxt = lax.cond(
        fits,
        lambda: _push_hop(out, f, act, n, W, dtype, word_bits, f_cap,
                          chunk),
        pull)
    return nxt, fits, jnp.where(fits, slots, 0)


def make_ell_step(dev: DeviceEll, n: int, W: int, levels: int,
                  word_bits: int = 32, first_visit: bool = True,
                  caps: tuple | None = None):
    """Compile a RESUMABLE hop block that stops itself:
    fn(frontier, seen, near, open_lanes, limit) →
    (frontier', seen', hops, ran, open_lanes', pushed, slots).

    It runs hops until no lane is open or `limit` (a traced scalar, at
    most `levels`) is reached, and at least one a call, so a staged
    traversal always advances. `open_lanes` is the packed mask [W] of
    lanes still open. Three rules close a lane, two of them here and one
    before the launch:
      * exhausted: no row of the hop's fresh mask carries the lane's bit;
      * ahead (`first_visit` only): some row of the fresh mask carries
        the lane's bit in `near` too. `near` [n+1, W] (permuted space,
        sentinel row zero, not donated: every stage reads it) has bit q
        of row r set iff r is one or two edges before lane q's target
        (an in-neighbour of it or, where the caller read the second
        level for the lane, an in-neighbour of one), so the target is
        one or two edges beyond this hop and the hops that would show
        it and its parent are never run: the caller finishes the path
        from the in-neighbours, which it has to read for the walk back
        anyway, and tells by the row that was hit which level closed
        the lane;
      * at the seed, by the caller: a lane whose source is itself such
        a row, or whose target has no in-neighbour, is never opened.
    With the first level whole, a target cannot show in a fresh mask
    while its lane is open (an in-neighbour would have carried the bit a
    hop sooner), so the program tests no target row. The rules are those
    of engine/batch.py's host scan, which stays the authority on which
    lane closed where. `hops` is a tuple of `levels` masks [n+1, W]
    (separate arrays, so a caller copies back only what was run):
    hops[h] is the fresh mask of this call's hop h+1 for h < `ran`, and
    not data beyond. Both mask carries are DONATED — successive blocks
    of a staged traversal (engine/batch.py's shortest groups) hand their
    buffers forward instead of re-allocating per stage, the donation
    contract the README documents.

    Each hop computes the same next mask one of two exact ways, chosen
    on the device from the frontier it is handed (_pull_or_push over
    `dev.out`). `pushed` counts the hops of this call that pushed and
    `slots` the out-edges they expanded (both int32). `caps` is push_caps
    of the relation; tests pass their own.

    `first_visit=False` drops the seen-masking: hops[h] is then the FULL
    set reachable in exactly h+1 hops (the level-DAG the k-shortest
    enumeration consumes), with `seen` passed through untouched, and a
    lane closes only when its frontier is exhausted: that program makes
    no use of `near`, and its caller passes None."""
    dtype = jnp.uint32 if word_bits == 32 else jnp.uint64
    prepared = prepare_parts(dev, W)
    caps = caps or prepared["caps"]
    # the index blocks ride as arguments (_as_arguments)
    held, blocks = _as_arguments((prepared, dev.out))

    def or_over(x, axis):
        return lax.reduce(x, dtype(0), lax.bitwise_or, (axis,))

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def step(arrays, frontier, seen, near, open_lanes, limit):
        prepared, out = blocks(arrays)

        def more(carry):
            _f, _s, _buf, ran, _pushed, _slots, open_ = carry
            return (ran == 0) | ((ran < limit) & (open_ != 0).any())

        def hop(carry):
            f, s, buf, ran, pushed, slots, open_ = carry
            nxt, fits, pushed_slots = _pull_or_push(
                prepared, out, f, caps, n, W, dtype, word_bits)
            pushed, slots = pushed + fits, slots + pushed_slots
            if first_visit:
                fresh = nxt & ~s
                s = s | fresh
            else:
                fresh = nxt
            buf = lax.dynamic_update_index_in_dim(buf, fresh, ran, 0)
            # row n is the zero sentinel: OR over all rows == over [:n]
            open_ = open_ & or_over(fresh, 0)
            if first_visit:
                open_ = open_ & ~or_over(fresh & near, 0)
            return fresh, s, buf, ran + 1, pushed, slots, open_

        buf = jnp.zeros((levels,) + frontier.shape, dtype)
        f, s, buf, ran, pushed, slots, open_ = lax.while_loop(
            more, hop,
            (frontier, seen, buf, jnp.int32(0), jnp.int32(0), jnp.int32(0),
             open_lanes))
        return (f, s, tuple(buf[h] for h in range(levels)), ran, open_,
                pushed, slots)

    return functools.partial(step, held)


def make_ell_tree(stages, n: int, W: int, word_bits: int = 32):
    """Compile a level-TREE pipeline over lane-packed masks: the batched
    form of a whole nested query (engine/treebatch.py), one fused XLA
    program for B = word_bits·W concurrent queries.

    Reference parity: query/query.go ProcessGraph descends a SubGraph
    tree level by level, one task per child per goroutine; here every
    level of every lane is one stage of this program, and filters are
    bitmask ANDs instead of per-uid IntersectSorted calls.

    Hop masks live in the STORE's global rank space, shape [n+1, W]
    (row n = sentinel, always zero). Each stage's EllGraph has its own
    degree-class permutation, so a stage translates its parent mask into
    its own permuted space (one row gather), does the ELL hop, and
    translates back (one row gather) — both translations stream
    sequentially and are noise next to the edge gather.

    `stages` is a list of dicts (static structure, device arrays):
      kind      "hop" | "recurse"
      prepared  prepare_parts output for the stage's EllGraph
      perm_in   [n+1] int32 device: permuted row r ← global perm_in[r]
      out_idx   [n+1] int32 device: global row v ← permuted out_idx[v]
      out       recurse only: the relation's out-CSR in the stage's
                permuted space (DeviceEll.out: indptr, indices, and the
                rows' out-degrees [n] int32), for the pushed hops and
                the traversed-edge count
      caps      recurse only: (row cap, slot cap, slots a turn) of a
                pushed hop; None for push_caps of the relation, which
                `prepared` holds (tests pass their own)
      parent    ("seed", slot) | ("stage", idx earlier in the list)
      filt      filter-mask slot index | None  (global space, ANDed in)
      depth     recurse only: hop count (static)
      keep_hops recurse only: also return per-hop first-visit masks

    A hop stage pulls. Each hop of a recurse stage's scan computes its
    next mask one of the two exact ways of make_ell_step's hops, chosen
    on the device from the frontier it is handed (_pull_or_push): hop 1
    of a k-hop expands its seeds' own out-edges, and a later hop pulls
    over every stored in-edge once its frontier is over a cap.

    Returns fn(seeds: tuple, filts: tuple) → tuple with one entry per
    stage: hop → mask [n+1, W]; recurse → (seen, count, edges, pushed,
    slots, hops): `seen` [n+1, W] the reachable set incl. seeds, in the stage's
    PERMUTED space (a consumer that wants a lane's members tests its
    column and maps the rows through perm_order: no translation is run
    for a set nobody reads); `count` int32[lanes] its population count a
    lane; `edges` int32[lanes] the out-degree mass of the rows expanded
    (seen less the last hop's fresh rows), the lane's traversed edges;
    `pushed` int32, how many of the stage's `depth` hops pushed, and
    `slots` int32, the out-edges those hops expanded; `hops`
    [depth, n+1, W] global-space first-visit masks when keep_hops, else
    None. The seed and filter masks are DONATED (consumed by the first
    gather).

    The stages' index blocks and out-CSRs ride as arguments, as
    make_ell_step's do (_as_arguments).
    """
    dtype = jnp.uint32 if word_bits == 32 else jnp.uint64
    held, rebuild = _as_arguments(
        [(s["prepared"], s["perm_in"], s["out_idx"], s.get("out"))
         for s in stages])
    caps = [s.get("caps") or s["prepared"]["caps"]
            if s["kind"] == "recurse" else None for s in stages]
    # a recurse stage's set is translated to global space only for a
    # later stage that expands it
    chained = {s["parent"][1] for s in stages if s["parent"][0] == "stage"}

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def tree(arrays, seeds, filts):
        blocks = rebuild(arrays)
        outs = []
        results = []
        for i, (s, (prepared, perm_in, out_idx, out)) in enumerate(
                zip(stages, blocks)):
            kind, par = s["kind"], s["parent"]
            parent = (seeds[par[1]] if par[0] == "seed"
                      else outs[par[1]])
            filt = filts[s["filt"]] if s["filt"] is not None else None
            pm = parent[perm_in]                 # global → permuted
            if kind == "hop":
                mask = _ell_hop(prepared, pm, W, dtype)[out_idx]
                if filt is not None:
                    mask = mask & filt
                outs.append(mask)
                results.append(mask)
                continue
            # recurse: iterate in permuted space (no per-hop translation)
            filt_p = filt[perm_in] if filt is not None else None
            keep_hops = s["keep_hops"]

            def hop(carry, _, _prep=prepared, _out=out, _caps=caps[i],
                    _filt_p=filt_p, _keep=keep_hops):
                frontier, seen, pushed, slots = carry
                nxt, fits, pushed_slots = _pull_or_push(
                    _prep, _out, frontier, _caps, n, W, dtype, word_bits)
                fresh = nxt & ~seen
                if _filt_p is not None:
                    fresh = fresh & _filt_p
                seen = seen | fresh
                return (fresh, seen, pushed + fits, slots + pushed_slots), (
                    fresh if _keep else None)

            (last, seen_p, pushed, slots), hops_p = lax.scan(
                hop, (pm, pm, jnp.int32(0), jnp.int32(0)), None,
                length=s["depth"])
            outs.append(seen_p[out_idx] if i in chained else None)
            results.append((
                seen_p,
                _lane_sums(seen_p, None, n, W, word_bits),
                _lane_sums(seen_p & ~last, out[2], n, W, word_bits),
                pushed, slots,
                hops_p[:, out_idx] if keep_hops else None))
        return tuple(results)

    return functools.partial(tree, held)


# ---------------------------------------------------------------------------
# The weighted lane family: a VALUE a lane and node, not a bit.
#
# dist[n + 1, lanes] holds, for every lane, the cheapest cost found so far
# from the lane's source to every node (INF elsewhere; row n the sentinel,
# always INF). One round is the min-plus form of _ell_hop:
#     dist'[v] = min(dist[v], min over stored in-slots (u, w) of dist[u] + w)
# over the same lists, with one weight a stored slot beside its index
# (ell_weights). A gathered row is `lanes` distances, 128 bytes of int16
# for 64 lanes where a mask row is 8: the first program whose pace the
# gather's WIDTH may set. Integer arithmetic, so every cost is exact.


def relax_dtype(max_weight: int, rounds: int):
    """(dtype, INF) of the narrowest signed distance type in which
    `rounds` rounds over weights up to `max_weight` cannot overflow: a
    finite distance is at most rounds * max_weight, under INF, and INF +
    max_weight is still inside the type (a round adds before it clamps).
    None where even int32 does not hold it."""
    import numpy as np
    for dt in (np.int16, np.int32):
        inf = int(np.iinfo(dt).max) - max(int(max_weight), 1)
        if rounds * max(int(max_weight), 1) < inf:
            return np.dtype(dt), inf
    return None


def ell_weights(g: EllGraph, indptr, w_in, dtype):
    """The weights of `g`'s stored slots, block for block: (parts, tiles),
    parts[i] an array of the shape of g.parts[i]'s block (None for the
    zero class) and tiles one of g.tiles' shape (None without a tail),
    slot [r, k] the weight of the in-edge whose source that slot of `g`
    names. `indptr` and `w_in` are the relation's IN-edges by target
    (old ranks), sources ascending within a row: build_ell's own order
    (its stable transpose), so the k-th in-edge of a row is its k-th
    slot. Padding slots weigh 0 (their source is the sentinel, at INF).
    For a `g` without a hub block: one takes edges out of the lists."""
    import numpy as np
    assert g.dense is None, "the hub block has no min-plus product"
    indptr = np.asarray(indptr, np.int64)
    off, parts = 0, []
    for kind, e, rows in g.parts:
        nodes = g.perm_order[off:off + rows]
        off += rows
        parts.append(None if kind == "zero" else w_in[
            indptr[nodes][:, None] + np.arange(e.shape[1])].astype(dtype))
    tiles = None
    if g.tiles is not None:
        nodes = g.perm_order[off:]
        deg = indptr[nodes + 1] - indptr[nodes]
        seg_tile = g.tiles.shape[1]
        tile_start = np.cumsum(-(-deg // seg_tile)) - -(-deg // seg_tile)
        within = np.arange(int(deg.sum())) - np.repeat(
            np.cumsum(deg) - deg, deg)
        tiles = np.zeros(g.tiles.shape, dtype)
        tiles.reshape(-1)[np.repeat(tile_start * seg_tile, deg) + within] \
            = w_in[np.repeat(indptr[nodes], deg) + within]
    return parts, tiles


# bytes of gathered distance rows a turn of _min_plus may hold: XLA:TPU
# materialises every gathered row before it combines them (68 M slots of
# 128 bytes would be 8.75 GB a round), so a block is relaxed RELAX_CHUNK
# bytes of rows a turn of a loop; a turn of half a million gathers costs
# the loop nothing
RELAX_CHUNK = 64 << 20


def _min_plus(dist, e, w, inf, extra: int = 0):
    """out[i] = min_k dist[e[i, k]] + w[i, k], the min-plus form of
    _chain_or, as many rows of the block a turn as gather RELAX_CHUNK
    bytes, each turn written into its place of the result. `w` None adds
    nothing (the second level combines partials). `extra` rows of INF
    follow the block's (the sentinel the second level's padding reads).
    Up to CHAIN_MAX wide a turn is an unrolled chain of K row gathers,
    844 ms a round over `knows` on the v5e where one [rows, K] gather and
    a reduce take 926 (PR 46); wider (the widest second-level combines,
    whose rows are few), the reduce. Not clamped: the caller does, once."""
    rows, K = e.shape
    dt = dist.dtype

    def some(e_c, w_c):
        if K <= CHAIN_MAX:
            acc = None
            for k in range(K):
                got = dist[e_c[:, k]]
                if w_c is not None:
                    got = got + w_c[:, k].astype(dt)[:, None]
                acc = got if acc is None else jnp.minimum(acc, got)
            return acc
        got = dist[e_c]
        if w_c is not None:
            got = got + w_c.astype(dt)[:, :, None]
        return got.min(axis=1, initial=inf)

    def rows_of(x, at, size):
        return None if x is None else lax.dynamic_slice_in_dim(x, at, size)

    ch = max(1, RELAX_CHUNK // (K * dist.shape[1] * dt.itemsize))
    whole = rows // ch if rows >= 2 * ch else 0
    out = jnp.full((rows + extra, dist.shape[1]), inf, dt)
    if whole:
        out = lax.fori_loop(
            0, whole,
            lambda i, out: lax.dynamic_update_slice_in_dim(
                out, some(rows_of(e, i * ch, ch), rows_of(w, i * ch, ch)),
                i * ch, 0),
            out)
    at = whole * ch
    if at < rows:
        out = lax.dynamic_update_slice_in_dim(
            out, some(rows_of(e, at, rows - at), rows_of(w, at, rows - at)),
            at, 0)
    return out


def make_ell_relax(dev: DeviceEll, weights, n: int, lanes: int, dtype,
                   inf: int, max_rounds: int):
    """Compile the weighted lane program over a DeviceEll and its
    slot-aligned weights (`weights`: ell_weights' (parts, tiles), on the
    device): fn(src_rows, dst_rows, active) → (dist, rounds, open, cost).

    `src_rows`/`dst_rows` int32[lanes] are the lanes' endpoints in the
    permuted space and `active` bool[lanes] marks the lanes that ride
    (the others stay at INF everywhere). The distances start at 0 on each
    active lane's source, INF elsewhere, and the program runs pulled
    rounds under ONE while_loop: a round reads every stored slot's index
    and weight once and gathers one distance row a slot. A lane stays
    open while a round improved some node to a cost no more than its
    target's: label-correcting, and exact for non-negative weights, since
    once no such node improves every node that cheap, the target among
    them, holds its true cost (engine/shortest.py _weighted_one prunes
    the same way). The program stops when no lane is open, or at
    `max_rounds`: `open` bool[lanes] then names the lanes whose answer is
    not settled, which the caller sends to the host. `rounds` int32 is
    the rounds run, `cost` [lanes] the targets' distances, and `dist`
    [n + 1, lanes] stays on the device for the walk-back's gathers.
    The index blocks and the weights ride as arguments (_as_arguments)."""
    dtype = jnp.dtype(dtype)
    prepared = prepare_parts(dev, 1)
    assert prepared["dense"] is None and all(
        kind != "pallas" for kind, _e, _r in prepared["parts"])
    held, rebuild = _as_arguments((prepared, weights))
    lane_ids = jnp.arange(lanes)

    @jax.jit
    def relax(arrays, src_rows, dst_rows, active):
        prepared, (w_parts, w_tiles) = rebuild(arrays)

        def pull(dist):
            outs = []
            for (kind, e, rows), w in zip(prepared["parts"], w_parts):
                outs.append(jnp.full((rows, lanes), inf, dtype)
                            if kind == "zero"
                            else _min_plus(dist, e, w, inf))
            if prepared["tiles"] is not None:
                # row M: the INF partial that the combines' padding reads
                partials = _min_plus(dist, prepared["tiles"][1], w_tiles,
                                     inf, extra=1)
                for t2 in prepared["lvl2"]:
                    outs.append(_min_plus(partials, t2, None, inf))
            outs.append(jnp.full((1, lanes), inf, dtype))    # sentinel
            return jnp.minimum(jnp.concatenate(outs, axis=0), inf)

        def more(carry):
            _dist, rounds, open_ = carry
            return (rounds < max_rounds) & open_.any()

        def round_(carry):
            dist, rounds, open_ = carry
            new = jnp.minimum(dist, pull(dist))
            cost = new[dst_rows, lane_ids]
            better = (new < dist) & (new <= cost[None, :])
            return new, rounds + 1, better.any(axis=0) & active

        dist0 = jnp.full((n + 1, lanes), inf, dtype).at[
            jnp.where(active, src_rows, n), lane_ids].set(
            jnp.where(active, 0, inf).astype(dtype))
        dist, rounds, open_ = lax.while_loop(
            more, round_, (dist0, jnp.int32(0), active))
        return dist, rounds, open_, dist[dst_rows, lane_ids]

    return functools.partial(relax, held)


@jax.jit
def gather_pairs(dist, rows, lanes):
    """dist[rows[i], lanes[i]]: the distances the weighted walk-back reads
    (engine/batch.py), as many a call as the caller pads to."""
    return dist[rows, lanes]


def ell_recurse(g: EllGraph, mask0, depth: int, count_edges: bool = True):
    """One-shot convenience: device_put the blocks and run. For repeated
    runs hold make_ell_recurse + device arrays instead."""
    import numpy as np
    word_bits = 64 if np.asarray(mask0).dtype == np.uint64 else 32
    if word_bits == 64:
        assert jax.config.jax_enable_x64, \
            "uint64 lane words need x64 (with jax.enable_x64(True): ...)"
    dev = device_ell(g)
    fn = make_ell_recurse(dev, g.outdeg, g.n, mask0.shape[1],
                          count_edges, word_bits)
    return fn(jax.device_put(mask0), depth)
