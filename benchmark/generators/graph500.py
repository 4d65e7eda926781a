"""The Graph500 Kronecker graph, as arrays (numpy only).

The recursion of the Graph500 specification's generator (the reference
`kronecker_generator`): every one of edgefactor * 2^scale edges picks, bit
by bit, a quadrant of the adjacency matrix with probabilities A, B, C and
1 - A - B - C, so that a few vertices gather most edges and no degree is
capped. Edges stay directed as generated; self-loops and duplicate pairs
are dropped; a vertex no edge touches does not exist (a loader of the edge
file never sees it). The draws are made in a fixed number of chunks so
that threads can share the work and the seed still fixes every edge.
Node `i` has uid `i + 1`.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNKS = 16          # fixed: part of what a seed means
SCHEMA = "link: [uid] .\n"


def generate(params: dict, seed: int) -> dict:
    """`params`: scale, edgefactor, a, b, c, structure_seed. Returns
    `src`/`dst` int32 node indices, `n_nodes`, `max_in_degree`, and for
    the reference the edge list grouped by source: node i's targets are
    `dst[row_start[i] : row_start[i] + row_len[i]]`, and
    `node_of_structure[k]`, the node that the structure's k-th vertex
    became (the structure keeps the recursion's own order, in which the
    hubs are the low k).

    `structure_seed` fixes every edge up to the nodes' names, and with
    them every node's in- and out-degree (the shapes of the device's ELL
    blocks, one compiled program each: PERF.md); `seed` draws which node
    is which, by a permutation of the node numbers: the specification's
    shuffle of the vertex labels."""
    src, dst, n = _structure(params, int(params["structure_seed"]))
    perm = np.random.default_rng([seed, 1]).permutation(n).astype(np.int32)
    counts = np.bincount(src, minlength=n)
    starts = np.zeros(n, np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    row_start = np.empty(n, np.int64)
    row_len = np.empty(n, np.int64)
    row_start[perm] = starts
    row_len[perm] = counts
    return {"src": perm[src], "dst": perm[dst], "row_start": row_start,
            "row_len": row_len, "node_of_structure": perm,
            "n_nodes": np.array(n, np.int64),
            "max_in_degree": np.array(
                np.bincount(dst, minlength=n).max(), np.int64)}


def _structure(params: dict, seed: int):
    """(src, dst, n): the distinct edges sorted by (src, dst), over the
    vertices that have an edge, numbered 0..n-1 in the recursion's order."""
    scale = int(params["scale"])
    m = int(params["edgefactor"]) << scale
    a, b, c = (float(params[k]) for k in "abc")
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    key = np.empty(m, np.int64)          # src << scale | dst
    edges = np.linspace(0, m, CHUNKS + 1).astype(np.int64)
    rngs = np.random.default_rng(seed).spawn(CHUNKS)

    def fill(i):
        lo, hi = int(edges[i]), int(edges[i + 1])
        rng, cnt = rngs[i], hi - lo
        # one set of buffers a chunk: a fresh array a bit is most of the
        # time where a page fault is dear
        x = np.empty(cnt, np.float32)
        i_bit, j_bit, t = (np.empty(cnt, bool) for _ in range(3))
        ii, jj, w = (np.zeros(cnt, np.int64) for _ in range(3))
        for bit in range(scale):
            rng.random(out=x, dtype=np.float32)
            np.greater(x, ab, out=i_bit)
            rng.random(out=x, dtype=np.float32)
            # the column's bit: over c_norm in the lower half, a_norm above
            np.greater(x, c_norm, out=j_bit)
            np.greater(x, a_norm, out=t)
            np.copyto(j_bit, t, where=~i_bit)
            ii += np.multiply(i_bit, 1 << bit, out=w)
            jj += np.multiply(j_bit, 1 << bit, out=w)
        np.left_shift(ii, scale, out=ii)
        np.bitwise_or(ii, jj, out=key[lo:hi])

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(fill, range(CHUNKS)))
    key.sort()
    keep = np.empty(m, bool)
    keep[0] = True
    np.not_equal(key[1:], key[:-1], out=keep[1:])       # distinct pairs
    src, dst = key >> scale, key & ((1 << scale) - 1)
    keep &= src != dst                                   # no self-loops
    src, dst = src[keep], dst[keep]
    # vertices with an edge, renumbered in label order
    used = np.zeros(1 << scale, bool)
    used[src] = True
    used[dst] = True
    new = np.cumsum(used, dtype=np.int64) - 1
    return (new[src].astype(np.int32), new[dst].astype(np.int32),
            int(used.sum()))


def sizes(data: dict) -> dict:
    return {"nodes": int(data["n_nodes"]), "link": int(len(data["src"])),
            "max_in_degree": int(data["max_in_degree"])}
