"""A follower graph shaped like Twitter-2010, as arrays (numpy only).

The degree law and target skew of `dgraph_tpu/models/synthetic.py
powerlaw_edges` as it stood at PR 21 (Zipf(2.0) out-degree capped at 64x
the mean and rescaled to the mean; destinations Beta(0.6, 1.8)-skewed
toward low ranks, the hubs), drawn in a fixed number of chunks so that
threads can share the work and the seed still fixes every edge.
Node `i` has uid `i + 1`. Self-loops are dropped; duplicate pairs may
remain (the store's CSR build dedupes them).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNKS = 16          # fixed: part of what a seed means
SCHEMA = "follows: [uid] @reverse .\n"
TARGET_BETA = (0.6, 1.8)


def generate(params: dict, seed: int) -> dict:
    """`params`: nodes, mean_out_degree, zipf_a, structure_seed. Returns
    `src`/`dst` int32 node indices, `n_nodes`, and for the reference the
    edge list grouped by source: node i's targets are
    `dst[row_start[i] : row_start[i] + row_len[i]]`, and
    `node_of_structure[k]`, the node that the structure's k-th node became
    (the hubs are the low k).

    `structure_seed` fixes every edge up to the nodes' names, and with
    them every node's in- and out-degree (the shapes of the device's ELL
    blocks, one compiled program each: PERF.md); `seed` draws which node
    is which, by a permutation of the node numbers."""
    n = int(params["nodes"])
    src, dst = _structure(params, int(params["structure_seed"]))
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
            "n_nodes": np.array(n, np.int64)}


def _structure(params: dict, seed: int):
    n = int(params["nodes"])
    avg = float(params["mean_out_degree"])
    root = np.random.default_rng(seed)
    deg = root.zipf(float(params.get("zipf_a", 2.0)), size=n)
    deg = np.minimum(deg, max(int(avg * 64), 8))
    deg = np.maximum((deg * (avg / max(deg.mean(), 1e-9))).astype(np.int64),
                     0)
    src = np.repeat(np.arange(n, dtype=np.int32), deg)
    m = len(src)
    dst = np.empty(m, np.int32)
    edges = np.linspace(0, m, CHUNKS + 1).astype(np.int64)
    rngs = root.spawn(CHUNKS)

    def fill(i):
        lo, hi = int(edges[i]), int(edges[i + 1])
        x = rngs[i].beta(*TARGET_BETA, size=hi - lo)
        dst[lo:hi] = np.minimum((n * x).astype(np.int64), n - 1)

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(fill, range(CHUNKS)))
    keep = src != dst
    return src[keep], dst[keep]


def sizes(data: dict) -> dict:
    return {"nodes": int(data["n_nodes"]), "edges": int(len(data["src"]))}
