"""Plain reference for the k-hop neighbourhood count over the Graph500
graph (numpy only).

Works on the generator's own arrays; imports nothing of the program. The
query asks how many vertices lie within k hops of a seed, along `link`
edges in their direction:

    N as var(func: uid(S)) @recurse(depth: k, loop: false) { link }
    q(func: uid(N)) { count(uid) }

The rule: the count is of the vertices at distance 0 to k from the seed,
the seed itself among them (the engine's block var holds the block's root
beside everything the recursion reached; the source's GSQL is not at hand,
and whether it counts the seed is this configuration's assumption). One
answer is one number, compared exactly.

A breadth-first search to depth k over the CSR the generator hands over
(`row_start`, `row_len`, `dst`). A hop's edges are listed one of two plain
ways: row by row for a small frontier, by a mark on every edge of the list
for a large one (a 3-hop on a Kronecker graph expands up to two thirds of
all edges in its last hop).
"""

from __future__ import annotations

import json

import numpy as np

DENSE_SHARE = 16     # a frontier with over 1/16 of the edges marks them all


class Reference:
    def __init__(self, data: dict, row_cap: int = 0):
        """Adjacency by node index (uid - 1): node i's targets are
        `dst[row_start[i] : row_start[i] + row_len[i]]`. `row_cap` (the
        control) cuts every row at that many edges, as a fixed-width
        device row that drops its overflow would."""
        self.n = int(data["n_nodes"])
        self.dst = np.asarray(data["dst"])
        self.row_start = np.asarray(data["row_start"])
        self.full_len = self.row_len = np.asarray(data["row_len"])
        if row_cap:
            self.row_len = np.minimum(self.row_len, row_cap)
        self._edge_row = None

    def _targets(self, frontier: np.ndarray) -> np.ndarray:
        """Every target of every edge out of `frontier` (node indices),
        with repeats."""
        deg = self.row_len[frontier]
        total = int(deg.sum())
        if total * DENSE_SHARE > len(self.dst):
            return self.dst[self._mark_edges(frontier)]
        if not total:
            return np.zeros(0, np.int64)
        offs = np.cumsum(deg) - deg
        pos = (np.repeat(self.row_start[frontier] - offs, deg)
               + np.arange(total, dtype=np.int64))
        return self.dst[pos]

    def _mark_edges(self, frontier: np.ndarray) -> np.ndarray:
        """bool[edges]: the edge's source is in the frontier and the edge
        is inside its row's cap."""
        if self._edge_row is None:
            # position in the edge list -> its source node (built once:
            # rows tile the edge list), and whether the position lies
            # inside its row's cap
            rows = np.nonzero(self.full_len)[0]
            rows = rows[np.argsort(self.row_start[rows])]
            full = self.full_len[rows]
            self._edge_row = np.repeat(rows.astype(np.int32), full)
            self._in_cap = None
            if self.row_len is not self.full_len:
                place = np.arange(len(self.dst), dtype=np.int64) \
                    - np.repeat(self.row_start[rows], full)
                self._in_cap = place < self.row_len[self._edge_row]
        on = np.zeros(self.n, bool)
        on[frontier] = True
        marks = on[self._edge_row]
        return marks if self._in_cap is None else marks & self._in_cap

    def within(self, seed: int, k: int) -> int:
        """How many nodes lie at distance 0..k from node index `seed`."""
        seen = np.zeros(self.n, bool)
        seen[seed] = True
        frontier = np.array([seed], np.int64)
        for _ in range(k):
            fresh = np.zeros(self.n, bool)
            fresh[self._targets(frontier)] = True
            fresh &= ~seen
            frontier = np.nonzero(fresh)[0]
            if not len(frontier):
                break
            seen |= fresh
        return int(seen.sum())

    def answer(self, meta: dict) -> dict:
        """The right answer, shaped as the program shapes it."""
        return {"q": [{"count": self.within(meta["seed"] - 1,
                                            meta["depth"])}]}

    def check(self, meta: dict, got: dict) -> tuple[bool, str]:
        want = self.answer(meta)
        if got != want:
            return False, f"{json_of(got)} where {json_of(want)} is right"
        return True, ""


def json_of(obj) -> str:
    return json.dumps(obj, separators=(",", ":"))[:120]


def make(data: dict, params: dict) -> Reference:
    return Reference(data)


def make_control(data: dict, params: dict) -> Reference:
    """An approximate route where the configuration states an exact one:
    every adjacency row cut at 8 edges."""
    return Reference(data, row_cap=8)
