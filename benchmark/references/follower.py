"""Plain reference for `shortest(from, to)` over the follower graph (numpy).

Works on the generator's own arrays; imports nothing of the program. A
shortest path is not unique, so an answer is right when its `_path_` is
made of real `follows` edges, runs from the source to the target, and no
shorter path exists; `p` must list the path's nodes in ascending uid
order. No path at all is right only where none exists.

That no path under L hops exists is shown without a whole search (2 s a
pair at this size): the nodes within (L-1)//2 hops of the source, forward,
and those within the remaining hops of the target, backward, do not meet.
"""

from __future__ import annotations

import numpy as np

from references.common import walk


class Reference:
    def __init__(self, data: dict, row_cap: int = 0):
        """Adjacency by node index (uid - 1): the generator hands the edge
        list grouped by source (`row_start`, `row_len`); rows keep its
        order and its duplicates. `row_cap` (the control) cuts every row
        at that many edges, as a fixed-width device row that drops its
        overflow would."""
        self.n = int(data["n_nodes"])
        self.dst = np.asarray(data["dst"])
        self.row_start = np.asarray(data["row_start"])
        self.row_len = np.asarray(data["row_len"])
        if row_cap:
            self.row_len = np.minimum(self.row_len, row_cap)
        self._rev = None

    def _reversed(self) -> "Reference":
        """The same graph with every edge turned round (built once, on
        first use: one stable sort of the edge list by target)."""
        if self._rev is None:
            dst = self.dst
            src = np.empty(len(dst), np.int32)
            ends = self.row_start + np.asarray(self.row_len)
            for_rows = np.argsort(self.row_start, kind="stable")
            # edge position -> its source: rows tile the edge list
            src[:] = np.repeat(for_rows.astype(np.int32),
                               (ends - self.row_start)[for_rows])
            order = np.argsort(dst, kind="stable")
            counts = np.bincount(dst, minlength=self.n)
            starts = np.zeros(self.n, np.int64)
            np.cumsum(counts[:-1], out=starts[1:])
            rev = Reference.__new__(Reference)
            rev.n, rev.dst = self.n, src[order]
            rev.row_start, rev.row_len, rev._rev = starts, counts, self
            self._rev = rev
        return self._rev

    def ball(self, a: int, radius: int) -> np.ndarray:
        """Marks of the nodes within `radius` hops of a."""
        seen = np.zeros(self.n, bool)
        seen[a] = True
        frontier = np.array([a], np.int64)
        for _ in range(radius):
            nbrs, _srcs = self._expand(frontier)
            nbrs = np.unique(nbrs[~seen[nbrs]])
            if not len(nbrs):
                break
            seen[nbrs] = True
            frontier = nbrs
        return seen

    def shorter_exists(self, a: int, b: int, hops: int) -> bool:
        """Is there a path from a to b of fewer than `hops` edges?"""
        if hops <= 0:
            return False
        fwd = (hops - 1) // 2
        back = (hops - 1) - fwd
        return bool(np.any(self.ball(a, fwd)
                           & self._reversed().ball(b, back)))

    def row(self, i: int) -> np.ndarray:
        s = int(self.row_start[i])
        return self.dst[s:s + int(self.row_len[i])]

    def _expand(self, frontier: np.ndarray):
        """(neighbours, their sources) of a frontier of node indices."""
        starts = self.row_start[frontier]
        deg = self.row_len[frontier]
        total = int(deg.sum())
        if not total:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        offs = np.cumsum(deg) - deg
        pos = (np.repeat(starts - offs, deg)
               + np.arange(total, dtype=np.int64))
        return self.dst[pos].astype(np.int64), np.repeat(frontier, deg)

    def search(self, a: int, b: int, max_depth: int = 64):
        """Breadth-first from a to b (node indices): the path as a list
        of node indices, or None when b cannot be reached."""
        if a == b:
            return [a]
        parent = np.full(self.n, -1, np.int64)
        parent[a] = a
        frontier = np.array([a], np.int64)
        for _ in range(max_depth):
            nbrs, srcs = self._expand(frontier)
            new = parent[nbrs] < 0
            nbrs, srcs = nbrs[new], srcs[new]
            if not len(nbrs):
                return None
            parent[nbrs] = srcs
            if parent[b] >= 0:
                path = [b]
                while path[-1] != a:
                    path.append(int(parent[path[-1]]))
                return path[::-1]
            frontier = np.unique(nbrs)
        return None

    def answer(self, meta: dict) -> dict:
        """One right answer, shaped as the program shapes it."""
        path = self.search(meta["a"] - 1, meta["b"] - 1)
        if path is None:
            return {}
        uids = [i + 1 for i in path]
        obj = {"uid": hex(uids[-1])}
        for u in reversed(uids[:-1]):
            obj = {"uid": hex(u), "follows": obj}
        return {"_path_": [obj],
                "p": [{"uid": hex(u)} for u in sorted(set(uids))]}

    def check(self, meta: dict, got: dict) -> tuple[bool, str]:
        a, b = meta["a"], meta["b"]
        paths = got.get("_path_", [])
        if not paths:
            if self.search(a - 1, b - 1) is not None:
                return False, "no path returned, one exists"
            return (not got.get("p")), "p lists nodes of no path"
        if len(paths) != 1:
            return False, f"{len(paths)} paths returned"
        hops = walk(paths[0], "follows")
        if hops[0] != a or hops[-1] != b:
            return False, "path does not join source and target"
        for u, v in zip(hops, hops[1:]):
            if not np.any(self.row(u - 1) == v - 1):
                return False, f"{u:#x}->{v:#x} is not an edge"
        if self.shorter_exists(a - 1, b - 1, len(hops) - 1):
            return False, f"{len(hops) - 1} hops where fewer are enough"
        want = [{"uid": hex(u)} for u in sorted(set(hops))]
        if got.get("p", []) != want:
            return False, "p does not list the path's nodes"
        return True, ""


def make(data: dict, params: dict) -> Reference:
    return Reference(data)


def make_control(data: dict, params: dict) -> Reference:
    """An approximate route where the configuration states an exact one:
    every adjacency row cut at 8 edges."""
    return Reference(data, row_cap=8)
