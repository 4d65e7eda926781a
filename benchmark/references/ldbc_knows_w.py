"""Plain reference for the cheapest path between two persons over the
weighted `ldbc_knows_w` graph (numpy and heapq only).

Works on the generator's own arrays; imports nothing of the program. LDBC
SNB Interactive v2's IC14 as this repository's DQL asks it:

    path as shortest(from: A, to: B) { knows @facets(weight) }
    p(func: uid(path)) { uid }

A cheapest path is not unique, so an answer is right when its `_path_` is
made of stored `knows` edges from A to B, the weights of those edges add
up to the `_weight_` it states, no cheaper path exists, and `p` lists the
path's persons in ascending uid order. No path at all is right only where
none exists.

That no path under cost C exists is shown without a whole search (a
Dijkstra over 68 M edges a pair): the cheapest costs from A out to
(C - 1) // 2 and from B out to the rest of C - 1, each by a bucketed
Dijkstra (the weights are small integers: a bucket a cost), and the
cheapest meeting of the two balls, over the nodes in both and over the
edges from a node of the first to a node of the second, is no less than
C. (Every friendship is stored both ways at one weight, so the costs TO B
are the costs FROM it.) At the tests' small size a whole `heapq` Dijkstra
is the comparison.
"""

from __future__ import annotations

import heapq

import numpy as np

from references.common import walk

FAR = np.iinfo(np.int32).max


class Reference:
    def __init__(self, data: dict, row_cap: int = 0):
        """Adjacency by node index (uid - 1): node i's friends are
        `dst[row_start[i] : row_start[i] + row_len[i]]`, and `weight` lies
        beside `dst`. `row_cap` (the control) cuts every row at that many
        edges, as a fixed-width device row that drops its overflow
        would."""
        self.n = int(data["n_nodes"])
        self.dst = np.asarray(data["dst"])
        self.weight = np.asarray(data["weight"])
        self.row_start = np.asarray(data["row_start"])
        self.row_len = np.asarray(data["row_len"])
        if row_cap:
            self.row_len = np.minimum(self.row_len, row_cap)

    def row(self, i: int):
        """(friends, weights) of node i."""
        s = int(self.row_start[i])
        e = s + int(self.row_len[i])
        return self.dst[s:e], self.weight[s:e]

    def _expand(self, nodes: np.ndarray):
        """(friends, weights, their sources) of some nodes, edge by edge."""
        starts = self.row_start[nodes]
        deg = self.row_len[nodes]
        total = int(deg.sum())
        if not total:
            z = np.zeros(0, np.int64)
            return z, z, z
        offs = np.cumsum(deg) - deg
        pos = np.repeat(starts - offs, deg) + np.arange(total, dtype=np.int64)
        return (self.dst[pos].astype(np.int64),
                self.weight[pos].astype(np.int64), np.repeat(nodes, deg))

    def ball(self, a: int, radius: int) -> dict:
        """{node: cheapest cost from a} for every node within `radius`:
        Dijkstra by buckets, a bucket a cost, a bucket's nodes expanded
        together."""
        cost = {a: 0}
        buckets = {0: [np.array([a], np.int64)]}
        for c in range(radius + 1):
            if c not in buckets:
                continue
            nodes = np.unique(np.concatenate(buckets.pop(c)))
            nodes = nodes[[cost[int(v)] == c for v in nodes]]
            nbrs, ws, _src = self._expand(nodes)
            for v, nd in zip(nbrs.tolist(), (ws + c).tolist()):
                if nd <= radius and nd < cost.get(v, FAR):
                    cost[v] = nd
                    buckets.setdefault(nd, []).append(
                        np.array([v], np.int64))
        return cost

    def cheaper_exists(self, a: int, b: int, cost: int) -> bool:
        """Is there a path from a to b of cost under `cost`?"""
        if cost <= 0:
            return False
        near = (cost - 1) // 2
        fwd, back = self.ball(a, near), self.ball(b, cost - 1 - near)
        if any(c + back[v] < cost for v, c in fwd.items() if v in back):
            return True
        nodes = np.fromiter(fwd, np.int64)
        nbrs, ws, srcs = self._expand(nodes)
        return any(v in back and fwd[u] + w + back[v] < cost
                   for v, w, u in zip(nbrs.tolist(), ws.tolist(),
                                      srcs.tolist()))

    def reachable(self, a: int, b: int) -> bool:
        seen = np.zeros(self.n, bool)
        seen[a] = True
        frontier = np.array([a], np.int64)
        while len(frontier) and not seen[b]:
            nbrs, _ws, _src = self._expand(frontier)
            frontier = np.unique(nbrs[~seen[nbrs]])
            seen[frontier] = True
        return bool(seen[b])

    def search(self, a: int, b: int):
        """A whole Dijkstra from a (heapq): (cost, one cheapest path as
        node indices), or (None, None) where b cannot be reached."""
        cost, parent, heap = {a: 0}, {a: a}, [(0, a)]
        while heap:
            c, u = heapq.heappop(heap)
            if c > cost[u]:
                continue
            if u == b:
                path = [b]
                while path[-1] != a:
                    path.append(parent[path[-1]])
                return c, path[::-1]
            nbrs, ws = self.row(u)
            for v, w in zip(nbrs.tolist(), ws.tolist()):
                if c + w < cost.get(v, FAR):
                    cost[v], parent[v] = c + w, u
                    heapq.heappush(heap, (c + w, v))
        return None, None

    def answer(self, meta: dict) -> dict:
        """One right answer, shaped as the program shapes it."""
        cost, path = self.search(meta["a"] - 1, meta["b"] - 1)
        if path is None:
            return {}
        uids = [i + 1 for i in path]
        obj = {"uid": hex(uids[-1])}
        for u in reversed(uids[:-1]):
            obj = {"uid": hex(u), "knows": obj}
        obj["_weight_"] = float(cost)
        return {"_path_": [obj],
                "p": [{"uid": hex(u)} for u in sorted(set(uids))]}

    def check(self, meta: dict, got: dict) -> tuple[bool, str]:
        a, b = meta["a"], meta["b"]
        paths = got.get("_path_", [])
        if not paths:
            if self.reachable(a - 1, b - 1):
                return False, "no path returned, one exists"
            return (not got.get("p")), "p lists nodes of no path"
        if len(paths) != 1:
            return False, f"{len(paths)} paths returned"
        hops = walk(paths[0], "knows")
        if hops[0] != a or hops[-1] != b:
            return False, "path does not join source and target"
        cost = 0
        for u, v in zip(hops, hops[1:]):
            nbrs, ws = self.row(u - 1)
            at = np.flatnonzero(nbrs == v - 1)
            if not len(at):
                return False, f"{u:#x}->{v:#x} is not an edge"
            cost += int(ws[at[0]])
        if paths[0].get("_weight_") != cost:
            return False, (f"_weight_ {paths[0].get('_weight_')} where "
                           f"the path's weights add up to {cost}")
        if self.cheaper_exists(a - 1, b - 1, cost):
            return False, f"cost {cost} where a cheaper path exists"
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
