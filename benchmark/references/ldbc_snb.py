"""Plain reference for the LDBC SNB complex reads IC1-IC14 (numpy + scipy).

Computed straight from the generator's arrays; imports nothing of the
program and reads nothing the program made. The semantics are those the
query language states (the oracle of `tests/test_ldbc_ic.py` at PR 21,
moved from dicts to CSR so that it holds SF1 size):
  - an edge list renders in ascending uid order, deduped
  - orderasc/orderdesc: by value, uid breaks ties; `first: N` cuts after
    ordering, per parent
  - an object with nothing in it is dropped from its list, and an empty
    list drops its key
IC1-IC12 have one right answer and are compared whole. IC13/IC14 ask for
a shortest path, of which there can be several: the path is checked to be
made of real edges from source to target at the optimal cost, and what
hangs off it (`p`, `_weight_`) to follow from the path returned. IC14's
second path is held to be a valid path no cheaper than the first; its
optimality is not checked.
"""

from __future__ import annotations

import numpy as np

from references.common import walk

from generators import ldbc_snb as gen

WEIGHT_TOL = 1e-6


class Csr:
    """Adjacency over uid space: row(u) = sorted unique neighbours."""

    def __init__(self, pairs: np.ndarray, n: int, rev: bool = False,
                 weights: np.ndarray | None = None, cap: int = 0):
        self.cap = cap
        s, d = (pairs[:, 1], pairs[:, 0]) if rev else (pairs[:, 0],
                                                       pairs[:, 1])
        key = s.astype(np.int64) * (n + 1) + d.astype(np.int64)
        if weights is None:
            key = np.unique(key)
        else:
            key, first = np.unique(key, return_index=True)
            self.weights = np.asarray(weights, np.float64)[first]
        self.indices = key % (n + 1)
        counts = np.bincount(key // (n + 1), minlength=n + 2)
        self.indptr = np.zeros(n + 3, np.int64)
        np.cumsum(counts, out=self.indptr[1:])

    def row(self, u: int) -> np.ndarray:
        row = self.indices[self.indptr[u]:self.indptr[u + 1]]
        return row[:self.cap] if self.cap else row

    def has(self, u: int, v: int) -> bool:
        row = self.row(u)
        j = int(np.searchsorted(row, v))
        return j < len(row) and int(row[j]) == v

    def weight(self, u: int, v: int) -> float:
        row = self.row(u)
        j = int(np.searchsorted(row, v))
        return float(self.weights[self.indptr[u] + j])


class Reference:
    def __init__(self, data: dict, key_dtype=np.int64, row_cap: int = 0):
        """The control departs from exactness in two ways a faster
        program might: `key_dtype` float32 compares order-by keys as a
        device order-by would round unix seconds, and `row_cap` cuts
        every edge list at that many entries, as a fixed-size device
        buffer that does not regrow would."""
        self.d = data
        self.row_cap = row_cap
        self.n = gen.sizes(data)["nodes"]
        self.key_dtype = key_dtype
        self._adj: dict = {}
        self._msg0 = int(data["post_uids"][0])
        self._tag0 = int(data["tag_uids"][0])
        self._forum0 = int(data["forum_uids"][0])
        self._org0 = int(data["org_uids"][0])
        self._sp = None

    # -- data access --------------------------------------------------------
    def adj(self, pred: str, rev: bool = False) -> Csr:
        k = (pred, rev)
        if k not in self._adj:
            w = self.d["knows_weight"] if k == ("knows", False) else None
            self._adj[k] = Csr(self.d[pred], self.n, rev, w, self.row_cap)
        return self._adj[k]

    def first(self, u): return gen.FIRST_NAMES[self.d["first_name"][u - 1]]
    def last(self, u): return gen.LAST_NAMES[self.d["last_name"][u - 1]]
    def city(self, u): return gen.CITIES[self.d["city"][u - 1]]
    def bday(self, u): return int(self.d["birthday_year"][u - 1])
    def ts(self, m): return int(self.d["creation_ts"][m - self._msg0])
    def tag(self, t): return f"tag_{t - self._tag0}"
    def forum(self, f): return f"forum_{f - self._forum0}"
    def org(self, o): return f"org_{o - self._org0}"

    def order(self, uids, key, desc: bool = False, first: int = 0) -> list:
        uids = [int(u) for u in uids]
        keys = [key(u) for u in uids]
        if keys and not isinstance(keys[0], str):
            keys = np.asarray(keys).astype(self.key_dtype).tolist()
        if desc:
            # value descending, uid ascending among equals
            idx = sorted(range(len(uids)), key=lambda i: uids[i])
            idx.sort(key=lambda i: keys[i], reverse=True)
        else:
            idx = sorted(range(len(uids)), key=lambda i: (keys[i], uids[i]))
        out = [uids[i] for i in idx]
        return out[:first] if first else out

    def ball(self, start: int, depth: int) -> list[int]:
        knows = self.adj("knows")
        seen = {start}
        frontier = [start]
        for _ in range(depth):
            nxt = []
            for u in frontier:
                for v in knows.row(u).tolist():
                    if v not in seen:
                        seen.add(v)
                        nxt.append(v)
            frontier = nxt
        return sorted(seen)

    def bfs_dist(self, src: int, dst: int):
        knows = self.adj("knows")
        seen = {src}
        frontier = [src]
        d = 0
        while frontier:
            if dst in seen:
                return d
            nxt = []
            for u in frontier:
                for v in knows.row(u).tolist():
                    if v not in seen:
                        seen.add(v)
                        nxt.append(v)
            frontier = nxt
            d += 1
        return d if dst in seen else None

    def min_weight(self, src: int, dst: int):
        from scipy.sparse import csr_matrix
        from scipy.sparse.csgraph import dijkstra
        if self._sp is None:
            k = self.adj("knows")
            self._sp = csr_matrix((k.weights, k.indices, k.indptr[:-1]),
                                  shape=(self.n + 1, self.n + 1))
        dist = dijkstra(self._sp, directed=True, indices=src)[dst]
        return None if np.isinf(dist) else float(dist)

    # -- IC1-IC12: one right answer each -------------------------------------
    def _friends_block(self, p, per_friend):
        friends = [o for o in (per_friend(f)
                               for f in self.adj("knows").row(p).tolist())
                   if o]
        return {"q": [{"knows": friends}] if friends else []}

    def _tags_obj(self, m):
        tags = self.adj("has_tag").row(m).tolist()
        return ({"has_tag": [{"tag_name": self.tag(t)} for t in tags]}
                if tags else None)

    def ic1(self, pr):
        hits = [u for u in self.ball(pr["p"], 3)
                if self.first(u) == pr["fn"]]
        return {"q": [{"first_name": self.first(u),
                       "last_name": self.last(u), "city": self.city(u)}
                      for u in self.order(hits, self.last, first=20)]}

    def ic2(self, pr):
        def per(f):
            msgs = self.order(self.adj("has_creator", True).row(f), self.ts,
                              desc=True, first=20)
            return ({"~has_creator": [{"creation_ts": self.ts(m)}
                                      for m in msgs]} if msgs else None)
        return self._friends_block(pr["p"], per)

    def ic3(self, pr):
        cities = {pr["city"], pr["city2"]}

        def per(f):
            fof = [u for u in self.adj("knows").row(f).tolist()
                   if self.city(u) in cities]
            return ({"knows": [{"first_name": self.first(u),
                                "last_name": self.last(u),
                                "city": self.city(u)} for u in fof]}
                    if fof else None)
        return self._friends_block(pr["p"], per)

    def ic4(self, pr):
        def per(f):
            msgs = [m for m in self.adj("has_creator", True).row(f).tolist()
                    if self.ts(m) >= pr["ts"]][:20]
            objs = [o for o in map(self._tags_obj, msgs) if o]
            return {"~has_creator": objs} if objs else None
        return self._friends_block(pr["p"], per)

    def ic5(self, pr):
        def per(f):
            forums = self.order(self.adj("has_member", True).row(f),
                                self.forum, first=20)
            return ({"~has_member": [{"forum_title": self.forum(u)}
                                     for u in forums]} if forums else None)
        return self._friends_block(pr["p"], per)

    def ic6(self, pr):
        tag = self._tag0 + int(pr["tag"].split("_")[1])
        msgs = self.adj("has_tag", True).row(tag).tolist()[:50]
        objs = [o for o in map(self._tags_obj, msgs) if o]
        return {"t": [{"~has_tag": objs}] if objs else []}

    def ic7(self, pr):
        msgs = []
        for m in self.adj("has_creator", True).row(pr["p"]).tolist():
            likers = self.adj("likes", True).row(m).tolist()[:20]
            if likers:
                msgs.append({"~likes": [{"first_name": self.first(u)}
                                        for u in likers]})
        return {"q": [{"~has_creator": msgs}] if msgs else []}

    def ic8(self, pr):
        msgs = []
        for m in self.adj("has_creator", True).row(pr["p"]).tolist():
            replies = self.order(self.adj("reply_of", True).row(m), self.ts,
                                 desc=True, first=20)
            objs = []
            for c in replies:
                obj = {"creation_ts": self.ts(c)}
                authors = self.adj("has_creator").row(c).tolist()
                if authors:
                    obj["has_creator"] = [{"first_name": self.first(u)}
                                          for u in authors]
                objs.append(obj)
            if objs:
                msgs.append({"~reply_of": objs})
        return {"q": [{"~has_creator": msgs}] if msgs else []}

    def ic9(self, pr):
        knows = self.adj("knows")
        fof = sorted({u for f in knows.row(pr["p"]).tolist()
                      for u in knows.row(f).tolist()})
        out = []
        for u in fof:
            msgs = [m for m in self.adj("has_creator", True).row(u).tolist()
                    if self.ts(m) <= pr["ts"]][:20]
            if msgs:
                out.append({"~has_creator": [{"creation_ts": self.ts(m)}
                                             for m in msgs]})
        return {"q": out}

    def ic10(self, pr):
        def per(f):
            fof = [u for u in self.adj("knows").row(f).tolist()
                   if self.bday(u) >= pr["year"]][:10]
            return ({"knows": [{"first_name": self.first(u),
                                "city": self.city(u)} for u in fof]}
                    if fof else None)
        return self._friends_block(pr["p"], per)

    def ic11(self, pr):
        def per(f):
            orgs = [u for u in self.adj("works_at").row(f).tolist()
                    if self.org(u) == pr["org"]]
            return ({"works_at": [{"org_name": self.org(u)} for u in orgs]}
                    if orgs else None)
        return self._friends_block(pr["p"], per)

    def ic12(self, pr):
        parent_of = self.adj("reply_of")

        def per(f):
            comments = [m for m in
                        self.adj("has_creator", True).row(f).tolist()
                        if len(parent_of.row(m))][:20]
            objs = []
            for c in comments:
                parents = [o for o in map(self._tags_obj,
                                          parent_of.row(c).tolist()) if o]
                if parents:
                    objs.append({"reply_of": parents})
            return {"~has_creator": objs} if objs else None
        return self._friends_block(pr["p"], per)

    EXACT = {"IC1": ic1, "IC2": ic2, "IC3": ic3, "IC4": ic4, "IC5": ic5,
             "IC6": ic6, "IC7": ic7, "IC8": ic8, "IC9": ic9, "IC10": ic10,
             "IC11": ic11, "IC12": ic12}

    # -- IC13/IC14: any optimal path ------------------------------------------
    def _check_paths(self, pr, got, weighted: bool):
        paths = got.get("_path_", [])
        src, dst = pr["p"], pr["p2"]
        best = (self.min_weight(src, dst) if weighted
                else self.bfs_dist(src, dst))
        if best is None:
            return (not paths), "no path exists, one was returned"
        if not 1 <= len(paths) <= (2 if weighted else 1):
            return False, f"{len(paths)} paths returned"
        knows = self.adj("knows")
        costs = []
        for pth in paths:
            hops = walk(pth, "knows")
            if hops[0] != src or hops[-1] != dst:
                return False, "path does not join source and target"
            cost = 0.0
            for u, v in zip(hops, hops[1:]):
                if not knows.has(u, v):
                    return False, f"{u:#x}->{v:#x} is not an edge"
                cost += knows.weight(u, v) if weighted else 1.0
            if weighted and abs(cost - pth.get("_weight_", -1.0)) > WEIGHT_TOL:
                return False, "_weight_ is not the sum of the edge weights"
            costs.append(cost)
        if abs(costs[0] - best) > WEIGHT_TOL:
            return False, f"cost {costs[0]} is not the optimum {best}"
        if costs != sorted(costs):
            return False, "paths are not in order of cost"
        if not weighted:
            hops = sorted(set(walk(paths[0], "knows")))
            want = [{"first_name": self.first(u)} for u in hops]
            if got.get("p", []) != want:
                return False, "p does not list the path's nodes"
        return True, ""

    # -- the comparison ---------------------------------------------------------
    def answer(self, meta: dict):
        """The one right answer of an exact template (None for IC13/14)."""
        fn = self.EXACT.get(meta["template"])
        return None if fn is None else fn(self, meta["params"])

    def check(self, meta: dict, got: dict) -> tuple[bool, str]:
        """Is `got` (the response's `data`) a right answer to the request?"""
        want = self.answer(meta)
        if want is not None:
            return (got == want), "differs from the reference"
        return self._check_paths(meta["params"], got,
                                 weighted=meta["template"] == "IC14")


def make(data: dict, params: dict) -> Reference:
    return Reference(data)


def make_control(data: dict, params: dict) -> Reference:
    """An approximate answer where the configuration states an exact
    one: float32 order-by keys and edge lists cut at 64."""
    return Reference(data, key_dtype=np.float32, row_cap=64)
