"""Traffic kind `shortest_pairs`: unweighted shortest(from, to) over `follows`.

A from uniform over the nodes with an out-edge, B with the generator's own
target skew (the accounts many follow), never equal to A. `batch` queries
ride in one request, a JSON list to `/query/batch`. Every request carries
pairs never sent before.

The pairs are places in the graph's structure, drawn from the mix's
`schedule_seed` and the call's `stream`, as many as the call's requests
hold; the run's seed deals them into requests and names the places (the
uids). With `draw_requests` in the traffic the deal stays within each
draw of that many requests and never crosses two: with a draw of one
request every run sends the same requests in the same order, and the seed
picks which lane a pair rides and what everyone is called. Without the
key the deal is over all the pairs of the call (`run.py` calls for
`CHUNK` requests at a time): every run asks for the same set of paths,
grouped another way a seed. How far apart a pair is does not depend on
the seed either way; which pairs ride together sets what a launch costs
(its farthest pair its hops, the union of its frontiers whether a hop is
pushed), which is why the cell fixes the groups (`khop_seeds` and
`recurse_roots` have the reasons for a draw of one request).
"""

from __future__ import annotations

import json

import numpy as np

from generators import follower as gen

QUERY = ("{ path as shortest(from: %s, to: %s) { follows } "
         "p(func: uid(path)) { uid } }")


class Mix:
    def __init__(self, data: dict, params: dict, seed: int):
        self.n = int(data["n_nodes"])
        self.batch = int(params["batch"])
        # pairs a deal may cross: a draw's, or (no key) all of a call's
        self.draw = int(params.get("draw_requests", 0)) * self.batch
        self.params, self.seed = params, seed
        self.node_of = np.asarray(data["node_of_structure"], np.int64)
        # places of the structure that follow someone
        self.sources = np.nonzero(
            np.asarray(data["row_len"])[self.node_of] > 0)[0]

    def _pairs(self, rng, count: int):
        a = self.sources[rng.integers(0, len(self.sources), count)]
        b = np.minimum((self.n * rng.beta(*gen.TARGET_BETA, size=count)
                        ).astype(np.int64), self.n - 1)
        b = np.where(b == a, (b + 1) % self.n, b)
        return self.node_of[a] + 1, self.node_of[b] + 1          # uids

    def requests(self, count: int, stream: int = 0) -> list:
        rng = np.random.default_rng(
            [int(self.params["schedule_seed"]), stream])
        total = count * self.batch
        a, b = self._pairs(rng, total)
        deal = np.random.default_rng([self.seed, 3, stream])
        cuts = list(range(0, total, self.draw or max(total, 1))) + [total]
        for lo, hi in zip(cuts, cuts[1:]):
            lanes = lo + deal.permutation(hi - lo)
            a[lo:hi], b[lo:hi] = a[lanes], b[lanes]
        out = []
        for i in range(count):
            sl = slice(i * self.batch, (i + 1) * self.batch)
            metas = [{"a": int(x), "b": int(y)}
                     for x, y in zip(a[sl], b[sl])]
            queries = [QUERY % (hex(m["a"]), hex(m["b"])) for m in metas]
            out.append({"path": "/query/batch",
                        "ctype": "application/json",
                        "body": json.dumps({"queries": queries}).encode(),
                        "queries": self.batch, "meta": metas})
        return out

    def warm_requests(self, window_count: int = 0) -> list:
        return self.requests(int(self.params["warm_requests"]), stream=1)

    def split(self, request: dict, data) -> list:
        """(meta, answer) pairs of one finished request."""
        return list(zip(request["meta"], data))


def make(data: dict, params: dict, seed: int) -> Mix:
    return Mix(data, params, seed)
