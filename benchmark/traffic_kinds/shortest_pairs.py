"""Traffic kind `shortest_pairs`: unweighted shortest(from, to) over `follows`.

A from uniform over the nodes with an out-edge, B with the generator's own
target skew (the accounts many follow), never equal to A. `batch` queries
ride in one request, a JSON list to `/query/batch`. Every request carries
pairs never sent before.

The pairs are places in the graph's structure, drawn from the mix's
`schedule_seed`, 16 requests' worth at a time; the run's seed deals each
such draw into its requests and names the places (the uids). So every run
asks for the same set of paths, which pairs ride together and in which
order differs from seed to seed, and how far apart a pair is does not.
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
        a, b = self._pairs(rng, count * self.batch)
        deal = np.random.default_rng([self.seed, 3, stream]).permutation(
            count * self.batch)
        a, b = a[deal], b[deal]
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
