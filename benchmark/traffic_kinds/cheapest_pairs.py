"""Traffic kind `cheapest_pairs`: the cheapest path between two persons
over `knows`, weighted by the facet `weight` (LDBC SNB Interactive v2's
IC14 as DQL says it).

A and B are two distinct persons, each uniform over the persons that have
a friend. `batch` queries ride in one request, a JSON list to
`/query/batch`. Every request carries pairs never sent before.

The pairs are places in the graph's structure, drawn from the mix's
`schedule_seed` and the call's `stream`, as many as the call's requests
hold; the run's seed deals them into requests and names the places (the
uids). With `draw_requests` in the traffic the deal stays within each
draw of that many requests and never crosses two: with a draw of one
request every run sends the same requests in the same order, and the seed
picks which lane a pair rides and what everyone is called
(`shortest_pairs` has the reasons: a launch runs as many rounds as its
hardest pair needs, so which pairs ride together sets what it costs).
"""

from __future__ import annotations

import json

import numpy as np

QUERY = ("{ path as shortest(from: %s, to: %s) { knows @facets(weight) } "
         "p(func: uid(path)) { uid } }")


class Mix:
    def __init__(self, data: dict, params: dict, seed: int):
        self.batch = int(params["batch"])
        # pairs a deal may cross: a draw's, or (no key) all of a call's
        self.draw = int(params.get("draw_requests", 0)) * self.batch
        self.params, self.seed = params, seed
        self.node_of = np.asarray(data["node_of_structure"], np.int64)
        # places of the structure that have a friend
        self.persons = np.nonzero(
            np.asarray(data["row_len"])[self.node_of] > 0)[0]
        if len(self.persons) < 2:
            raise SystemExit("cheapest_pairs: fewer than two persons "
                             "have a friend")

    def _pairs(self, rng, count: int):
        a = rng.integers(0, len(self.persons), count)
        # another person: a step of 1 .. len - 1 round the list
        b = (a + rng.integers(1, len(self.persons), count)) \
            % len(self.persons)
        return (self.node_of[self.persons[a]] + 1,
                self.node_of[self.persons[b]] + 1)            # uids

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
