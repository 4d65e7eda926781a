"""Traffic kind `khop_seeds`: the k-hop neighbourhood count of a seed list.

Each query asks how many vertices lie within `depth` hops of one seed:

    N as var(func: uid(S)) @recurse(depth: D, loop: false) { <predicate> }
    q(func: uid(N)) { count(uid) }

`batch` of them, over seeds that all differ, ride in one request, a JSON
list to `/query/batch`; one number an answer. A seed is drawn uniformly
over the vertices with an out-edge.

The seeds are places in the graph's structure, drawn from the mix's
`schedule_seed`, `draw_requests` requests' worth at a time and all
distinct; the run's seed deals each such draw into its requests and names
the places (the uids). With a draw of one request every run sends the same
requests in the same order, and the seed picks which lane a seed rides and
what everyone is called (`recurse_roots` has the reasons). A graph with
fewer such vertices than a stream wants, a rehearsal's, goes round them
again; a round is a whole number of requests and no draw is dealt across
two, so that a request's seeds still all differ.
"""

from __future__ import annotations

import json

import numpy as np

QUERY = ("{ N as var(func: uid(%s)) @recurse(depth: %d, loop: %s) { %s } "
         "q(func: uid(N)) { count(uid) } }")


class Mix:
    def __init__(self, data: dict, params: dict, seed: int):
        self.batch = int(params["batch"])
        self.depth = int(params["depth"])
        self.pred = params["predicate"]
        self.loop = "true" if params["recurse_loop"] else "false"
        if params["seeds"] != "uniform-distinct":
            raise SystemExit(f"seeds: {params['seeds']!r} is not a draw "
                             f"this kind knows")
        self.draw = int(params["draw_requests"]) * self.batch
        self.params, self.seed = params, seed
        self.node_of = np.asarray(data["node_of_structure"], np.int64)
        # places of the structure whose vertex has an out-edge
        self.sources = np.nonzero(
            np.asarray(data["row_len"])[self.node_of] > 0)[0]
        # a round: the most places, all distinct, that fill whole requests
        self.round = len(self.sources) // self.batch * self.batch
        if not self.round:
            raise SystemExit(f"fewer than {self.batch} vertices with a "
                             f"{self.pred} edge: no request can be filled")

    def requests(self, count: int, stream: int = 0) -> list:
        total = count * self.batch
        rng = np.random.default_rng(
            [int(self.params["schedule_seed"]), stream])
        one = self.sources[rng.permutation(len(self.sources))[:self.round]]
        places = np.tile(one, -(-total // self.round))[:total]
        deal = np.random.default_rng([self.seed, 3, stream])
        cuts = sorted(set(range(0, total, self.draw))
                      | set(range(0, total, self.round)) | {total})
        for lo, hi in zip(cuts, cuts[1:]):
            places[lo:hi] = places[lo:hi][deal.permutation(hi - lo)]
        uids = self.node_of[places] + 1
        out = []
        for i in range(count):
            metas = [{"template": "khop", "seed": int(u),
                      "depth": self.depth}
                     for u in uids[i * self.batch:(i + 1) * self.batch]]
            queries = [QUERY % (hex(m["seed"]), self.depth, self.loop,
                                self.pred) for m in metas]
            out.append({"path": self.params["endpoint"],
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
