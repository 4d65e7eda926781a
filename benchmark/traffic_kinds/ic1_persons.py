"""Traffic kind `ic1_persons`: LDBC SNB complex read IC1 over a list of
start persons.

Each query asks for the persons within `depth` `knows`-steps of one start
person whose first name is the one given, the first `first` of them in
the order of `order`:

    v as var(func: uid(P)) @recurse(depth: D, loop: false) { <predicate> }
    q(func: uid(v), orderasc: <order>, first: F)
      @filter(eq(first_name, "X")) { first_name last_name city }

(`ic_mix.py TEMPLATES["IC1"]`, word for word.) `batch` of them, over start
persons that all differ, ride in one request, a JSON list to
`/query/batch`. A start person is drawn uniformly over the persons; the
name asked is the first name of a second person drawn uniformly, so a name
is asked as often as it is borne (the specification's curated parameters
take names that occur).

Both persons are places in the graph's structure, drawn from the mix's
`schedule_seed`, `draw_requests` requests' worth at a time, the start
persons all distinct; the run's seed deals each such draw into its
requests and names the places (their uids and what they are called). With
a draw of one request every run sends the same requests in the same order,
and the seed picks which lane a pair rides (`recurse_roots` has the
reasons). A graph with fewer persons than a stream wants, a rehearsal's,
goes round them again; a round is a whole number of requests and no draw
is dealt across two, so that a request's start persons still all differ.
"""

from __future__ import annotations

import json

import numpy as np

from generators import ldbc_knows as gen

QUERY = ('{ v as var(func: uid(%s)) @recurse(depth: %d, '
         'loop: %s) { %s } '
         'q(func: uid(v), orderasc: %s, first: %d) '
         '@filter(eq(first_name, "%s")) '
         '{ first_name last_name city } }')


class Mix:
    def __init__(self, data: dict, params: dict, seed: int):
        self.batch = int(params["batch"])
        self.depth = int(params["depth"])
        self.first = int(params["first"])
        self.pred, self.order = params["predicate"], params["order"]
        self.loop = "true" if params["recurse_loop"] else "false"
        if (params["persons"], params["names"]) != (
                "uniform-distinct", "of-a-uniform-person"):
            raise SystemExit(f"persons: {params['persons']!r}, names: "
                             f"{params['names']!r} are not draws this "
                             f"kind knows")
        self.draw = int(params["draw_requests"]) * self.batch
        self.params, self.seed = params, seed
        self.node_of = np.asarray(data["node_of_structure"], np.int64)
        self.first_name = np.asarray(data["first_name"])
        self.names = gen.dictionaries(data)["first_name"]
        # a round: the most places, all distinct, that fill whole requests
        self.round = len(self.node_of) // self.batch * self.batch
        if not self.round:
            raise SystemExit(f"fewer than {self.batch} persons: no "
                             f"request can be filled")

    def requests(self, count: int, stream: int = 0) -> list:
        total = count * self.batch
        rng = np.random.default_rng(
            [int(self.params["schedule_seed"]), stream])
        one = rng.permutation(len(self.node_of))[:self.round]
        places = np.tile(one, -(-total // self.round))[:total]
        named = rng.integers(0, len(self.node_of), total)
        deal = np.random.default_rng([self.seed, 3, stream])
        cuts = sorted(set(range(0, total, self.draw))
                      | set(range(0, total, self.round)) | {total})
        for lo, hi in zip(cuts, cuts[1:]):
            lanes = deal.permutation(hi - lo)
            places[lo:hi] = places[lo:hi][lanes]
            named[lo:hi] = named[lo:hi][lanes]
        uids = self.node_of[places] + 1
        asked = self.first_name[self.node_of[named]]
        out = []
        for i in range(count):
            at = slice(i * self.batch, (i + 1) * self.batch)
            metas = [{"template": "IC1", "person": int(u),
                      "first_name": self.names[k], "depth": self.depth,
                      "first": self.first}
                     for u, k in zip(uids[at], asked[at])]
            queries = [QUERY % (hex(m["person"]), self.depth, self.loop,
                                self.pred, self.order, self.first,
                                m["first_name"]) for m in metas]
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
