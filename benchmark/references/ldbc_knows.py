"""Plain reference for LDBC SNB Interactive's complex read IC1 over the
`ldbc_knows` graph (numpy only).

Works on the generator's own arrays; imports nothing of the program. IC1,
"transitive friends with a certain name", as this repository's DQL asks it:

    v as var(func: uid(P)) @recurse(depth: 3, loop: false) { knows }
    q(func: uid(v), orderasc: last_name, first: 20)
      @filter(eq(first_name, "X")) { first_name last_name city }

The rule: `v` is everyone at distance 0 to 3 from the start person along
the stored directed edges, the start person among them (the engine's block
var holds the block's root beside everything the recursion reached; the
specification leaves the start person out and orders by distance first,
which DQL has no way to say: the configuration lists both under
`assumed`). Of those, the persons whose first name is the one asked, by
last name ascending and then by uid, the first 20, each as its three
properties. One answer is compared whole and exactly.

A breadth-first search to depth 3 over the CSR the generator hands over
(`row_start`, `row_len`, `dst`); a hop's edges are listed as the Graph500
reference lists them (`references/graph500.py`: row by row for a small
frontier, by a mark on every edge of the list for a large one; the third
hop of a person with a hundred friends expands most of the edges there
are).
"""

from __future__ import annotations

import numpy as np

from generators import ldbc_knows as gen
from references import graph500


class Reference(graph500.Reference):
    """The Graph500 reference's adjacency and its two ways of listing a
    hop's edges (node i's friends are `dst[row_start[i] : row_start[i] +
    row_len[i]]`; `row_cap`, the control, cuts every row at that many
    edges), with the persons' properties beside them."""

    def __init__(self, data: dict, row_cap: int = 0):
        super().__init__(data, row_cap)
        words = gen.dictionaries(data)
        self.first = np.asarray(data["first_name"])
        self.last = np.asarray(data["last_name"])
        self.city = np.asarray(data["city"])
        self.first_index = {w: i for i, w in enumerate(words["first_name"])}
        self.last_names, self.cities = words["last_name"], words["city"]
        # a last name's place in the order of the strings
        self.last_place = np.empty(len(self.last_names), np.int64)
        self.last_place[np.argsort(np.array(self.last_names))] = \
            np.arange(len(self.last_names))

    def reached(self, start: int, k: int) -> np.ndarray:
        """bool[n]: the nodes at distance 0..k from node index `start`."""
        seen = np.zeros(self.n, bool)
        seen[start] = True
        frontier = np.array([start], np.int64)
        for _ in range(k):
            fresh = np.zeros(self.n, bool)
            fresh[self._targets(frontier)] = True
            fresh &= ~seen
            frontier = np.nonzero(fresh)[0]
            if not len(frontier):
                break
            seen |= fresh
        return seen

    def answer(self, meta: dict) -> dict:
        """The right answer, shaped as the program shapes it."""
        # a name that is in no dictionary is nobody's
        name = self.first_index.get(meta["first_name"], -1)
        found = np.nonzero(self.reached(meta["person"] - 1, meta["depth"])
                           & (self.first == name))[0]
        # by last name as a string, then by uid (node index + 1)
        found = found[np.lexsort((found, self.last_place[self.last[found]]))]
        return {"q": [{"first_name": meta["first_name"],
                       "last_name": self.last_names[self.last[i]],
                       "city": self.cities[self.city[i]]}
                      for i in found[:meta["first"]]]}


def make(data: dict, params: dict) -> Reference:
    return Reference(data)


def make_control(data: dict, params: dict) -> Reference:
    """An approximate route where the configuration states an exact one:
    every adjacency row cut at 8 edges."""
    return Reference(data, row_cap=8)
