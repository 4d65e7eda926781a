"""LDBC datagen's person-knows-person graph with the weight of LDBC SNB's
cheapest-path reads on every `knows`, as arrays (numpy only).

The graph is `generators/ldbc_knows.py`'s, bit for bit, at the same
`generator_params` and seed; this file adds one integer per stored edge,

    weight = max(round(40 - sqrt(numInteractions)), 1)        1 .. 40,

the weight of BI Q19 that Interactive v2's IC14 took over, the same both
ways of a pair. `numInteractions` counts the replies two persons wrote to
each other's messages; the messages are not generated here, so its law is
this file's (`configs/ldbc-knows-7_5w-fb.json` lists it under `assumed`):
a log-normal count, floor(exp(N(ln m, 1.3))), with median m =
`interactions_local` for two persons of one community of the structure
and `interactions_far` for two of different ones: most pairs a handful
of replies, a few hundreds. A pair with no interaction keeps its edge, at
weight 40 (the formula at 0).

A pair's count is a pure function of the pair's two places in the graph's
STRUCTURE and of `structure_seed` (a counter-based hash, no stream of
draws), so it does not move with `--seed`, is the same both ways, and can
be asked for any list of pairs in any order: the loader asks it for the
edges in the store's order and never aligns two 68 M-row arrays.
"""

from __future__ import annotations

import numpy as np

from generators import ldbc_knows as base

SCHEMA = "knows: [uid] @reverse .\n"      # with the facet weight: int
FACET = "weight"
SIGMA = 1.3                # of ln(numInteractions)
_M1, _M2 = np.uint64(0xBF58476D1CE4E5B9), np.uint64(0x94D049BB133111EB)
_GOLD = np.uint64(0x9E3779B97F4A7C15)


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finaliser, over a uint64 array (wrapping on purpose)."""
    x = (x ^ (x >> np.uint64(30))) * _M1
    x = (x ^ (x >> np.uint64(27))) * _M2
    return x ^ (x >> np.uint64(31))


LAW = ("persons", "structure_seed", "interactions_local",
       "interactions_far")      # what a weight is a function of, beside
#                                 the pair: `generate` hands them on


def communities(law: dict) -> np.ndarray:
    """The community of every place of the structure: the first draw of
    `ldbc_knows._structure` at `structure_seed`, made again."""
    n = int(law["persons"])
    root = np.random.default_rng(int(law["structure_seed"]))
    return root.integers(0, max(int(np.sqrt(n)), 4), n)


def pair_weights(law: dict, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """uint8 weight of each pair (a[i], b[i]) of places of the structure,
    whichever way round it is given. `law` holds the keys of LAW: the
    parameters, or what `generate` returned."""
    n = np.uint64(int(law["persons"]))
    comm = communities(law)
    a, b = np.asarray(a), np.asarray(b)
    with np.errstate(over="ignore"):
        key = (np.minimum(a, b).astype(np.uint64) * n
               + np.maximum(a, b).astype(np.uint64)
               + np.uint64(int(law["structure_seed"])) * _GOLD)
        h1, h2 = _mix(key), _mix(key + _GOLD)
    # two uniforms in (0, 1) from the hashes' top 53 bits, one normal
    u1 = ((h1 >> np.uint64(11)).astype(np.float64) + 0.5) * 2.0 ** -53
    u2 = ((h2 >> np.uint64(11)).astype(np.float64) + 0.5) * 2.0 ** -53
    z = np.sqrt(-2.0 * np.log(u1)) * np.cos(2.0 * np.pi * u2)
    median = np.where(comm[a] == comm[b],
                      float(law["interactions_local"]),
                      float(law["interactions_far"]))
    count = np.floor(median * np.exp(SIGMA * z))
    return np.maximum(np.rint(40.0 - np.sqrt(count)), 1.0).astype(np.uint8)


def generate(params: dict, seed: int) -> dict:
    """`ldbc_knows.generate`'s graph (`src`, `dst`, `row_start`,
    `row_len`, `node_of_structure`, `n_nodes`, `max_degree`; the persons'
    properties are not kept: the configuration loads none), `weight`,
    uint8 beside `src`/`dst`, edge for edge, and the parameters of LAW."""
    data = base.generate(params, seed)
    out = {k: data[k] for k in ("src", "dst", "row_start", "row_len",
                                "node_of_structure", "n_nodes",
                                "max_degree")}
    place = structure_places(out)
    out["weight"] = pair_weights(params, place[out["src"]],
                                 place[out["dst"]])
    for k in LAW:
        out[k] = np.array(params[k], np.float64)
    return out


def structure_places(data: dict) -> np.ndarray:
    """node -> its place in the structure (`node_of_structure` inverted)."""
    perm = np.asarray(data["node_of_structure"])
    place = np.empty(len(perm), np.int32)
    place[perm] = np.arange(len(perm), dtype=np.int32)
    return place


def sizes(data: dict) -> dict:
    return base.sizes(data)
