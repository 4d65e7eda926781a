"""Graph500 arrays -> the program's `Store` (runs in the build child):
one predicate `link`, forward only (nothing walks back), node i at uid
i+1 and rank i.

Before anything is built the loader asks the program's planner how it
would serve the configuration's one query. A program that keeps per-hop
masks for an @recurse stage no block renders rebuilds every lane's visited
edges on the host: 10^7 edge visits a lane at this scale, 64 lanes a batch
(PERF.md, PR 34). That is no deployment of this configuration, and the
build child says so and exits rather than leave a run grinding.
"""

from __future__ import annotations

import types

from dgraph_tpu.store.store import PredicateData, _csr_from_pairs

QUERY = ("{ N as var(func: uid(0x1)) @recurse(depth: 3, loop: false) "
         "{ link } q(func: uid(N)) { count(uid) } }")


def counts_on_device(schema) -> bool:
    """Does the level-tree planner hand the count query's @recurse stage
    to the device whole, with no hop masks kept for the host?"""
    from dgraph_tpu.dql.parser import parse
    from dgraph_tpu.engine.treebatch import plan_tree
    planned = plan_tree(types.SimpleNamespace(schema=schema), parse(QUERY))
    return planned is not None and not any(
        s.keep_hops for s in planned[1].stages)


def build(data: dict, schema) -> tuple:
    import numpy as np
    if not counts_on_device(schema):
        raise SystemExit(
            "graph500: this program rebuilds an unrendered @recurse "
            "stage's hop masks on the host; it cannot serve the k-hop "
            "count at this scale inside a window")
    n = int(data["n_nodes"])
    uids = np.arange(1, n + 1, dtype=np.int64)
    pd = PredicateData(schema=schema.get("link"))
    pd.fwd = _csr_from_pairs(data["src"], data["dst"], n)
    return uids, {"link": pd}
