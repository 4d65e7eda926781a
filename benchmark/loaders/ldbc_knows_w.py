"""`ldbc_knows_w` arrays -> the program's `Store` (runs in the build
child): `knows` with its reverse and one integer facet, `weight`, on every
edge, node i at uid i+1 and rank i. The generator stores every friendship
both ways, so the relation is its own reverse: one CSR serves both
directions. The facet is handed over as ONE typed column in the CSR's
order (`FacetCol.vals`, a uint8 array), never as 68 M Python objects: a
pair's weight is a function of the pair (`generators/ldbc_knows_w.py`), so
it is asked for the edges as the store orders them.

Before anything is built the loader asks the program's planner how it
would serve the configuration's one query: `plan_batch_groups` on
`MIN_BATCH` copies of it, over a two-person store built through the
program's own builder. The configuration states (`served`) that a
facet-weighted `shortest` of a batch is answered by one lane launch that
relaxes distances on the device. A program whose planner returns no lane
plan for it would walk 64 weighted paths on the host, 2 to 12 s a path at
a smaller size (ROADMAP.md Queue 2 A4): minutes a request. That is no
deployment of this configuration, and the build child says so and exits
rather than measure it.
"""

from __future__ import annotations

import numpy as np

from dgraph_tpu.store.store import (FacetCol, PredicateData,
                                    _csr_from_pairs)

from generators import ldbc_knows_w as gen
from traffic_kinds import cheapest_pairs

QUERY = cheapest_pairs.QUERY % ("0x1", "0x2")


def lane_plans() -> tuple:
    """(plans, leftover) of the program's planner for MIN_BATCH copies of
    the cell's query over two persons who know each other."""
    from dgraph_tpu.dql.parser import parse
    from dgraph_tpu.engine import batch
    from dgraph_tpu.store.schema import parse_schema
    from dgraph_tpu.store.store import StoreBuilder
    builder = StoreBuilder(parse_schema(gen.SCHEMA))
    for a, b in ((1, 2), (2, 1)):
        builder.add_edge(a, "knows", b, facets={gen.FACET: 3})
    return batch.plan_batch_groups(
        builder.finalize(), [parse(QUERY) for _ in range(batch.MIN_BATCH)])


def build(data: dict, schema) -> tuple:
    """(uids, preds) for `Store(uids, schema, preds)`."""
    plans, leftover = lane_plans()
    if len(plans) != 1 or leftover:
        raise SystemExit(
            "ldbc_knows_w: this program's planner returns no lane plan "
            f"for a batch of `{QUERY}` ({len(plans)} plans, "
            f"{len(leftover)} queries left to the per-query route); it "
            "would walk 64 weighted paths on the host, which is no "
            "deployment of this configuration")
    n = int(data["n_nodes"])
    uids = np.arange(1, n + 1, dtype=np.int64)
    knows = PredicateData(schema=schema.get("knows"))
    knows.fwd = knows.rev = _csr_from_pairs(data["src"], data["dst"], n)
    # the weights in the CSR's order: every edge has one
    place = gen.structure_places(data)
    src = np.repeat(np.arange(n, dtype=np.int32),
                    np.diff(knows.fwd.indptr))
    knows.efacets[gen.FACET] = FacetCol(
        pos=np.arange(knows.fwd.nnz, dtype=np.int64),
        vals=gen.pair_weights(data, place[src], place[knows.fwd.indices]))
    return uids, {"knows": knows}
