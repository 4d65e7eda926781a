"""`ldbc_knows` arrays -> the program's `Store` (runs in the build child):
`knows` with its reverse and the four person properties, node i at uid
i+1 and rank i. The generator stores every friendship both ways, so the
relation is its own reverse: one CSR serves both directions.

Before anything is built the loader asks the program's planner how it
would serve the configuration's one query, and reads the answer off the
plan's own record (`TreePlan.var_reads`: for every block that reads the
var of an @recurse stage no block renders, the label
`tree_var_reads_total{by=}` will count the read under). The configuration
states (`served`) that IC1's filtered reader of the 3-hop var is answered
by probing the filter's candidates against the device's `seen`. A program
whose plan records `column` there, or keeps no such record, lists each
lane's 300,000 to 600,000 reached persons on the host and then filters,
orders and cuts them to 20 in Python: 25.9 queries/s with the device idle
0.71 of the time and `render_ms.batch` 1571 of `server_ms.batch` 2506
(ledger, PR 36, the parent's column, TPU v5 lite), where the probing
program gives 66.1 with idle 0.12. That is a host walk and no deployment
of this configuration, and the build child says so and exits rather than
measure it.
"""

from __future__ import annotations

import types

import numpy as np

from dgraph_tpu.store.store import (PredicateData, ValueColumn,
                                    _csr_from_pairs)

from generators import ldbc_knows as gen
from traffic_kinds import ic1_persons

QUERY = ic1_persons.QUERY % ("0x1", 3, "false", "knows", "last_name", 20,
                             "Ba")


def recorded_reads(schema) -> list | None:
    """The `by` of every entry of the plan's record for IC1, in its order:
    `["probe"]` is the deployment. None where the planner makes no
    level-tree plan of the query; `[]` where its plan keeps no record."""
    from dgraph_tpu.dql.parser import parse
    from dgraph_tpu.engine import treebatch
    planned = treebatch.plan_tree(types.SimpleNamespace(schema=schema),
                                  parse(QUERY))
    if planned is None:
        return None
    return [r.by for r in getattr(planned[1], "var_reads", ())]


def build(data: dict, schema) -> tuple:
    """(uids, preds) for `Store(uids, schema, preds)`."""
    reads = recorded_reads(schema)
    if reads != ["probe"]:
        raise SystemExit(
            "ldbc_knows: this program's plan does not record that IC1's "
            "filtered reader of the 3-hop var is answered by probing the "
            f"filter's candidates (its record of the reads: {reads}); it "
            "would list every lane's reached persons on the host, which "
            "is no deployment of this configuration")
    n = int(data["n_nodes"])
    uids = np.arange(1, n + 1, dtype=np.int64)
    subj = np.arange(n, dtype=np.int32)
    knows = PredicateData(schema=schema.get("knows"))
    knows.fwd = knows.rev = _csr_from_pairs(data["src"], data["dst"], n)
    preds = {"knows": knows}
    for prop, strings in gen.dictionaries(data).items():
        words = np.empty(len(strings), dtype=object)
        words[:] = strings
        pd = PredicateData(schema=schema.get(prop))
        pd.vals[""] = ValueColumn(subj=subj, vals=words[data[prop]])
        preds[prop] = pd
    pd = PredicateData(schema=schema.get("birthday_year"))
    pd.vals[""] = ValueColumn(
        subj=subj, vals=np.asarray(data["birthday_year"], np.int64))
    preds["birthday_year"] = pd
    return uids, preds
