"""Generator arrays -> the program's `Store` (runs in the build child).

The role of upstream's bulk loader in front of `alpha -p`: the served
checkpoint is made from arrays with numpy, not edge by edge through the
mutation path (161 s at SF1 size, PERF.md PR 21). `benchmark/tests`
holds it equal to `models/ldbc.load_into` + `checkpoint_to`.
"""

from __future__ import annotations

import numpy as np

from dgraph_tpu.store.store import (FacetCol, PredicateData, ValueColumn,
                                    _csr_from_pairs)

from generators import ldbc_snb as gen


def _strings(values) -> np.ndarray:
    out = np.empty(len(values), dtype=object)
    out[:] = values
    return out


def build(data: dict, schema) -> tuple:
    """(uids, preds) for `Store(uids, schema, preds)`; uid u has rank u-1."""
    n = gen.sizes(data)["nodes"]
    uids = np.arange(1, n + 1, dtype=np.int64)
    preds = {}
    for pred in gen.EDGE_PREDS:
        pairs = data[pred]
        s = (pairs[:, 0] - 1).astype(np.int32)
        o = (pairs[:, 1] - 1).astype(np.int32)
        pd = PredicateData(schema=schema.get(pred))
        pd.fwd = _csr_from_pairs(s, o, n)
        pd.rev = _csr_from_pairs(o, s, n)
        preds[pred] = pd
    # `knows` is unique and sorted by (src, dst), so row i of the pairs is
    # position i of the forward CSR
    kn = preds["knows"]
    if kn.fwd.nnz != len(data["knows"]):
        raise ValueError("knows pairs are not unique")
    weights = np.empty(len(data["knows_weight"]), dtype=object)
    weights[:] = [float(w) for w in data["knows_weight"]]
    kn.efacets["weight"] = FacetCol(
        pos=np.arange(kn.fwd.nnz, dtype=np.int64), vals=weights)

    def column(pred, subj_uids, vals):
        pd = PredicateData(schema=schema.get(pred))
        pd.vals[""] = ValueColumn(subj=(subj_uids - 1).astype(np.int32),
                                  vals=vals)
        preds[pred] = pd

    persons = data["person_uids"]
    column("first_name", persons,
           _strings([gen.FIRST_NAMES[i] for i in data["first_name"]]))
    column("last_name", persons,
           _strings([gen.LAST_NAMES[i] for i in data["last_name"]]))
    column("city", persons, _strings([gen.CITIES[i] for i in data["city"]]))
    column("birthday_year", persons, data["birthday_year"].astype(np.int64))
    msgs = np.concatenate([data["post_uids"], data["comment_uids"]])
    column("creation_ts", msgs, data["creation_ts"].astype(np.int64))
    column("tag_name", data["tag_uids"],
           _strings([f"tag_{i}" for i in range(len(data["tag_uids"]))]))
    column("forum_title", data["forum_uids"],
           _strings([f"forum_{i}" for i in range(len(data["forum_uids"]))]))
    column("org_name", data["org_uids"],
           _strings([f"org_{i}" for i in range(len(data["org_uids"]))]))
    return uids, preds
