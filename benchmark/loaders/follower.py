"""Follower arrays -> the program's `Store` (runs in the build child):
one predicate `follows` with its reverse, node i at uid i+1 and rank i."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from dgraph_tpu.store.store import PredicateData, _csr_from_pairs


def build(data: dict, schema) -> tuple:
    n = int(data["n_nodes"])
    src, dst = data["src"], data["dst"]
    uids = np.arange(1, n + 1, dtype=np.int64)
    pd = PredicateData(schema=schema.get("follows"))
    # the native CSR build releases the interpreter lock: both directions
    # sort side by side
    with ThreadPoolExecutor(max_workers=2) as pool:
        fwd = pool.submit(_csr_from_pairs, src, dst, n)
        rev = pool.submit(_csr_from_pairs, dst, src, n)
        pd.fwd, pd.rev = fwd.result(), rev.result()
    return uids, {"follows": pd}
