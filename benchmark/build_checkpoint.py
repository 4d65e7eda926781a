#!/usr/bin/env python3
"""Build child: generator arrays on disk -> a checkpoint `alpha --p` opens.

Runs with JAX held to the CPU (the program's store module imports jax;
this process must never reach for the chip). Usage:
  build_checkpoint.py --generator NAME --arrays DIR --p DIR
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def save_arrays(data: dict, dirname: str) -> None:
    import numpy as np
    os.makedirs(dirname, exist_ok=True)
    for k, v in data.items():
        np.save(os.path.join(dirname, k + ".npy"), np.asarray(v))


def load_arrays(dirname: str) -> dict:
    import numpy as np
    return {f[:-4]: np.load(os.path.join(dirname, f), mmap_mode="r")
            for f in sorted(os.listdir(dirname)) if f.endswith(".npy")}


def build(generator: str, arrays_dir: str, p_dir: str) -> dict:
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("the build child must run with JAX_PLATFORMS=cpu")
    from dgraph_tpu.store import checkpoint
    from dgraph_tpu.store.schema import parse_schema
    from dgraph_tpu.store.store import (TYPE_PRED, Store, build_indexes)
    from dgraph_tpu.store.types import Kind

    gen = importlib.import_module(f"generators.{generator}")
    loader = importlib.import_module(f"loaders.{generator}")
    secs = {}
    t0 = time.perf_counter()
    data = load_arrays(arrays_dir)
    schema = parse_schema(gen.SCHEMA)
    # what StoreBuilder does for the type predicate
    tp = schema.get(TYPE_PRED)
    tp.kind, tp.is_list = Kind.STRING, True
    if not tp.index_tokenizers:
        tp.index_tokenizers = ("exact",)
    uids, preds = loader.build(data, schema)
    build_indexes(preds)
    store = Store(uids=uids, schema=schema, preds=preds)
    secs["build"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    checkpoint.save_versioned(store, p_dir, base_ts=1)
    secs["save"] = time.perf_counter() - t0
    return secs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--generator", required=True)
    ap.add_argument("--arrays", required=True)
    ap.add_argument("--p", required=True)
    args = ap.parse_args(argv)
    print(json.dumps(build(args.generator, args.arrays, args.p)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
