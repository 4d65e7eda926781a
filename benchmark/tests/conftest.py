"""Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`
(not part of the repo's tier-1 run)."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def holds_entries(bench: dict, ent: dict, cell: str) -> None:
    """`data/<cell>.entries.json` against BENCHMARK.json: the entries it
    names are present, and the cell is among the `workloads` of each metric
    it names. Cells and metrics that later PRs append (a cell that joins a
    metric's `workloads`, an entry behind the last) are not its business."""
    for group in ("configs", "workloads"):
        for e in ent[group]:
            assert e in bench[group], e["name"]
    held = {m["name"]: m for g in ("end_to_end", "per_layer")
            for m in bench[g]}
    for e in ent["per_layer"]:
        got = held[e["name"]]
        assert {**got, "workloads": e["workloads"]} == e, e["name"]
    for name in [e["name"] for e in ent["per_layer"]] + ent["also_in"]:
        assert cell in held[name]["workloads"], name
