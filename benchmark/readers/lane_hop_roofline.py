"""The lane BFS step's share of its roofline, in % (device trace).

The least a hop of the bit-packed lane BFS (`ops/bfs.py`, one compiled
program `jit_step`) must move through HBM, from shapes alone:
  - every stored in-edge's int32 index, once:            4 * edges
  - one mask row of W uint32 words gathered per edge:    4 * W * edges
  - the next frontier written, `seen` read and written:  3 * 4 * W * (nodes + 1)
with W = lanes / 32. The time is the device time of the `jit_step`
program in the trace (`XLA Modules`), and the hops in it are the runs of
that program's loop body in the same spans (`trace_reduce.body_runs`).
Share = hops * bytes / peak bandwidth / time. The peak comes from `peaks.json`
by `device_kind`; a kind that is not there is an error, not a default.
"""

import json
import os


def hop_bytes(nodes: int, edges: int, lanes: int) -> int:
    w = max(lanes // 32, 1)
    return 4 * edges + 4 * w * edges + 3 * 4 * w * (nodes + 1)


def read(ctx: dict, program: str = "jit_step"):
    tr = ctx.get("trace")
    if not tr or not tr.get("device_plane"):
        return None
    mine = [m for m in tr.get("modules", []) if m[0] == program]
    secs = sum(m[1] for m in mine)
    hops = sum(m[3] for m in mine)
    if secs <= 0 or hops <= 0:
        return None
    with open(os.path.join(ctx["root"], "peaks.json")) as f:
        peaks = json.load(f)
    kind = ctx["device"]["kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    lanes = int(ctx["traffic"]["batch"])
    need = hops * hop_bytes(ctx["sizes"]["nodes"], ctx["sizes"]["edges"],
                            lanes)
    return 100.0 * need / peaks[kind]["hbm_bytes_per_s"] / secs
