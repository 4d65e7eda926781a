"""The weighted lane program's share of its roofline, in % (device trace
and the program's own count of its rounds).

args: `edges`, the key of `generators/<g>.sizes` that counts the stored
edges of the one relation the program relaxes over; `family`, the label
of its counters; `dist_bytes` and `weight_bytes`, the widths of a
distance and of a stored weight (the configuration's: int16 and uint8 for
weights of 1 to 40 within 64 rounds).

The least a pulled round of the min-plus lane program (`ops/bfs.py
make_ell_relax`) must move through HBM, from shapes alone:
  - every stored in-edge's int32 index and its weight, once:
                                            (4 + weight_bytes) * edges
  - one distance row of `lanes` distances gathered a stored edge:
                                     dist_bytes * lanes * edges
  - the distances read and written:  2 * dist_bytes * lanes * (nodes + 1)
(padding slots, tile partials and the second level are what the layout
costs on top, and count against the share). The program pushes no round.

The ROUNDS come from the program's own counter over the window,
`kernel_relax_rounds_total{family=}`, not from a vote over the trace's
loop bodies (`trace_reduce.body_runs`, which a `cond` in a body splits).
A launch of this program can outlast the traced seconds, so the time is
not a sum of whole program spans either: it is the device's busy share of
the trace (`busy_s / device_span_s`, what `device_ms_per_query.batch`
reads) over the rate of completed queries, times the queries the family's
launches answered in the window: every operation that ran on the device
is billed to the program, so the share reads low rather than high.
Share = rounds * bytes / peak bandwidth / time. The peak comes from
`peaks.json` by `device_kind`; a kind that is not there is an error.
"""

import json
import os

from readers.prom_ratio import delta


def round_bytes(nodes: int, edges: int, lanes: int, dist_bytes: int,
                weight_bytes: int) -> int:
    return ((4 + weight_bytes) * edges + dist_bytes * lanes * edges
            + 2 * dist_bytes * lanes * (nodes + 1))


def read(ctx: dict, edges: str = "edges", family: str = "weighted",
         dist_bytes: int = 2, weight_bytes: int = 1):
    tr = ctx.get("trace")
    if not tr or not tr.get("device_plane") or not tr.get("device_span_s") \
            or not ctx.get("completed_qps"):
        return None
    labels = {"family": family}
    rounds = delta(ctx, [{"name": "kernel_relax_rounds_total",
                          "labels": labels}])
    queries = delta(ctx, [{"name": "kernel_group_queries_total",
                           "labels": labels}])
    if rounds <= 0 or queries <= 0:
        return None
    with open(os.path.join(ctx["root"], "peaks.json")) as f:
        peaks = json.load(f)
    kind = ctx["device"]["kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    secs = tr["busy_s"] / tr["device_span_s"] / ctx["completed_qps"] * queries
    need = rounds * round_bytes(ctx["sizes"]["nodes"], ctx["sizes"][edges],
                                int(ctx["traffic"]["batch"]), dist_bytes,
                                weight_bytes)
    return 100.0 * need / peaks[kind]["hbm_bytes_per_s"] / secs
