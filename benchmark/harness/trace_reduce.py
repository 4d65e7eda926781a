#!/usr/bin/env python3
"""`.xplane.pb` -> device busy time, idle gaps, time per operation.

Run as a child (`python trace_reduce.py <file.xplane.pb>`), with JAX held
to the CPU: `jax.profiler.ProfileData` only parses the file and never
initialises a backend. Prints one JSON object:

  planes        every plane with its lines and event counts (for the eye)
  devices       per device plane: busy_s, ops [[name, seconds, count]]
  modules       [[program, seconds, runs, body_runs]] from the device
                planes' `XLA Modules` line, summed over devices: a compiled
                program's time on the device, by the name jit gave it, how
                often it ran, and how often its loop body ran (below)
  busy_s        union of the device-operation intervals, averaged over the
                device planes
  device_span_s first device operation to last, averaged likewise
  window_s      the traced window: from the first event of any plane to
                the last, or to where the profiler's own `stop_trace`
                begins on the host (its teardown holds the interpreter
                for 0.4-1.6 s in which the server launches nothing: the
                instrument's idle time, not the program's)
  device_ops    [[name, seconds]] summed over devices, largest first
  idle_gaps     [[what the host was doing, seconds]] summed, largest first

A device plane is one named `/device:TPU:<n>`; its operations are the
events of its `XLA Ops` line (all its lines where there is none). A
program's `body_runs` is how often the operations of its loop ran: of the
operations that ran inside the program's spans more often than the program
did, the count that most of them share (an operation outside the loop runs
once a program; one that runs twice a turn, or a second, smaller loop, is
outvoted), with the part of a turn that a cut span holds counted as a
part. 0 where the program has no loop. Where a
trace has no device plane (a CPU rehearsal), events that carry an
`hlo_module` stat on the host's planes stand in, and `device_plane` is
false: such numbers are never a device metric.
"""

from __future__ import annotations

import json
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STOP_EVENT = "stop_trace"       # `jax/_src/profiler.py stop_trace`
TOP = 10
LABELLED_GAPS = 64


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_planes(planes: list) -> dict:
    """`planes`: [{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns, is_hlo)]}]}] -> the summary above. Pure: the tests feed it
    hand-made planes."""
    t_lo, t_hi = None, None
    for pl in planes:
        for ln in pl["lines"]:
            for _n, s, d, _h in ln["events"]:
                t_lo = s if t_lo is None else min(t_lo, s)
                t_hi = s + d if t_hi is None else max(t_hi, s + d)
    if t_lo is None:
        return {"device_plane": False, "devices": [], "modules": [],
                "busy_s": 0.0, "device_span_s": 0.0, "window_s": 0.0,
                "device_ops": [], "idle_gaps": []}
    stops = [s for pl in planes if not pl["name"].startswith("/device:")
             for ln in pl["lines"] for n, s, _d, _h in ln["events"]
             if n.endswith(STOP_EVENT)]
    if stops:
        t_hi = max(min(t_hi, min(stops)), t_lo)
    dev_planes = [pl for pl in planes if pl["name"].startswith("/device:TPU")]
    device_plane = bool(dev_planes)
    per_dev = []
    if device_plane:
        for pl in dev_planes:
            lines = [ln for ln in pl["lines"] if ln["name"] == OPS_LINE] \
                or pl["lines"]
            evs = [(n, s, min(d, t_hi - s)) for ln in lines
                   for n, s, d, _h in ln["events"] if d > 0 and s < t_hi]
            per_dev.append((pl["name"], evs))
        host = [pl for pl in planes if pl not in dev_planes]
    else:
        evs = [(n, s, min(d, t_hi - s)) for pl in planes
               for ln in pl["lines"]
               for n, s, d, h in ln["events"] if h and d > 0 and s < t_hi]
        per_dev.append(("host-standin", evs))
        host = planes
    host_evs = [(s, s + d, n) for pl in host for ln in pl["lines"]
                for n, s, d, h in ln["events"]
                if d > 0 and (device_plane or not h)]
    labeller = _HostLabeller(host_evs)
    devices, totals, gaps = [], {}, {}
    for name, evs in per_dev:
        busy = union([[s, s + d] for _n, s, d in evs])
        ops = {}
        for n, _s, d in evs:
            sec, cnt = ops.get(n, (0.0, 0))
            ops[n] = (sec + d / 1e9, cnt + 1)
            totals[n] = totals.get(n, 0.0) + d / 1e9
        devices.append({
            "plane": name,
            "busy_s": sum(e - s for s, e in busy) / 1e9,
            "span_s": (busy[-1][1] - busy[0][0]) / 1e9 if busy else 0.0,
            "ops": sorted(([n, v[0], v[1]] for n, v in ops.items()),
                          key=lambda x: -x[1])[:4 * TOP]})
        edges = [t_lo] + [t for iv in busy for t in iv] + [t_hi]
        prev_op = {s + d: n for n, s, d in evs}
        idle = sorted(((g1 - g0, g0, g1) for g0, g1
                       in zip(edges[0::2], edges[1::2]) if g1 > g0),
                      reverse=True)
        # name the longest gaps; the many short ones go under one label
        for k, (length, g0, g1) in enumerate(idle):
            if k < LABELLED_GAPS:
                label = labeller.label(g0, g1) or (
                    "after " + prev_op[g0] if g0 in prev_op
                    else "before the first operation")
            else:
                label = "short gaps (not named)"
            gaps[label] = gaps.get(label, 0.0) + length / 1e9
    n_dev = max(len(devices), 1)
    modules = {}
    for pl, (_name, evs) in zip(dev_planes, per_dev):
        spans = {}
        for ln in pl["lines"]:
            if ln["name"] == MODULES_LINE:
                for n, s, d, _h in ln["events"]:
                    spans.setdefault(n.split("(", 1)[0], []).append(
                        (s, s + d))
        for n, ivs in spans.items():
            sec, cnt, body = modules.get(n, (0.0, 0, 0.0))
            modules[n] = (sec + sum(e - s for s, e in ivs) / 1e9,
                          cnt + len(ivs), body + body_runs(evs, ivs))
    return {
        "device_plane": device_plane,
        "devices": devices,
        "modules": sorted(([n, *v] for n, v in modules.items()),
                          key=lambda x: -x[1]),
        "busy_s": sum(d["busy_s"] for d in devices) / n_dev,
        "device_span_s": sum(d["span_s"] for d in devices) / n_dev,
        "window_s": (t_hi - t_lo) / 1e9,
        "device_ops": sorted(([n, s / n_dev] for n, s in totals.items()),
                             key=lambda x: -x[1])[:TOP],
        "idle_gaps": sorted(([n, s / n_dev] for n, s in gaps.items()),
                            key=lambda x: -x[1])[:TOP],
    }


def body_runs(evs: list, spans: list) -> float:
    """How often a program's loop body ran: see the module's docstring.
    `evs` are a device's operations (name, start, dur), `spans` the
    program's [start, end) on that device."""
    import bisect
    spans = sorted(spans)
    starts = [s for s, _e in spans]
    runs = {}                                # name -> [count, seconds]
    for n, s, d in evs:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s + d <= spans[i][1]:
            c = runs.setdefault(n, [0, 0.0])
            c[0] += 1
            c[1] += d
    votes = {}
    for c, _t in runs.values():
        if c > len(spans):
            votes[c] = votes.get(c, 0) + 1
    if not votes:
        return 0.0
    turns = max(votes, key=lambda c: (votes[c], c))
    # a span that the trace cut holds part of a turn: some operations ran
    # once more than others. The body's time over the time of one turn.
    body = [(c, t) for c, t in runs.values()
            if abs(c - turns) <= len(spans) and c > len(spans)]
    return sum(t for _c, t in body) / sum(t / c for c, t in body)


class _HostLabeller:
    """What the host was doing in a gap: the shortest host event that
    covers at least half of it (host events nest, and the outermost is a
    thread's whole life), else the one that covers most of it."""

    def __init__(self, host_evs: list):
        import numpy as np
        self.np = np
        self.starts = np.array([e[0] for e in host_evs], np.float64)
        self.ends = np.array([e[1] for e in host_evs], np.float64)
        self.names = [e[2] for e in host_evs]

    def label(self, g0: float, g1: float):
        np = self.np
        if not len(self.names):
            return None
        cov = np.minimum(self.ends, g1) - np.maximum(self.starts, g0)
        half = np.nonzero(cov >= 0.5 * (g1 - g0))[0]
        if len(half):
            i = half[np.argmin((self.ends - self.starts)[half])]
            return self.names[int(i)]
        i = int(np.argmax(cov))
        return self.names[i] if cov[i] > 0 else None


def read_planes(path: str) -> list:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    # stats are only needed to find the stand-in operations of a trace
    # with no device plane; reading them is most of the cost
    want_stats = not any(pl.name.startswith("/device:TPU")
                         for pl in data.planes)
    planes = []
    for pl in data.planes:
        lines = []
        for ln in pl.lines:
            evs = []
            for e in ln.events:
                # on a device plane an event is named by its whole HLO
                # line; what is left of " = " is the operation's name
                is_hlo = (want_stats and ln.name.startswith("tf_XLA")
                          and any(k == "hlo_module" for k, _v in e.stats))
                evs.append((e.name.split(" = ", 1)[0][:80],
                            float(e.start_ns), float(e.duration_ns), is_hlo))
            lines.append({"name": ln.name, "events": evs})
        planes.append({"name": pl.name, "lines": lines})
    return planes


def dump_small(planes: list, path: str, host_min_ns: float = 2e5) -> None:
    """A recorded trace cut to what a repository can hold: every device
    event, and the host events of 0.2 ms and more (the ones that can name
    a gap), in `reduce_planes`' own input form, gzipped."""
    import gzip
    small = []
    for pl in planes:
        dev = pl["name"].startswith("/device:TPU")
        lines = [{"name": ln["name"],
                  "events": [e for e in ln["events"]
                             if dev or e[3] or e[2] >= host_min_ns]}
                 for ln in pl["lines"]]
        small.append({"name": pl["name"],
                      "lines": [ln for ln in lines if ln["events"]]})
    with gzip.open(path, "wt") as f:
        json.dump(small, f)


def main(argv) -> int:
    planes = read_planes(argv[1])
    if len(argv) > 2:
        dump_small(planes, argv[2])
    out = reduce_planes(planes)
    out["planes"] = [{"name": pl["name"],
                      "lines": [[ln["name"], len(ln["events"])]
                                for ln in pl["lines"]]} for pl in planes]
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
