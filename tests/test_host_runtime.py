"""The seven per-layer metrics of the layer "host runtime" (ISSUE 40), read
as the benchmark reads them: each `benchmark/layer_metrics/<name>.json`
through `benchmark/readers/prom_ratio.py`, over the registry's own
exposition rendered before and after a small `/query/batch`.

Every file reads a number where the program exports its series, and
nothing (not 0) from an exposition that lacks them: what the parent's
program gives the same files.
"""

import gc
import json
import os
import sys
import urllib.request

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness.server import parse_prom           # noqa: E402
from readers import prom_ratio                  # noqa: E402

from dgraph_tpu.utils import tracing            # noqa: E402
from dgraph_tpu.utils.metrics import METRICS    # noqa: E402

HOST_RUNTIME = ["host_cpu_ms.batch", "host_sys_ms.batch",
                "host_stall_ms.batch", "gc_ms.batch", "dispatch_ms.batch",
                "preemptions.batch", "background_cpu_ms.batch"]
# the series this PR adds: an exposition without them is the parent's
NEW_SERIES = ("phase_cpu_us_total", "phase_offcpu_us_total",
              "request_ctx_switches_total", "gc_pause_us",
              "jit_dispatch_us", "background_cpu_us_total")


def _spec(name: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "prom_ratio"
    return spec["args"]


@pytest.fixture(scope="module")
def window():
    """(before, after): the exposition around one warm `/query/batch` of
    four shortest queries, served by a process armed as `alpha` arms it."""
    from dgraph_tpu.server.api import Alpha
    from dgraph_tpu.server.http import make_http_server, serve_background

    tracing.set_enabled(True)
    tracing.arm()
    alpha = Alpha(device_threshold=10**9)
    alpha.alter("name: string @index(exact) .\nfollows: [uid] @reverse .")
    lines = [f'_:p{i} <name> "p{i}" .' for i in range(12)]
    lines += [f"_:p{i} <follows> _:p{i + 1} ." for i in range(11)]
    uids = alpha.mutate(set_nquads="\n".join(lines))["uids"]
    u = [uids[f"_:p{i}"] for i in range(12)]
    srv = make_http_server(alpha)
    serve_background(srv)

    def batch(tag: str) -> None:
        qs = ['{ path as shortest(from: %s, to: %s) { follows } '
              '%s(func: uid(path)) { name } }' % (u[i], u[i + 5], tag)
              for i in range(4)]
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.server_address[1]}/query/batch",
            data=json.dumps({"queries": qs}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as r:
            assert len(json.loads(r.read())["data"]) == 4

    try:
        batch("warm")                    # the launch's first call compiles
        before = parse_prom(METRICS.render())
        batch("timed")                   # a seen key: a dispatch, no compile
        with tracing.background("flightrec"):
            gc.collect(0)
        after = parse_prom(METRICS.render())
    finally:
        srv.shutdown()
        gc.callbacks.remove(tracing._gc_hook)
    return before, after


@pytest.mark.parametrize("name", HOST_RUNTIME)
def test_metric_reads_a_number_where_the_series_are(window, name):
    before, after = window
    v = prom_ratio.read({"prom_before": before, "prom_after": after},
                        **_spec(name))
    assert v is not None and v >= 0
    if name in ("host_cpu_ms.batch", "dispatch_ms.batch", "gc_ms.batch"):
        assert v > 0                    # the batch ran, dispatched, collected


@pytest.mark.parametrize("name", HOST_RUNTIME)
def test_metric_reads_nothing_from_the_parents_exposition(window, name):
    def parents(series):
        return [(n, ls, v) for n, ls, v in series
                if not n.startswith(tuple("dgraph_tpu_" + s
                                          for s in NEW_SERIES))]
    before, after = window
    assert len(parents(after)) < len(after)
    ctx = {"prom_before": parents(before), "prom_after": parents(after)}
    assert prom_ratio.read(ctx, **_spec(name)) is None
    # the denominator is there: it is the numerator that is missing
    assert prom_ratio.delta(ctx, _spec(name)["den"]) == 1


def test_benchmark_lists_the_seven_under_their_layers():
    """By name, not by place: a later PR appends entries and cells."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    got = {m["name"]: m for m in bench["per_layer"]
           if m["name"] in HOST_RUNTIME}
    assert sorted(got) == sorted(HOST_RUNTIME)
    for name, m in got.items():
        assert m["layer"] == ("device programs" if name ==
                              "dispatch_ms.batch" else "host runtime")
        assert (m["moves"], m["source"], m["better"]) == \
            ("completed_qps", "program_counter", "lower")
        assert {"follower.shortest-batch", "g500-22.khop3-batch",
                "knows-7_5.ic1-batch"} <= set(m["workloads"])
