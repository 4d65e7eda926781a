"""The per-layer metrics that read the program's phases and hop counters
(PR 24): `readers/prom_total.py`, and every `layer_metrics/*.json` that PR
added, resolved against a recorded exposition.

The exposition is `GET /debug/prometheus_metrics` of an alpha on the CPU
over a 16-node chain, cut to the series these metrics read: BEFORE after
one warm-up `/query/batch` of 64 `shortest` (the ELL build, its upload
and the one program's compile are in it), AFTER two more such batches:
the window. No path is over 5 hops, so a launch of 8 hops used 5."""

import json
import os

import pytest

from conftest import BENCH
from harness.server import parse_prom
from readers import prom_ratio, prom_total

BEFORE = """\
# TYPE dgraph_tpu_kernel_group_launches_total counter
dgraph_tpu_kernel_group_launches_total{family="shortest"} 1.0
# TYPE dgraph_tpu_kernel_group_queries_total counter
dgraph_tpu_kernel_group_queries_total{family="shortest"} 64.0
# TYPE dgraph_tpu_kernel_hops_run_total counter
dgraph_tpu_kernel_hops_run_total{family="shortest"} 8.0
# TYPE dgraph_tpu_kernel_hops_used_total counter
dgraph_tpu_kernel_hops_used_total{family="shortest"} 5.0
# TYPE dgraph_tpu_jit_compile_us histogram
dgraph_tpu_jit_compile_us_sum{kernel="bfs.ell_step"} 182318.33400000143
dgraph_tpu_jit_compile_us_count{kernel="bfs.ell_step"} 1
# TYPE dgraph_tpu_phase_us histogram
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="admission.admit"} 17.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="admission.admit"} 1
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.build_ell"} 356.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.build_ell"} 1
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.device_wait"} 182422.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.device_wait"} 1
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.fetch"} 24.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.fetch"} 1
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.plan"} 14608.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.plan"} 1
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.render"} 17985.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.render"} 1
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.scan"} 294.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.scan"} 1
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.seed"} 903256.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.seed"} 1
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.upload_ell"} 20779.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.upload_ell"} 1
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.walk_back"} 2427.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.walk_back"} 1
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="http.decode"} 84.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="http.decode"} 1
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="http.encode"} 1039.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="http.encode"} 1
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="mvcc.read_view"} 2443.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="mvcc.read_view"} 1
# TYPE dgraph_tpu_query_latency_us histogram
dgraph_tpu_query_latency_us_sum{endpoint="query_batch"} 1137061.0
dgraph_tpu_query_latency_us_count{endpoint="query_batch"} 1
"""

AFTER = """\
# TYPE dgraph_tpu_kernel_group_launches_total counter
dgraph_tpu_kernel_group_launches_total{family="shortest"} 3.0
# TYPE dgraph_tpu_kernel_group_queries_total counter
dgraph_tpu_kernel_group_queries_total{family="shortest"} 192.0
# TYPE dgraph_tpu_kernel_hops_run_total counter
dgraph_tpu_kernel_hops_run_total{family="shortest"} 24.0
# TYPE dgraph_tpu_kernel_hops_used_total counter
dgraph_tpu_kernel_hops_used_total{family="shortest"} 15.0
# TYPE dgraph_tpu_jit_compile_us histogram
dgraph_tpu_jit_compile_us_sum{kernel="bfs.ell_step"} 182318.33400000143
dgraph_tpu_jit_compile_us_count{kernel="bfs.ell_step"} 1
# TYPE dgraph_tpu_phase_us histogram
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="admission.admit"} 44.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="admission.admit"} 3
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.build_ell"} 356.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.build_ell"} 1
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.device_wait"} 182721.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.device_wait"} 3
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.fetch"} 46.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.fetch"} 3
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.plan"} 22234.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.plan"} 3
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.render"} 30568.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.render"} 3
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.scan"} 637.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.scan"} 3
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.seed"} 904467.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.seed"} 3
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.upload_ell"} 20779.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.upload_ell"} 1
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="batch.walk_back"} 6908.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="batch.walk_back"} 3
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="http.decode"} 185.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="http.decode"} 3
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="http.encode"} 2513.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="http.encode"} 3
dgraph_tpu_phase_us_sum{endpoint="query_batch",span="mvcc.read_view"} 2550.0
dgraph_tpu_phase_us_count{endpoint="query_batch",span="mvcc.read_view"} 3
# TYPE dgraph_tpu_query_latency_us histogram
dgraph_tpu_query_latency_us_sum{endpoint="query_batch"} 1165325.0
dgraph_tpu_query_latency_us_count{endpoint="query_batch"} 3
"""

# an exposition from before PR 24: no phase, no hop counter
PARENT = """\
# TYPE dgraph_tpu_jit_compile_us histogram
dgraph_tpu_jit_compile_us_sum{kernel="bfs.ell_step"} 201177.0
dgraph_tpu_jit_compile_us_count{kernel="bfs.ell_step"} 1
# TYPE dgraph_tpu_query_latency_us histogram
dgraph_tpu_query_latency_us_sum{endpoint="query_batch"} 1165325.0
dgraph_tpu_query_latency_us_count{endpoint="query_batch"} 3
"""

# metric -> what the recording above holds for it: deltas over the window's
# two batches for the `.batch` ones, the value at the window's end for
# set-up's (microseconds in, ms a batch / % / s out)
EXPECTED = {
    "shell_ms.batch": ((185 - 84) + (44 - 17) + (2513 - 1039)) / 2 / 1e3,
    "read_view_ms.batch": (2550 - 2443) / 2 / 1e3,
    "plan_ms.batch": (22234 - 14608) / 2 / 1e3,
    "launch_prep_ms.batch": (904467 - 903256) / 2 / 1e3,
    "device_wait_ms.batch": (182721 - 182422) / 2 / 1e3,
    "hops_fetch_ms.batch": ((46 - 24) + (637 - 294)) / 2 / 1e3,
    "walk_back_ms.batch": (6908 - 2427) / 2 / 1e3,
    "render_ms.batch": (30568 - 17985) / 2 / 1e3,
    "lane_hops_used.batch": 100.0 * (15 - 5) / (24 - 8),
    # every leaf phase inside `query_latency_us`' clock, which stops before
    # `http.encode`: the ten others, over the latency's own delta
    "phase_cover.batch": 100.0 * 26800 / (1165325 - 1137061),
    "setup_ell_build_s": 356e-6,
    "setup_ell_upload_s": 20779e-6,
    "setup_program_build_s": 182318.33400000143e-6,
}
# on a program from before PR 24 the counters are not there: nothing to
# read, but for the compile histogram, which is older. A phase the program
# does not have is not a phase that took no time (PR 27; it read 0.0).
ON_PARENT = {"setup_program_build_s": 201177.0e-6}


def spec_of(metric: str) -> dict:
    with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as f:
        return json.load(f)


def read(metric: str, before: str, after: str):
    spec = spec_of(metric)
    reader = {"prom_ratio": prom_ratio, "prom_total": prom_total}[
        spec["reader"]]
    ctx = {"prom_before": parse_prom(before), "prom_after": parse_prom(after)}
    return reader.read(ctx, **spec["args"])


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_each_new_metric_resolves_against_the_recording(metric):
    assert read(metric, BEFORE, AFTER) == pytest.approx(EXPECTED[metric],
                                                        rel=1e-9)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_each_new_metric_on_a_program_without_the_counters(metric):
    got = read(metric, PARENT, PARENT.replace(" 3\n", " 5\n").replace(
        "1165325.0", "1965325.0"))
    if metric in ON_PARENT:
        assert got == pytest.approx(ON_PARENT[metric])
    else:
        assert got is None


def test_a_series_that_is_there_and_did_not_move_reads_zero():
    # the numerator exists on the program and nothing was added to it in
    # the window: a reading of 0, not the absence of one
    still = BEFORE.replace(
        'query_latency_us_count{endpoint="query_batch"} 1',
        'query_latency_us_count{endpoint="query_batch"} 2')
    assert read("plan_ms.batch", BEFORE, still) == 0.0
    # and with no request in the window there is nothing to read
    assert read("plan_ms.batch", BEFORE, BEFORE) is None


def test_benchmark_json_lists_them_with_the_cells_they_read():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    for metric in EXPECTED:
        m = entries[metric]
        assert m["source"] == "program_counter"
        if metric.endswith(".batch"):
            assert "follower.shortest-batch" in m["workloads"]
            assert m["moves"] == "completed_qps"
        else:                   # set-up's: every cell reports `setup_s`
            assert "workloads" not in m and m["moves"] == "setup_s"
            assert m["layer"] == "set-up"
    assert {"MVCC read view", "walk-back and render"} <= layers


def test_prom_total_sums_what_matches_and_scales():
    ctx = {"prom_after": parse_prom(AFTER), "prom_before": []}
    both = [{"name": "phase_us_sum", "labels": {"span": "batch.build_ell"}},
            {"name": "phase_us_sum", "labels": {"span": "batch.upload_ell"}}]
    assert prom_total.read(ctx, both, scale=1e-3) == pytest.approx(21.135)
    assert prom_total.read(ctx, [{"name": "phase_us_count"}]) == 3 * 11 + 2
    # a series that is there and reads 0 is a reading; one that is not, none
    zero = parse_prom('dgraph_tpu_x_total{k="v"} 0.0\n')
    assert prom_total.read({"prom_after": zero}, [{"name": "x_total"}]) == 0.0
    assert prom_total.read({"prom_after": zero},
                           [{"name": "x_total", "labels": {"k": "w"}}]) is None


# -- PR 27: the load generator's own cost, and the lane @recurse route -------

RECURSE_BEFORE = """\
dgraph_tpu_kernel_group_launches_total{family="recurse"} 4.0
dgraph_tpu_kernel_group_queries_total{family="recurse"} 64.0
dgraph_tpu_kernel_group_launches_total{family="shortest"} 1.0
dgraph_tpu_kernel_group_queries_total{family="shortest"} 64.0
"""
RECURSE_AFTER = """\
dgraph_tpu_kernel_group_launches_total{family="recurse"} 31.0
dgraph_tpu_kernel_group_queries_total{family="recurse"} 496.0
dgraph_tpu_kernel_group_launches_total{family="shortest"} 1.0
dgraph_tpu_kernel_group_queries_total{family="shortest"} 64.0
"""


def test_recurse_queries_per_launch_reads_its_own_family():
    assert read("recurse_queries_per_launch.batch", RECURSE_BEFORE,
                RECURSE_AFTER) == 16.0
    # a window that launched nothing of the family, a program without it
    assert read("recurse_queries_per_launch.batch", RECURSE_AFTER,
                RECURSE_AFTER) is None
    assert read("recurse_queries_per_launch.batch", PARENT, PARENT) is None


@pytest.mark.parametrize("metric,key", [
    ("client_gap_ms.batch", "client_gap_ms"),
    ("response_mb.batch", "response_mb")])
def test_the_harness_s_own_readings_are_host_values(metric, key):
    from readers import host_value
    spec = spec_of(metric)
    assert spec["reader"] == "host_value"
    assert host_value.read({"host": {key: 0.25}}, **spec["args"]) == 0.25
    assert host_value.read({"host": {}}, **spec["args"]) is None


def test_lane_recurse_roofline_counts_the_knows_edges_of_one_word():
    from readers import lane_hop_roofline as r
    spec = spec_of("lane_recurse_roofline.batch")
    assert spec["reader"] == "lane_hop_roofline"
    # 26 launches of a depth-3 scan in the trace, beside another program
    trace = {"device_plane": True,
             "modules": [["jit_recurse", 0.26, 26, 78.0],
                         ["jit_step", 9.0, 3, 24.0]]}
    sizes = {"nodes": 3_094_529, "edges": 13_700_000, "knows": 1_000_000}
    ctx = {"trace": trace, "root": BENCH, "device": {"kind": "TPU v5 lite"},
           "traffic": {"batch": 16}, "sizes": sizes}
    # 16 queries ride the 32 lanes of one word
    a_hop = 4 * 1_000_000 + 4 * 1 * 1_000_000 + 12 * 1 * 3_094_530
    assert r.hop_bytes(sizes["nodes"], sizes["knows"], 16) == a_hop
    assert r.read(ctx, **spec["args"]) == pytest.approx(
        100.0 * 78 * a_hop / 819e9 / 0.26)
    ctx["trace"] = {"device_plane": True, "modules": [["jit_step", 9, 3, 24]]}
    assert r.read(ctx, **spec["args"]) is None


def test_the_recurse_cell_s_entries_list_it_where_its_metrics_read():
    """The cell failed PR 27's gate and is not in BENCHMARK.json; what a
    later PR adds for it waits in `data/`, and `client_gap_ms.batch`
    stands on the follower cell."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    gap = {m["name"]: m for m in bench["per_layer"]}["client_gap_ms.batch"]
    assert gap["layer"] == "load generator"
    assert "follower.shortest-batch" in gap["workloads"]
    assert gap["moves"] == "completed_qps" and gap["source"] == "host_clock"
    with open(os.path.join(BENCH, "tests", "data",
                           "snb-sf1.recurse-batch.entries.json")) as f:
        entries = json.load(f)
    listed = set(entries["also_in"]) | {m["name"]
                                        for m in entries["per_layer"]}
    assert listed == {
        "completed_qps", "client_gap_ms.batch", "response_mb.batch",
        "recurse_queries_per_launch.batch", "lane_recurse_roofline.batch",
        "server_ms.batch", "shell_ms.batch", "read_view_ms.batch",
        "plan_ms.batch", "compiles_in_window.batch",
        "device_ms_per_query.batch"}
    # (the lane route's phases do not exist on the recurse route yet)
    for metric in listed - {"completed_qps"}:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           metric + ".json")), metric
