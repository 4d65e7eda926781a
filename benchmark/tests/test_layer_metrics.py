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
# read, but for the compile histogram, which is older. The phase metrics
# go through `prom_ratio` as it stands: a numerator that is absent reads 0
# over a denominator that is there.
ON_PARENT = {"lane_hops_used.batch": None, "setup_ell_build_s": None,
             "setup_ell_upload_s": None,
             "setup_program_build_s": 201177.0e-6}


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
        assert got == (ON_PARENT[metric] if ON_PARENT[metric] is None
                       else pytest.approx(ON_PARENT[metric]))
    else:
        assert got == 0.0


def test_benchmark_json_lists_them_with_the_cells_they_read():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    for metric in EXPECTED:
        m = entries[metric]
        assert m["source"] == "program_counter"
        if metric.endswith(".batch"):
            assert m["workloads"] == ["follower.shortest-batch"]
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
