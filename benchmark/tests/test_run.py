"""run.py end to end on the CPU at tiny size (`--rehearsal`): each cell,
the contract's last line, and `correct` false where an answer is altered.

`snb-sf1.ic-open` is no cell of BENCHMARK.json yet (PERF.md, Open
questions): it runs here in a scratch copy of the benchmark to which
`data/snb-sf1.ic-open.entries.json` adds it, by entries alone: what a
later PR that brings a cell does."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT

SCALE = {"snb-sf1.ic-open": '{"sf": 0.02}',
         "follower.shortest-batch": '{"nodes": 20000}'}
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
ADDED = os.path.join(HERE, "data", "snb-sf1.ic-open.entries.json")


@pytest.fixture(scope="module")
def roots(tmp_path_factory):
    """Where each cell's BENCHMARK.json lives: the repo, or the scratch
    copy with one more cell."""
    scratch = tmp_path_factory.mktemp("one_more_cell")
    shutil.copytree(BENCH, scratch / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(os.path.join(ROOT, "dgraph_tpu"), scratch / "dgraph_tpu")
    bench = bench_of(ROOT)
    with open(ADDED) as f:
        for group, entries in json.load(f).items():
            bench[group] += entries
    with open(scratch / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return {"snb-sf1.ic-open": str(scratch),
            "follower.shortest-batch": ROOT}


def run(root, workload, *extra, seed=2147483900, seconds=4, trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace), "--rehearsal", "--scale",
         SCALE[workload], *extra], cwd=root, env=env, capture_output=True,
        text=True, timeout=900)
    return proc


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stderr[-2000:] + proc.stdout[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_of(root) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", list(SCALE))
def test_each_cell_runs_end_to_end(roots, workload):
    out = last_line(run(roots[workload], workload))
    assert KEYS <= set(out) and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in bench_of(roots[workload])["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"      # a rehearsal says so


def test_a_traced_run_reports_the_per_layer_metrics():
    out = last_line(run(ROOT, "follower.shortest-batch", trace=1))
    names = {m["name"] for m in bench_of(ROOT)["per_layer"]
             if "follower.shortest-batch" in m.get(
                 "workloads", ["follower.shortest-batch"])}
    # the device readers find no device plane on a CPU and return nothing
    assert set(out["metrics"]) <= names
    assert {"server_ms.batch", "lane_queries_per_launch.batch",
            "setup_data_s"} <= set(out["metrics"])
    assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
    assert len(out["breakdown"]["device_ops"]) <= 10


def test_an_altered_answer_reads_correct_false():
    out = last_line(run(ROOT, "follower.shortest-batch", "--break-answer"))
    assert out["correct"] is False and out["failed"] >= 1


def test_the_control_is_seen_to_fail_on_a_run_s_sample(roots):
    out = last_line(run(roots["snb-sf1.ic-open"], "snb-sf1.ic-open",
                        "--control", seconds=8))
    assert out["correct"] is True
    assert out["checks"]["control_mismatches"] > 0


def test_alone_in_a_directory_there_is_no_result(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "follower.shortest-batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
