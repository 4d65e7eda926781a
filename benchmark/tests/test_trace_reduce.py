"""The reduction from a profiler trace to busy time, gaps and op sums:
on hand-made planes, and on a trace recorded on the chip (cut small)."""

import gzip
import json
import os

import pytest

from conftest import HERE
from harness import trace_reduce as tr

MS = 1e6   # ns


def planes(dev_events, host_events=()):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit_f", 0.0, 100 * MS,
                                                False)]},
            {"name": tr.OPS_LINE, "events": list(dev_events)}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": list(host_events)}]},
    ]


def test_busy_is_the_union_of_the_operation_intervals():
    out = tr.reduce_planes(planes([
        ("fusion.1", 10 * MS, 20 * MS, False),     # 10..30
        ("fusion.2", 25 * MS, 10 * MS, False),     # 25..35 overlaps
        ("fusion.1", 60 * MS, 5 * MS, False)],     # 60..65
        [("serve", 0.0, 100 * MS, False)]))
    assert out["device_plane"] is True
    assert out["busy_s"] == pytest.approx(0.030)
    assert out["window_s"] == pytest.approx(0.100)
    ops = dict(out["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.025)
    assert ops["fusion.2"] == pytest.approx(0.010)


def test_only_the_ops_line_counts_where_there_is_one():
    # the module's span covers the whole window: counting it would read
    # the device as never idle
    out = tr.reduce_planes(planes([("fusion.1", 0.0, 1 * MS, False)]))
    assert out["busy_s"] == pytest.approx(0.001)


def test_gaps_are_named_by_the_innermost_host_event_that_covers_them():
    out = tr.reduce_planes(planes(
        [("a", 0.0, 10 * MS, False), ("b", 50 * MS, 10 * MS, False)],
        [("thread.run", 0.0, 100 * MS, False),
         ("render", 12 * MS, 36 * MS, False),
         ("tiny", 20 * MS, 1 * MS, False)]))
    gaps = dict(out["idle_gaps"])
    assert gaps["render"] == pytest.approx(0.040)          # 10..50
    assert gaps["thread.run"] == pytest.approx(0.040)      # 60..100
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])


def test_a_trace_without_a_device_plane_says_so():
    out = tr.reduce_planes([{"name": "/host:CPU", "lines": [
        {"name": "tf_XLACpu", "events": [("dot", 0.0, 5 * MS, True),
                                         ("$py", 0.0, 9 * MS, False)]}]}])
    assert out["device_plane"] is False
    assert out["busy_s"] == pytest.approx(0.005)


def test_the_window_ends_where_the_profiler_s_own_stop_begins():
    out = tr.reduce_planes(planes(
        [("a", 0.0, 10 * MS, False), ("b", 50 * MS, 10 * MS, False)],
        [("serve", 0.0, 100 * MS, False),
         ("$profiler.py:213 stop_trace", 70 * MS, 30 * MS, False)]))
    assert out["window_s"] == pytest.approx(0.070)
    assert out["device_span_s"] == pytest.approx(0.060)
    assert sum(dict(out["idle_gaps"]).values()) == pytest.approx(0.050)


def test_a_program_s_loop_turns_are_counted_inside_its_own_spans():
    turn = [(n, (2 + i) * MS, 1 * MS, False) for i, n in enumerate("abcd")]
    turn += [("twice", 6 * MS, 0.1 * MS, False),
             ("twice", 6.5 * MS, 0.1 * MS, False)]
    evs = [("setup", 1 * MS, 1 * MS, False)]
    for k in range(6):                       # one program run, six turns
        evs += [(n, s + 5 * k * MS, d, h) for n, s, d, h in turn]
    evs.append(("elsewhere", 150 * MS, 1 * MS, False))   # outside jit_f
    out = tr.reduce_planes(planes(evs))
    assert out["modules"] == [["jit_f", pytest.approx(0.100), 1,
                               pytest.approx(6.0)]]
    # a trace that ends after the sixth turn's second operation
    cut = [e for e in evs if e[1] < 29 * MS]
    assert tr.reduce_planes(planes(cut))["modules"][0][3] == \
        pytest.approx(5.5)
    # a program without a loop has no turns to count
    out = tr.reduce_planes(planes([("setup", 1 * MS, 1 * MS, False)]))
    assert out["modules"][0][3] == 0


def test_an_empty_trace_reduces_to_nothing():
    assert tr.reduce_planes([])["busy_s"] == 0.0


RECORDED = os.path.join(HERE, "data", "follower.shortest-batch.trace.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside the tests")
def test_the_recorded_chip_trace_reduces_to_what_was_read_on_the_chip():
    with gzip.open(RECORDED, "rt") as f:
        recorded = json.load(f)
    with open(RECORDED.replace(".trace.json.gz", ".expected.json")) as f:
        want = json.load(f)
    out = tr.reduce_planes([
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [tuple(e) for e in ln["events"]]}
            for ln in p["lines"]]} for p in recorded])
    assert out["device_plane"] is True
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert [n for n, _s in out["device_ops"]] == \
        [n for n, _s in want["device_ops"]]
    for (_n, got), (_m, exp) in zip(out["device_ops"], want["device_ops"]):
        assert got == pytest.approx(exp, rel=1e-9)
    # the lane step is one compiled program, told apart by its name
    assert [m[0] for m in out["modules"]] == ["jit_step"]
    assert out["modules"][0][1] == pytest.approx(want["modules"][0][1])
    # 8 hops of one launch and 5.68 of the one the trace began inside
    assert out["modules"][0][2] == 2
    assert out["modules"][0][3] == pytest.approx(want["modules"][0][3])
    assert 13.6 < out["modules"][0][3] < 13.7
    assert out["busy_s"] < out["device_span_s"] < out["window_s"]
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)


def test_lane_hop_roofline_counts_bytes_from_shapes():
    from readers import lane_hop_roofline as r
    assert r.hop_bytes(nodes=9, edges=100, lanes=64) == \
        4 * 100 + 4 * 2 * 100 + 3 * 4 * 2 * 10
    trace = {"device_plane": True, "modules": [["jit_step", 2.0, 1, 4],
                                               ["jit_other", 9.0, 3, 99]]}
    ctx = {"trace": trace, "root": os.path.dirname(HERE),
           "device": {"kind": "TPU v5 lite"}, "traffic": {"batch": 64},
           "sizes": {"nodes": 9, "edges": 100}}
    want = 100.0 * 4 * r.hop_bytes(9, 100, 64) / 819e9 / 2.0
    assert r.read(ctx) == pytest.approx(want)
    ctx["device"] = {"kind": "TPU v9"}
    with pytest.raises(KeyError):
        r.read(ctx)
    ctx["trace"] = {"device_plane": False}
    assert r.read(ctx) is None


def test_device_time_a_query_is_a_share_over_a_rate():
    from readers import trace_busy_per_query as r
    ctx = {"trace": {"device_plane": True, "busy_s": 3.0,
                     "device_span_s": 4.0}, "completed_qps": 25.0}
    assert r.read(ctx) == pytest.approx(1e3 * 0.75 / 25.0)
    assert r.read({**ctx, "completed_qps": 0.0}) is None
    assert r.read({**ctx, "trace": {"device_plane": False}}) is None
