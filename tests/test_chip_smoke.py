"""chip_smoke.py in rehearsal: the same phases the chip runs, on the CPU.

The smoke is the first command sent to the chip on every later PR, so
what it does off the chip is pinned here: the rehearsal runs every phase
end to end at the smallest size, and the ways it must FAIL — a serving
process that is not on a tpu outside `--rehearsal`, a tree without the
repo — exit non-zero, print no result line, and leave the supervisor
off jax.
"""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")

sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def _env(tmp_path) -> dict:
    # the children get a plain one-device CPU, not the suite's 8-device
    # sanitizer harness; the compile cache stays out of the checkout
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "DGRAPH_TPU_LOCK_SANITIZER",
                        "DGRAPH_TPU_RACE_SANITIZER")}
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    return env


def _run(args, tmp_path, script=SMOKE, cwd=ROOT):
    return subprocess.run([sys.executable, script] + args, cwd=cwd,
                          env=_env(tmp_path), capture_output=True,
                          text=True, timeout=600)


def test_rehearsal_runs_every_phase(tmp_path):
    proc = _run(["--rehearsal", "--sf", "0.01"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    # last on stdout: the verdict, with exactly the keys the driver parses
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    # before it: the run's full record
    res = json.loads(lines[-2])
    assert res["ok"] is True and res["rehearsal"] is True
    assert res["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert res["reduced"], "a cut of sf must be listed under reduced"
    assert res["digests_equal"] and res["write_read_back"]
    assert res["failed_requests"] == 0
    assert res["batch_crosschecked"] >= 1
    # the device routes did the work and nothing fell back
    assert res["routes"]["edges_fused"] > 0
    assert res["routes"]["kernel_group_launches_recurse"] >= 1
    assert res["routes"]["kernel_group_queries_recurse"] == 64
    assert not any(res["fallbacks"].values()), res["fallbacks"]
    # every phase reported its seconds; first and second requests apart
    for key in ("build", "generate", "load", "checkpoint", "open",
                "IC1.first", "IC1.second", "IC13.second", "batch",
                "shutdown_chip", "kernels", "total"):
        assert key in res["seconds"], key
    assert res["compile"]["jit_compile_total"] >= 1
    assert res["compile_cache"]["dir"] == str(tmp_path / "jax_cache")
    k = res["kernels"]
    assert k["pallas"]["equal"] and k["pallas"]["interpret"]
    assert k["pallas"]["W"] == 128 and len(k["pallas"]["widths"]) >= 2
    assert k["u64"]["equal_u32"] and k["counters_exact"]


def test_not_a_tpu_fails_without_rehearsal(tmp_path):
    """Outside --rehearsal a serving process on any platform but tpu is
    a failure: non-zero exit, no result line, supervisor off jax — and
    it fails at boot, before a request is sent."""
    out = tmp_path / "out"
    proc = _run(["--sf", "0.01", "--out", str(out)], tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "reports platform 'cpu', not 'tpu'" in proc.stderr
    assert "supervisor imported jax: False" in proc.stderr
    assert "IC1.first" not in proc.stderr
    # the failed run's record and logs land beside each other, off stdout
    failed = json.loads((out / "chip_smoke_failed.json").read_text())
    assert failed["ok"] is False and "not 'tpu'" in failed["error"]
    assert failed["nodes"] > 0 and (out / "alpha_chip.log").exists()


def test_fails_in_a_tree_without_the_repo(tmp_path):
    lone = tmp_path / "lone"
    lone.mkdir()
    shutil.copy(SMOKE, lone / "chip_smoke.py")
    proc = _run(["--rehearsal", "--sf", "0.01"], tmp_path,
                script=str(lone / "chip_smoke.py"), cwd=str(lone))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_strip_extensions_keeps_the_answer_bytes():
    body = (b'{"data":{"q":[{"uid":"0x1"}]},"extensions":'
            b'{"server_latency":{"total_us":12},"trace_id":"ab"}}')
    assert chip_smoke.strip_extensions(body) == \
        b'{"data":{"q":[{"uid":"0x1"}]}'
    batch = json.dumps({"data": [{"q": []}],
                        "extensions": {"trace_id": "x"}}).encode()
    assert chip_smoke.strip_extensions(batch) == b'{"data": [{"q": []}]'
    assert chip_smoke.digest(body) != chip_smoke.digest(batch)
    assert chip_smoke.strip_extensions(b'{"data":{}}') == b'{"data":{}}'


def test_prometheus_parsing_and_label_sums():
    text = ('# TYPE dgraph_tpu_edges_traversed_total counter\n'
            'dgraph_tpu_edges_traversed_total{path="fused"} 12.0\n'
            'dgraph_tpu_edges_traversed_total{path="numpy"} 3.0\n'
            'dgraph_tpu_build_info{backend="tpu",device_kind="TPU v5 lite"'
            ',devices="1",jax="0.9.0",version="0.1.0"} 1.0\n'
            'dgraph_tpu_pallas_degraded 0.0\n')
    series = chip_smoke.parse_prom(text)
    assert chip_smoke.msum(series, "edges_traversed_total") == 15.0
    assert chip_smoke.msum(series, "edges_traversed_total",
                           path="fused") == 12.0
    assert chip_smoke.msum(series, "fused_fallback_total") == 0.0
    assert chip_smoke.device_of(series) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
        "jax": "0.9.0"}
