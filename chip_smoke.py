#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path starts on the chip.

Drives the system's main path once, through the entry points a user
calls: `python -m dgraph_tpu alpha --p <dir>` answering DQL over HTTP, at
LDBC SNB SF1 size (`models/ldbc.py` sf=3: 3.09 M nodes / 13.7 M edges),
and checks every answer against the repo's plain reference (the numpy
host walk: the same CLI with `JAX_PLATFORMS=cpu DGRAPH_TPU_FUSED=0
--store device_threshold=1000000000`).

One process owns the chip at a time. This supervisor never imports jax;
it runs the phases below one after another, each in its own child:

  1. build      make -C dgraph_tpu/native (libdgtpu.so is git-ignored),
                then require native.HAVE_NATIVE and native.HAVE_EMIT
  2. seed       (JAX held to the CPU) ldbc.generate -> load_into ->
                checkpoint_to, plus the request plan the servers answer
  3. serve      the alpha CLI on the environment's default device:
                /health, IC1/2/5/9/13 twice each, one /query/batch of 64
                @recurse queries (the lane kernel), one commitNow write
                and its read-back, the metrics, SIGTERM, clean exit
  4. reference  the same requests against the numpy-walk server on its
                own copy of the directory; bodies must be equal
  5. kernels    compile the Pallas hop for every bucket width of the
                seeded `knows` relation at W = 128 and the u64 lane
                words for real, compare with the XLA hop / u32 words,
                and hold the MXU edge counters to numpy's integers

Pass means: the serving process itself reports platform "tpu"; every
body equals the reference; no request failed; the device did the work
(route counters > 0, the batch answered by the kernel); every fallback
counter is zero. Anything else — a phase that raises, times out or is
skipped — is a non-zero exit and no result line.

On success stdout carries two JSON lines: the run's full record (sizes,
per-phase seconds, compile and route counters, digests), then, last,
exactly
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}
with the device as the serving process reported it.

`--rehearsal` relaxes ONLY the platform check (and runs the Pallas hop
under the interpreter), so the same phases run end to end on a CPU:
  JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal --sf 0.01
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

DEFAULT_SF = 3.0          # models/ldbc.py sf=3 == official LDBC SNB SF1 size
MIN_SF = 1.0              # never smoke below this outside a rehearsal
DEFAULT_SEED = 9
GLOBAL_DEADLINE_S = 1170  # the contract's 1200 s, minus room to clean up
IC_NAMES = ("IC1", "IC2", "IC5", "IC9", "IC13")
BATCH_QUERIES = 64
BATCH_DEPTH = 3
PALLAS_W = 128            # mask words per row at the 4096-lane serving width
U64_LANES = 256
REF_STORE_FLAG = "device_threshold=1000000000"
# per-query cross-check of the batch against the numpy walk: the batch
# rides the lane kernel on BOTH servers, so a few of its queries are also
# sent one by one through the reference's /query (a route that shares no
# kernel with it)
BATCH_CROSSCHECK = 4

_children: list[subprocess.Popen] = []


def log(*a) -> None:
    print("[chip_smoke]", *a, file=sys.stderr, flush=True)


class SmokeFailure(Exception):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# supervisor plumbing: deadline, children, HTTP

_T0 = time.monotonic()


def remaining() -> float:
    return GLOBAL_DEADLINE_S - (time.monotonic() - _T0)


def _on_alarm(_signum, _frame):
    raise SmokeFailure(f"global deadline of {GLOBAL_DEADLINE_S}s hit")


def spawn(cmd: list, env: dict, log_path: str) -> subprocess.Popen:
    out = open(log_path, "ab")
    proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=out,
                            stderr=subprocess.STDOUT,
                            start_new_session=True)
    out.close()
    _children.append(proc)
    return proc


def kill_all() -> None:
    for proc in _children:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass


def log_tail(path: str, n: int = 30) -> str:
    try:
        with open(path, "rb") as f:
            return b"".join(f.readlines()[-n:]).decode(errors="replace")
    except OSError:
        return ""


def run_child(name: str, cmd: list, env: dict, workdir: str,
              budget_s: float) -> float:
    """Run one phase child to completion; returns its wall seconds. A
    non-zero exit or a timeout fails the smoke (nothing is carried
    past a failed phase)."""
    log_path = os.path.join(workdir, f"{name}.log")
    t0 = time.perf_counter()
    proc = spawn(cmd, env, log_path)
    try:
        rc = proc.wait(timeout=max(min(budget_s, remaining()), 1.0))
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"phase {name} timed out; log tail:\n"
                           + log_tail(log_path))
    if rc != 0:
        raise SmokeFailure(f"phase {name} exited {rc}; log tail:\n"
                           + log_tail(log_path))
    return time.perf_counter() - t0


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method: str, url: str, body: bytes | None = None,
         ctype: str | None = None):
    """(status, body bytes, seconds). The timeout is whatever is left of
    the global deadline: a first request may sit in a long compile."""
    req = urllib.request.Request(url, data=body, method=method)
    if ctype:
        req.add_header("Content-Type", ctype)
    t0 = time.perf_counter()
    try:
        with urllib.request.urlopen(req,
                                    timeout=max(remaining(), 1.0)) as r:
            data, status = r.read(), r.status
    except urllib.error.HTTPError as e:
        data, status = e.read(), e.code
    return status, data, time.perf_counter() - t0


_EXT = re.compile(rb',\s*"extensions":\s*\{')


def strip_extensions(body: bytes) -> bytes:
    """The response body up to its trailing `extensions` object (which
    carries timings and a trace id): what must be equal byte for byte."""
    last = None
    for last in _EXT.finditer(body):
        pass
    return body if last is None else body[:last.start()]


def digest(body: bytes) -> str:
    return hashlib.sha256(strip_extensions(body)).hexdigest()


_PROM = re.compile(r'^(\w+)(?:\{(.*)\})?\s+(\S+)$')
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_prom(text: str) -> list:
    """Prometheus exposition -> [(name, {label: value}, float)]."""
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _PROM.match(line)
        if m is None:
            continue
        try:
            val = float(m.group(3))
        except ValueError:
            continue
        out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")),
                    val))
    return out


def msum(series: list, name: str, **labels) -> float:
    """Sum of the series `dgraph_tpu_<name>` whose labels include
    `labels` (0.0 when the series was never emitted)."""
    full = "dgraph_tpu_" + name
    return sum(v for n, ls, v in series
               if n == full and all(ls.get(k) == x
                                    for k, x in labels.items()))


def cache_entries(path: str) -> int:
    try:
        return sum(len(files) for _d, _s, files in os.walk(path))
    except OSError:
        return 0


# ---------------------------------------------------------------------------
# phase 3/4: one alpha server, driven over real HTTP

class Server:
    """One `python -m dgraph_tpu alpha --p p_dir`, driven over real HTTP:
    start, wait for /health, send the plan's requests, read the metrics,
    stop."""

    def __init__(self, label: str, p_dir: str, env: dict, extra: list,
                 workdir: str):
        self.label = label
        port, grpc_port = free_port(), free_port()
        self.base = f"http://127.0.0.1:{port}"
        self.log_path = os.path.join(workdir, f"alpha_{label}.log")
        self.out: dict = {"label": label, "requests": [],
                          "failed_requests": 0}
        self._t_boot = time.perf_counter()
        self.proc = spawn(
            [sys.executable, "-m", "dgraph_tpu", "alpha", "--p", p_dir,
             "--http_port", str(port), "--grpc_port", str(grpc_port)]
            + extra, env, self.log_path)

    def fail(self, msg: str):
        raise SmokeFailure(f"[{self.label}] {msg}; server log tail:\n"
                           + log_tail(self.log_path))

    def wait_healthy(self, platform: str | None) -> None:
        """Block until /health answers. With `platform`, the device the
        server itself reports is checked before any request is sent: a
        chip that was not found fails here, not twenty minutes on."""
        while True:
            if self.proc.poll() is not None:
                self.fail(f"alpha exited {self.proc.returncode} before "
                          f"/health")
            if remaining() <= 0:
                self.fail("alpha never answered /health")
            try:
                status, _body, _s = http("GET", self.base + "/health")
                if status == 200:
                    break
            except (urllib.error.URLError, ConnectionError, OSError):
                pass
            time.sleep(0.25)
        self.out["boot_s"] = time.perf_counter() - self._t_boot
        dev = device_of(self.metrics())
        log(f"[{self.label}] up after {self.out['boot_s']:.1f}s on {dev}")
        if platform is not None and dev["platform"] != platform:
            self.fail(f"the serving process reports platform "
                      f"{dev['platform']!r}, not {platform!r} (no "
                      f"--rehearsal given)")

    def metrics(self) -> list:
        status, prom, _s = http("GET",
                                self.base + "/debug/prometheus_metrics")
        require(status == 200,
                f"[{self.label}] /debug/prometheus_metrics {status}")
        return parse_prom(prom.decode())

    def send(self, name: str, path: str, body: bytes, ctype: str) -> bytes:
        if self.proc.poll() is not None:
            self.fail(f"alpha died (rc {self.proc.returncode}) before "
                      f"{name}")
        status, data, secs = http("POST", self.base + path, body, ctype)
        self.out["requests"].append(
            {"name": name, "status": status, "seconds": secs,
             "bytes": len(data), "sha256": digest(data)})
        log(f"[{self.label}] {name}: {status} {len(data)}B {secs:.2f}s")
        if status != 200 or data.startswith(b'{"errors"'):
            self.out["failed_requests"] += 1
            log(f"[{self.label}] {name} FAILED: {data[:400]!r}")
        return data

    def drive(self, plan: dict) -> dict:
        out, send = self.out, self.send
        # first call = tablet device-put + compile, second = steady
        for name in IC_NAMES:
            for rep in ("first", "second"):
                send(f"{name}.{rep}", "/query",
                     plan["templates"][name].encode(), "application/dql")
        out["batch_data"] = send(
            "batch", "/query/batch",
            json.dumps({"queries": plan["batch"]}).encode(),
            "application/json")
        if self.label == "reference":
            # the independent route for the batch: a few of its queries
            # one by one through the numpy walk (before the write
            # changes what they reach)
            out["batch_single"] = [
                send(f"batch[{i}].single", "/query",
                     plan["batch"][i].encode(), "application/dql")
                for i in range(min(BATCH_CROSSCHECK, len(plan["batch"])))]
        send("write", "/mutate?commitNow=true",
             plan["write"]["rdf"].encode(), "application/rdf")
        readback = send("readback", "/query",
                        plan["write"]["readback"].encode(),
                        "application/dql")
        out["readback_ok"] = plan["write"]["expect"].encode() in readback
        out["metrics"] = self.metrics()
        status, mem, _s = http("GET", self.base + "/debug/memory")
        require(status == 200, f"[{self.label}] /debug/memory {status}")
        out["memory"] = json.loads(mem)
        return out

    def terminate(self) -> None:
        """SIGTERM: the server drains, writes its final checkpoint and
        must exit by itself (`wait_exit`)."""
        self._t_stop = time.perf_counter()
        self.proc.send_signal(signal.SIGTERM)

    def wait_exit(self) -> None:
        try:
            rc = self.proc.wait(timeout=max(remaining(), 1.0))
        except subprocess.TimeoutExpired:
            self.fail("alpha did not exit after SIGTERM")
        if rc != 0:
            self.fail(f"alpha exited {rc} after SIGTERM (want a clean 0)")
        self.out["shutdown_s"] = time.perf_counter() - self._t_stop
        log(f"[{self.label}] clean exit "
            f"{self.out['shutdown_s']:.1f}s after SIGTERM")

    def kill(self) -> None:
        os.killpg(self.proc.pid, signal.SIGKILL)
        self.proc.wait()


def device_of(series: list) -> dict:
    """The device the serving process itself reported (its build_info
    labels: jax.devices()[0].platform / .device_kind / len)."""
    for n, ls, _v in series:
        if n == "dgraph_tpu_build_info":
            return {"platform": ls.get("backend"),
                    "kind": ls.get("device_kind"),
                    "count": int(ls.get("devices", "0")),
                    "jax": ls.get("jax")}
    raise SmokeFailure("the serving process exported no build_info")


def compile_seconds(series: list) -> float:
    return sum(v for n, _ls, v in series
               if n == "dgraph_tpu_jit_compile_us_sum") / 1e6


# ---------------------------------------------------------------------------
# supervisor

def supervise(args, res: dict) -> None:
    """Run every phase, filling `res` as the facts come in (a failed run
    leaves what it had)."""
    res.update({"sf": args.sf, "seed": args.seed, "reduced": [],
                "rehearsal": bool(args.rehearsal), "seconds": {}})
    if args.sf < DEFAULT_SF:
        res["reduced"].append(
            f"sf {args.sf:g} instead of {DEFAULT_SF:g} (asked for on the "
            f"command line)")
    secs = res["seconds"]
    workdir = args.workdir or tempfile.mkdtemp(prefix="chip_smoke_")
    os.makedirs(workdir, exist_ok=True)
    env = dict(os.environ)
    cpu_env = dict(os.environ, JAX_PLATFORMS="cpu")
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or os.path.join(ROOT, ".jax_cache"))
    res["compile_cache"] = {"dir": cache_dir,
                            "entries_before": cache_entries(cache_dir)}
    me = os.path.abspath(__file__)
    try:
        # -- 1. build: the native library a fresh checkout does not have
        t0 = time.perf_counter()
        run_child("build", ["make", "-C",
                            os.path.join(ROOT, "dgraph_tpu", "native")],
                  env, workdir, 300)
        from dgraph_tpu import native
        require(native.HAVE_NATIVE and native.HAVE_EMIT,
                "libdgtpu.so was built but does not load with the "
                "emitter (native.HAVE_NATIVE / HAVE_EMIT)")
        secs["build"] = time.perf_counter() - t0

        # -- 2. seed: generate, load, checkpoint — on the CPU, off the chip
        seed_dir = os.path.join(workdir, "seed")
        plan_path = os.path.join(workdir, "plan.json")
        run_child("seed", [sys.executable, me, "--phase", "seed",
                           "--sf", repr(args.sf), "--seed",
                           str(args.seed), "--p", seed_dir,
                           "--plan", plan_path],
                  cpu_env, workdir, 900)
        with open(plan_path) as f:
            plan = json.load(f)
        for k in ("generate", "load", "checkpoint", "open"):
            secs[k] = plan["seconds"][k]
        res.update(nodes=plan["nodes"], edges=plan["edges"],
                   persons=plan["persons"],
                   disk_bytes=plan["disk_bytes"])
        log(f"seeded sf={args.sf:g}: {plan['nodes']} nodes / "
            f"{plan['edges']} edges, {plan['disk_bytes'] >> 20} MiB")

        # each server opens its OWN copy: the write and the shutdown
        # checkpoint of one must not reach the other's snapshot
        chip_dir = os.path.join(workdir, "p_chip")
        ref_dir = os.path.join(workdir, "p_ref")
        shutil.copytree(seed_dir, chip_dir)
        shutil.copytree(seed_dir, ref_dir)

        # -- 3. serve on the chip: the environment picks the device
        extra = (["--mesh-devices", str(args.mesh_devices)]
                 if args.mesh_devices else [])
        t0 = time.perf_counter()
        srv = Server("chip", chip_dir, env, extra, workdir)
        srv.wait_healthy(None if args.rehearsal else "tpu")
        chip = srv.drive(plan)
        srv.terminate()
        secs["serve"] = time.perf_counter() - t0

        # -- 4. reference: the numpy walk, same requests, same write. It
        # is held to the CPU and never touches the chip, so it answers
        # while the chip server writes its shutdown checkpoint; it may
        # be killed once its answers are in, the chip server must exit
        # by itself
        ref_env = dict(cpu_env, DGRAPH_TPU_FUSED="0")
        t0 = time.perf_counter()
        rsrv = Server("reference", ref_dir, ref_env,
                      ["--store", REF_STORE_FLAG], workdir)
        rsrv.wait_healthy("cpu")
        ref = rsrv.drive(plan)
        rsrv.kill()
        secs["reference"] = time.perf_counter() - t0
        srv.wait_exit()

        # -- 5. kernels: after the server has exited and freed the chip
        kern_path = os.path.join(workdir, "kernels.json")
        cmd = [sys.executable, me, "--phase", "kernels", "--p", seed_dir,
               "--plan", kern_path, "--seed", str(args.seed)]
        if args.rehearsal:
            cmd.append("--rehearsal")
        secs["kernels"] = run_child("kernels", cmd, env, workdir, 600)
        with open(kern_path) as f:
            res["kernels"] = json.load(f)

        res["compile_cache"]["entries_after"] = cache_entries(cache_dir)
        report(res, chip, ref, args)
        judge(res, args)
    finally:
        kill_all()
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            for name in os.listdir(workdir):
                if name.endswith(".log"):
                    shutil.copy(os.path.join(workdir, name),
                                os.path.join(args.out, name))
        if not args.keep and not args.workdir:
            shutil.rmtree(workdir, ignore_errors=True)


def report(res: dict, chip: dict, ref: dict, args) -> None:
    """Everything the processes reported, into `res` — facts only, so a
    run that fails a condition still leaves its whole record behind."""
    m = chip["metrics"]
    dev = device_of(m)
    res["device"] = {k: dev[k] for k in ("platform", "kind", "count")}
    res.update(platform=dev["platform"], device_kind=dev["kind"],
               n_devices=dev["count"], jax=dev["jax"],
               jaxlib=res["kernels"]["jaxlib"])
    secs = res["seconds"]
    secs["boot_chip"] = chip["boot_s"]
    secs["boot_reference"] = ref["boot_s"]
    secs["shutdown_chip"] = chip["shutdown_s"]
    for r in chip["requests"]:
        secs[r["name"]] = r["seconds"]
    for r in ref["requests"]:
        secs["reference." + r["name"]] = r["seconds"]

    # every response against the reference, request by request (the
    # write's body carries timestamps; its read-back is what is held)
    res["failed_requests"] = (chip["failed_requests"]
                              + ref["failed_requests"])
    ref_by = {r["name"]: r for r in ref["requests"]}
    res["digests"] = {r["name"]: r["sha256"][:16]
                      for r in chip["requests"]}
    res["digests_unequal"] = [
        r["name"] for r in chip["requests"]
        if r["name"] != "write"
        and r["sha256"] != ref_by[r["name"]]["sha256"]]
    res["digests_equal"] = not res["digests_unequal"]
    res["write_read_back"] = bool(chip["readback_ok"]
                                  and ref["readback_ok"])
    # the batch against the route that shares no kernel with it
    batch = json.loads(chip["batch_data"])["data"]
    res["batch_errored"] = [i for i, o in enumerate(batch)
                            if "errors" in o]
    res["batch_crosscheck_unequal"] = [
        i for i, single in enumerate(ref["batch_single"])
        if json.loads(single)["data"] != batch[i]]
    res["batch_crosschecked"] = len(ref["batch_single"])
    res["batch_queries"] = len(batch)

    res["routes"] = {
        "edges_fused": msum(m, "edges_traversed_total", path="fused"),
        "edges_device": msum(m, "edges_traversed_total", path="device"),
        "edges_mesh": msum(m, "edges_traversed_total", path="mesh"),
        "edges_numpy": msum(m, "edges_traversed_total", path="numpy"),
        "fused_route_fused": msum(m, "fused_route_total", route="fused"),
        "fused_route_staged": msum(m, "fused_route_total",
                                   route="staged"),
        "kernel_group_launches_recurse":
            msum(m, "kernel_group_launches_total", family="recurse"),
        "kernel_group_queries_recurse":
            msum(m, "kernel_group_queries_total", family="recurse"),
    }
    res["fallbacks"] = {
        "fused_fallback_total": msum(m, "fused_fallback_total"),
        "fused_route_fallback": msum(m, "fused_route_total",
                                     route="fallback"),
        "pallas_fallback_total": msum(m, "pallas_fallback_total"),
        "pallas_degraded": msum(m, "pallas_degraded"),
        "batch_group_fallback_total":
            msum(m, "batch_group_fallback_total"),
        "oom_events": chip["memory"]["oom"]["events"],
        "oom_degraded": len(chip["memory"]["degraded"]),
    }
    res["compile"] = {
        "jit_compile_total": msum(m, "jit_compile_total"),
        "jit_compile_seconds": compile_seconds(m),
        "jit_cache_hits_total": msum(m, "jit_cache_hits_total"),
        "fused_program_misses_total":
            msum(m, "fused_program_misses_total"),
        "fused_program_hits_total": msum(m, "fused_program_hits_total"),
    }
    devices = chip["memory"].get("devices", [])
    res["hbm"] = {
        "peak_bytes": max((d.get("peak_bytes_in_use") or 0
                           for d in devices), default=0),
        "bytes_in_use": [d.get("bytes_in_use") for d in devices],
        "resident_device_bytes":
            chip["memory"]["budgets"]["device"]["resident_bytes"],
    }
    if args.mesh_devices:
        res["mesh"] = {
            "mesh_route": {r: msum(m, "mesh_route_total", route=r)
                           for r in ("mesh", "fused", "chain", "numpy")},
            "mesh_hop_resharded_total":
                msum(m, "mesh_hop_resharded_total"),
            "bytes_in_use": res["hbm"]["bytes_in_use"]}


def judge(res: dict, args) -> None:
    """Every pass condition, over what `report` recorded."""
    if not args.rehearsal:
        require(res["platform"] == "tpu",
                f"the serving process reports platform "
                f"{res['platform']!r}, not 'tpu' (no --rehearsal given)")
        require(args.sf >= MIN_SF,
                f"sf {args.sf:g} is below {MIN_SF:g}: not a smoke at a "
                f"size LDBC users call real (use --rehearsal)")
        require(res["kernels"]["platform"] == "tpu",
                "the kernel check did not run on the tpu")
    require(res["failed_requests"] == 0,
            f"{res['failed_requests']} failed request(s)")
    require(res["digests_equal"], f"responses differ from the reference: "
                                  f"{res['digests_unequal']}")
    require(res["write_read_back"],
            "the acknowledged write was not read back")
    require(not res["batch_errored"], f"batch queries answered with "
                                      f"errors: {res['batch_errored']}")
    require(not res["batch_crosscheck_unequal"],
            f"batch queries {res['batch_crosscheck_unequal']} differ from "
            f"the numpy walk's answer")

    routes, fallbacks = res["routes"], res["fallbacks"]
    on_device = (routes["edges_mesh"] if args.mesh_devices else
                 routes["edges_fused"] + routes["edges_device"])
    require(on_device > 0, f"no edge was traversed on the device: {routes}")
    require(routes["kernel_group_launches_recurse"] >= 1
            and routes["kernel_group_queries_recurse"]
            == res["batch_queries"],
            f"the batch was not answered by the lane kernel: {routes}")
    bad = {k: v for k, v in fallbacks.items() if v}
    require(not bad, f"fallback counters are not zero: {bad}")
    if args.mesh_devices:
        mesh = res["mesh"]
        require(mesh["mesh_route"]["mesh"] > 0,
                f"no mesh route taken: {mesh}")
        require(mesh["mesh_hop_resharded_total"] == 0,
                f"mesh hops resharded: {mesh}")
        if not args.rehearsal:
            used = mesh["bytes_in_use"][:args.mesh_devices]
            require(len(used) == args.mesh_devices
                    and all((b or 0) > 0 for b in used),
                    f"resident bytes are not on every mesh device: {mesh}")

    k = res["kernels"]
    require(k["pallas"]["equal"] and k["pallas"]["widths"],
            f"the Pallas hop does not equal the XLA hop: {k['pallas']}")
    require(k["u64"]["equal_u32"] and k["counters_exact"],
            f"u64 words / MXU edge counters are not exact: {k}")


# ---------------------------------------------------------------------------
# phase 2 (child, JAX on the CPU): the data and the request plan

def phase_seed(args) -> None:
    require(os.environ.get("JAX_PLATFORMS") == "cpu",
            "the seed phase must not touch the chip")
    import numpy as np

    from dgraph_tpu.models import ldbc
    from dgraph_tpu.server.api import Alpha

    secs = {}
    t0 = time.perf_counter()
    g = ldbc.generate(args.sf, args.seed)
    secs["generate"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    alpha = Alpha()
    ldbc.load_into(alpha, g)
    secs["load"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    alpha.checkpoint_to(args.p)
    secs["checkpoint"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    reopened = Alpha.open(args.p)
    secs["open"] = time.perf_counter() - t0
    require(reopened.mvcc.base.n_nodes == g.n_nodes,
            "the checkpoint does not reopen at the generated size")

    templates = ldbc.ic_templates(g)
    rng = np.random.default_rng(args.seed)
    n_batch = min(BATCH_QUERIES, g.n_persons)
    persons = rng.choice(g.person_uids, n_batch, replace=False)
    batch = ["{ q(func: uid(%s)) @recurse(depth: %d, loop: false) "
             "{ uid knows } }" % (hex(int(p)), BATCH_DEPTH)
             for p in persons]
    # the write: a `knows` edge between two EXISTING persons that are
    # not linked yet, so neither server assigns a uid
    a = int(g.person_uids[1])
    linked = set(g.knows[g.knows[:, 0] == a][:, 1].tolist())
    b = next(int(u) for u in g.person_uids[::-1]
             if int(u) != a and int(u) not in linked)
    write = {
        "rdf": "<%s> <knows> <%s> ." % (hex(a), hex(b)),
        "readback": "{ q(func: uid(%s)) { knows @filter(uid(%s)) "
                    "{ uid } } }" % (hex(a), hex(b)),
        "expect": '"uid":"%s"' % hex(b),
    }
    disk = sum(os.path.getsize(os.path.join(d, f))
               for d, _s, files in os.walk(args.p) for f in files)
    with open(args.plan, "w") as f:
        json.dump({"nodes": g.n_nodes, "edges": g.n_edges,
                   "persons": g.n_persons, "disk_bytes": disk,
                   "seconds": secs, "batch": batch, "write": write,
                   "templates": {k: templates[k] for k in IC_NAMES}}, f)


# ---------------------------------------------------------------------------
# phase 5 (child, on the chip): kernels that compile, integers that are exact

def _host_recurse_edges(indptr, indices, seeds, depth: int) -> int:
    """Vectorised numpy loop=false recurse for one lane: edges traversed
    (the exact integer the device counter is held to)."""
    import numpy as np
    frontier = np.unique(seeds).astype(np.int64)
    seen = np.zeros(indptr.shape[0] - 1, bool)
    seen[frontier] = True
    edges = 0
    for _ in range(depth):
        if not len(frontier):
            break
        starts = indptr[frontier].astype(np.int64)
        deg = indptr[frontier + 1].astype(np.int64) - starts
        total = int(deg.sum())
        edges += total
        pos = (np.repeat(starts, deg) + np.arange(total)
               - np.repeat(np.cumsum(deg) - deg, deg))
        nbrs = np.unique(indices[pos])
        frontier = nbrs[~seen[nbrs]]
        seen[frontier] = True
    return edges


def phase_kernels(args) -> None:
    import jax
    import jax.numpy as jnp
    import jaxlib
    import numpy as np

    from dgraph_tpu.utils.jaxcompat import enable_compile_cache
    enable_compile_cache()

    from dgraph_tpu.ops import bfs
    from dgraph_tpu.ops.pallas_hop import BLOCK_ROWS, bucket_hop_pallas
    from dgraph_tpu.store import checkpoint

    dev0 = jax.devices()[0]
    out = {"platform": dev0.platform, "device_kind": dev0.device_kind,
           "jaxlib": jaxlib.__version__}
    store, _ts = checkpoint.load(args.p)
    rel = store.rel("knows", False)
    g = bfs.build_ell(rel.indptr, rel.indices)
    dev = bfs.device_ell(g)
    n = g.n

    # -- the Pallas hop, called directly, at the serving width ------------
    # interpret mode only because the rehearsal asked for it: there is
    # no Mosaic off the chip
    interpret = bool(args.rehearsal)
    frontier = jax.random.bits(jax.random.key(args.seed),
                               (n + 1, PALLAS_W), jnp.uint32)
    frontier = frontier.at[n].set(0)             # the sentinel row
    blocks = [(f"ell{int(e.shape[1])}", e) for kind, e, _r in dev.parts
              if kind == "ell"]
    if dev.tiles is not None and dev.seg_rows:
        blocks.append((f"tiles{int(dev.tiles.shape[1])}", dev.tiles))
    widths, equal, t_pallas = [], True, time.perf_counter()
    for name, e in blocks:
        rows = int(e.shape[0])
        padded = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
        nbr = jnp.concatenate(
            [e, jnp.full((padded - rows, e.shape[1]), n, jnp.int32)])
        got = bucket_hop_pallas(nbr, frontier,
                                interpret=interpret)[:rows]
        want = jax.jit(bfs._chain_or, static_argnums=2)(
            frontier, e, jnp.uint32)
        same = bool(jnp.array_equal(got, want))
        widths.append({"block": name, "rows": rows, "equal": same})
        equal = equal and same
    out["pallas"] = {"W": PALLAS_W, "interpret": interpret,
                     "widths": widths, "equal": equal,
                     "seconds": time.perf_counter() - t_pallas}
    del frontier

    # -- u64 lane words + the MXU edge counters ---------------------------
    rng = np.random.default_rng(args.seed)
    persons = np.nonzero(np.diff(rel.indptr) > 0)[0]
    seeds = [rng.choice(persons, 2) for _ in range(U64_LANES)]
    t_u64 = time.perf_counter()
    m32 = bfs.pack_seed_masks(g, seeds, word_bits=32)
    fn32 = bfs.make_ell_recurse(dev, g.outdeg, n, m32.shape[1])
    _l32, seen32, edges32 = fn32(jax.device_put(m32), BATCH_DEPTH)
    seen32, edges32 = np.asarray(seen32), np.asarray(edges32)
    with jax.enable_x64(True):
        m64 = bfs.pack_seed_masks(g, seeds, word_bits=64)
        fn64 = bfs.make_ell_recurse(dev, g.outdeg, n, m64.shape[1],
                                    word_bits=64)
        _l64, seen64, edges64 = fn64(jax.device_put(m64), BATCH_DEPTH)
        seen64, edges64 = np.asarray(seen64), np.asarray(edges64)
    # lane q sits in bit q%64 of word q//64: on a little-endian host the
    # u64 mask viewed as u32 words IS the u32 mask
    as_u32 = np.ascontiguousarray(seen64).view(np.uint32)
    out["u64"] = {
        "lanes": U64_LANES,
        "equal_u32": bool(np.array_equal(as_u32, seen32)
                          and np.array_equal(edges64, edges32)),
        "seconds": time.perf_counter() - t_u64}
    exact = np.array([_host_recurse_edges(rel.indptr, rel.indices,
                                          g_seeds, BATCH_DEPTH)
                      for g_seeds in seeds], np.int64)
    out["counters_exact"] = bool(np.array_equal(edges32, exact))
    out["max_outdeg"] = int(g.outdeg.max())
    out["max_lane_edges"] = int(exact.max())
    with open(args.plan, "w") as f:
        json.dump(out, f)
    require(equal and out["u64"]["equal_u32"] and out["counters_exact"],
            f"kernel check failed: {out}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=DEFAULT_SF,
                    help="models/ldbc.py scale factor (3 = LDBC SNB SF1 "
                         "size; never below 1 outside --rehearsal)")
    ap.add_argument("--seed", type=int, default=DEFAULT_SEED)
    ap.add_argument("--rehearsal", action="store_true",
                    help="relax ONLY the platform check (and interpret "
                         "the Pallas hop): the same phases on a CPU")
    ap.add_argument("--mesh-devices", type=int, default=0,
                    dest="mesh_devices",
                    help="start the chip server on --mesh-devices N and "
                         "hold it to the mesh counters (four-chip host; "
                         "frontiers reach the mesh route from sf 1 up)")
    ap.add_argument("--workdir", default=None,
                    help="where the posting dirs go (default: a temp "
                         "dir, removed afterwards)")
    ap.add_argument("--out", default=None,
                    help="copy the phase logs and the result JSON here "
                         "(a failed run leaves chip_smoke_failed.json: "
                         "its record so far, never printed)")
    ap.add_argument("--keep", action="store_true")
    ap.add_argument("--phase", choices=("seed", "kernels"), default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--p", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--plan", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.phase == "seed":
        phase_seed(args)
        return 0
    if args.phase == "kernels":
        phase_kernels(args)
        return 0

    signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(GLOBAL_DEADLINE_S)
    res: dict = {}
    try:
        supervise(args, res)
    except SmokeFailure as e:
        log("FAILED:", e)
        log("supervisor imported jax:", "jax" in sys.modules)
        if args.out:
            # the failed run's record, beside the logs — never on stdout:
            # a failure prints no result line
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, "chip_smoke_failed.json"),
                      "w") as f:
                json.dump({"ok": False, "error": str(e)[:2000], **res}, f)
        return 1
    finally:
        signal.alarm(0)
        kill_all()
    assert "jax" not in sys.modules, "the supervisor must stay off jax"
    res["seconds"]["total"] = time.monotonic() - _T0
    device = res.pop("device")
    record = json.dumps({"ok": True, "device": device, **res})
    if args.out:
        with open(os.path.join(args.out, "chip_smoke.json"), "w") as f:
            f.write(record + "\n")
    # the run's full record first, then — last on stdout — the verdict in
    # exactly the shape the driver parses: "ok" and "device", nothing else
    print(record, flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
