#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of BENCHMARK.json.

  python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This parent never imports jax. It makes the cell's data from the seed,
builds the checkpoint in a child held to the CPU, starts
`python -m dgraph_tpu alpha --p <dir>` as the one process that holds the
chip, refuses to go on unless that process itself reports platform "tpu"
(`--rehearsal` relaxes only this, for CPU rehearsals at tiny size), warms
the cell's own shapes, measures for `--seconds`, kills the server, checks
a seeded sample of the window's answers against the plain reference, and
prints the contract's JSON object as the last line of stdout.

Everything that belongs to one cell is found by name: the configuration
(`configs/<config>.json` -> `generators/`, `loaders/`, `references/`), the
traffic mix (`traffic/<traffic>.json` -> `traffic_kinds/`), each per-layer
metric (`layer_metrics/<metric>.json` -> `readers/`).
"""

from __future__ import annotations

import time

_T_START = time.monotonic()          # set-up runs from here

import argparse                      # noqa: E402
import importlib                     # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import signal                        # noqa: E402
import subprocess                    # noqa: E402
import sys                           # noqa: E402
import threading                     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import build_checkpoint               # noqa: E402
from harness import loadgen, server as srv, stats   # noqa: E402

CHECK_SAMPLE = 128        # answers compared with the reference, a run
BOOT_DEADLINE_S = 600.0
OPEN_LOOP_WORKERS = 32    # threads that send an open loop's requests
OPEN_LOOP_WARM_CLIENTS = 4


def say(*a) -> None:
    print(f"[bench {time.monotonic() - _T_START:6.1f}s]", *a, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json has no {what} named {name!r}")


class Cell:
    """One workload of BENCHMARK.json with its files resolved."""

    def __init__(self, bench: dict, name: str):
        self.bench = bench
        self.workload = find(bench["workloads"], name, "workload")
        entry = find(bench["configs"], self.workload["config"], "config")
        self.config = load_json(ROOT, entry["file"])
        self.traffic = load_json(HERE, "traffic",
                                 self.workload["traffic"] + ".json")
        self.name = name

    def metrics(self, group: str) -> list:
        """The cell's metrics of `end_to_end` or `per_layer`."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]


# ---------------------------------------------------------------------------
# set-up

def child_env(jax_cpu: bool) -> dict:
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (ROOT, env.get("PYTHONPATH")) if p)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(HERE, ".cache", "jax"))
    if jax_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def run_child(cmd: list, env: dict, log_path: str, budget_s: float) -> str:
    """Run a child to its end; its stdout. A failure raises with the log."""
    with open(log_path, "ab") as err:
        proc = subprocess.Popen(cmd, cwd=ROOT, env=env,
                                stdout=subprocess.PIPE, stderr=err,
                                start_new_session=True)
        try:
            out, _ = proc.communicate(timeout=budget_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise srv.HarnessError(f"{cmd[1:3]} timed out; log:\n"
                                   + srv.log_tail(log_path))
    if proc.returncode != 0:
        raise srv.HarnessError(f"{cmd[1:3]} exited {proc.returncode}; "
                               f"log:\n" + srv.log_tail(log_path))
    return out.decode()


def ensure_native(workdir: str) -> None:
    """`libdgtpu.so` is git-ignored: a fresh checkout builds it. Without
    it the served path quietly renders and decodes in Python."""
    native = os.path.join(ROOT, "dgraph_tpu", "native")
    if not os.path.exists(os.path.join(native, "libdgtpu.so")):
        run_child(["make", "-C", native], dict(os.environ),
                  os.path.join(workdir, "make.log"), 300)


def set_up(cell: Cell, args, workdir: str, secs: dict):
    """Data from the seed, checkpoint, server up on the right device,
    shapes warm. Returns (data, mix, server, device)."""
    cfg = cell.config
    gen = importlib.import_module(f"generators.{cfg['generator']}")
    gparams = dict(cfg["generator_params"])
    if args.scale:
        if not args.rehearsal:
            raise SystemExit("--scale is for --rehearsal only")
        gparams.update(json.loads(args.scale))
    t0 = time.perf_counter()
    data = gen.generate(gparams, args.seed)
    say(f"generated {cfg['generator']} seed {args.seed}: "
        f"{gen.sizes(data)}")
    arrays = os.path.join(workdir, "arrays")
    build_checkpoint.save_arrays(data, arrays)
    ensure_native(workdir)
    p_dir = os.path.join(workdir, "p")
    out = run_child(
        [sys.executable, os.path.join(HERE, "build_checkpoint.py"),
         "--generator", cfg["generator"], "--arrays", arrays, "--p", p_dir],
        child_env(jax_cpu=True), os.path.join(workdir, "build.log"), 900)
    say("checkpoint built:", out.strip().splitlines()[-1])
    shutil.rmtree(arrays, ignore_errors=True)
    secs["setup_data_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    server = srv.Server(ROOT, p_dir, child_env(jax_cpu=False),
                        os.path.join(workdir, "alpha.log"),
                        tuple(cfg.get("alpha_flags", ())))
    try:
        server.wait_healthy(BOOT_DEADLINE_S)
        device = srv.device_of(server.metrics())
        say("serving process reports", device)
        if not args.rehearsal:
            if device["platform"] != "tpu":
                server.fail(f"the serving process reports platform "
                            f"{device['platform']!r}, not 'tpu'")
            if device["count"] < cell.workload["chips"]:
                server.fail(f"{device['count']} chips, the cell asks for "
                            f"{cell.workload['chips']}")
        secs["setup_boot_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        kind = importlib.import_module(
            f"traffic_kinds.{cell.traffic['kind']}")
        mix = kind.make(data, cell.traffic, args.seed)
        warm(server, mix, cell.traffic, args.seconds)
        secs["setup_warm_s"] = time.perf_counter() - t0
    except BaseException:
        server.kill()
        raise
    return data, mix, server, device


def window_count(traffic: dict, seconds: float) -> int:
    """How many requests an open loop offers in `seconds`."""
    return max(int(round(float(traffic["rate_qps"]) * seconds)), 1)


def warm(server, mix, traffic: dict, seconds: float) -> None:
    """The cell's own shapes, through the window's own path, on requests
    that the window does not send: one pass of the mix's warm-up, a draw
    of its own. What the window still has to build, it builds inside the
    window, and `compiles_in_window.*` counts it."""
    reqs = mix.warm_requests(window_count(traffic, seconds)
                             if traffic["loop"] == "open" else 0)
    clients = (OPEN_LOOP_WARM_CLIENTS if traffic["loop"] == "open"
               else int(traffic["clients"]))
    before = compiles(server.metrics())
    streams = [iter(reqs[k::clients]) for k in range(clients)]
    _t, recs = loadgen.closed_loop(server, streams, seconds=3600.0)
    bad = [r for r in recs if not r["ok"]]
    if bad:
        server.fail(f"{len(bad)} warm-up requests failed, the first "
                    f"with status {bad[0]['status']}")
    say(f"warm-up: {len(recs)} requests, "
        f"{compiles(server.metrics()) - before:g} programs built")


def built_in_window(before: list, after: list) -> dict:
    """Which programs the window built, by the program's own labels."""
    names = ("dgraph_tpu_jit_compile_total",
             "dgraph_tpu_fused_program_misses_total")
    was = {(n, tuple(sorted(ls.items()))): v for n, ls, v in before
           if n in names}
    out = {}
    for n, ls, v in after:
        if n in names:
            d = v - was.get((n, tuple(sorted(ls.items()))), 0.0)
            if d > 0:
                out[",".join(ls.values()) or n] = d
    return out


def compiles(series: list) -> float:
    return (srv.msum(series, "jit_compile_total")
            + srv.msum(series, "fused_program_misses_total"))


# ---------------------------------------------------------------------------
# the window

def window(cell: Cell, args, mix, server, seconds: float, rate=None):
    """Drive the cell's traffic for `seconds`. Returns (t_open, records)."""
    traffic = cell.traffic
    if traffic["loop"] == "open":
        import numpy as np
        count = window_count(traffic if rate is None
                             else {"rate_qps": rate}, seconds)
        rate = float(rate if rate is not None else traffic["rate_qps"])
        # every run offers the same set of gaps, in an order of its own
        rng = np.random.default_rng([args.seed, 7])
        gaps = loadgen.exponential_gaps(count, rate, rng)
        offsets = (np.cumsum(gaps) - gaps[0]).tolist()
        reqs = mix.requests(count, stream=0)
        return loadgen.open_loop(server, reqs, offsets, OPEN_LOOP_WORKERS)
    clients = int(traffic["clients"])

    def stream(k):
        chunk = 0
        while True:
            yield from mix.requests(16, stream=100 + 1000 * chunk + k)
            chunk += 1

    return loadgen.closed_loop(server, [stream(k) for k in range(clients)],
                               seconds)


def traced(server, trace_dir: str, start_s: float, length_s: float,
           t_open_hint: float) -> threading.Thread:
    """Start the server's profiler `start_s` into the window and stop it
    `length_s` later, from a thread of its own."""
    def go():
        time.sleep(max(t_open_hint + start_s - time.perf_counter(), 0))
        server.profile("start", trace_dir)
        time.sleep(length_s)
        server.profile("stop", trace_dir)
    th = threading.Thread(target=go, daemon=True)
    th.start()
    return th


def reduce_trace(trace_dir: str, workdir: str, dump_to=None):
    """The profiler's `.xplane.pb` through `harness/trace_reduce.py`, in a
    child held to the CPU; `dump_to` also keeps the trace, cut small."""
    paths = [os.path.join(d, f) for d, _s, files in os.walk(trace_dir)
             for f in files if f.endswith(".xplane.pb")]
    if not paths:
        return None
    cmd = [sys.executable, os.path.join(HERE, "harness", "trace_reduce.py"),
           paths[0]]
    if dump_to:
        os.makedirs(os.path.dirname(dump_to) or ".", exist_ok=True)
        cmd.append(dump_to)
    out = run_child(cmd, child_env(jax_cpu=True),
                    os.path.join(workdir, "trace_reduce.log"), 300)
    return json.loads(out.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# correctness

def check(cell: Cell, args, data, mix, records: list) -> dict:
    """Compare a seeded sample of the answers the window produced with the
    plain reference. Every number compared is returned beside its limit."""
    import numpy as np
    cfg = cell.config
    ref_mod = importlib.import_module(f"references.{cfg['reference']}")
    ref = ref_mod.make(data, cfg)
    answers = [(meta, got, rec) for rec in records if rec["ok"]
               for meta, got in mix.split(rec["req"], rec["data"])]
    rng = np.random.default_rng([args.seed, 11])
    n = min(CHECK_SAMPLE, len(answers))
    pick = set(rng.choice(len(answers), n, replace=False).tolist()) \
        if n else set()
    # the longest answer of the window is always in the sample
    if answers:
        pick.add(max(range(len(answers)),
                     key=lambda i: answers[i][2]["bytes"]))
    # and every kind of request the mix sent, at least once
    seen = set()
    for i, (meta, _g, _r) in enumerate(answers):
        k = meta.get("template")
        if k is not None and k not in seen:
            seen.add(k)
            pick.add(i)
    wrong = []
    t0 = time.perf_counter()
    for i in sorted(pick):
        meta, got, rec = answers[i]
        if args.break_answer and i == min(pick):
            got = perturb(got)
        ok, why = ref.check(meta, got)
        if not ok:
            wrong.append((meta, why))
            rec["ok"] = False
    out = {"compared": len(pick), "mismatches": len(wrong),
           "reference_s": time.perf_counter() - t0}
    for meta, why in wrong[:5]:
        say("WRONG ANSWER:", json.dumps(meta), "-", why)
    if args.control:
        ctrl = ref_mod.make_control(data, cfg)
        bad = tried = 0
        for i in sorted(pick):
            meta = answers[i][0]
            ans = ctrl.answer(meta)
            if ans is None:
                continue
            tried += 1
            bad += not ref.check(meta, ans)[0]
        out["control_compared"], out["control_mismatches"] = tried, bad
    return out


def perturb(got):
    """Self-test: alter one answer where the harness receives it."""
    text = json.dumps(got)
    for a, b in (('"0x', '"0x1'), (":1", ":2"), ('"', '"x')):
        if a in text:
            return json.loads(text.replace(a, b, 1))
    return {"perturbed": True}


# ---------------------------------------------------------------------------

def measure(cell: Cell, args) -> dict:
    workdir = os.path.join(HERE, ".cache", "run",
                           f"{cell.name}-{args.seed}")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    secs: dict = {}
    server = None
    try:
        data, mix, server, device = set_up(cell, args, workdir, secs)
        if args.sweep:
            return sweep(cell, args, mix, server)
        trace_dir = os.path.join(workdir, "trace")
        prom_before = server.metrics()
        setup_s = time.monotonic() - _T_START
        th = None
        if args.trace:
            th = traced(server, trace_dir,
                        min(3.0, args.seconds / 4), min(4.0, args.seconds / 2),
                        time.perf_counter())
        t_open, records = window(cell, args, mix, server, args.seconds)
        if th is not None:
            th.join()
        prom_after = server.metrics()
        memory = server.memory()
        if server.proc.poll() is not None:
            server.fail("alpha died during the window")
    finally:
        if server is not None:
            server.kill()
    try:
        trace = (reduce_trace(trace_dir, workdir, args.keep_trace)
                 if args.trace else None)
        return report(cell, args, data, mix, records, t_open, setup_s, secs,
                      device, prom_before, prom_after, memory, trace)
    finally:
        if not args.keep:
            shutil.rmtree(workdir, ignore_errors=True)


def report(cell, args, data, mix, records, t_open, setup_s, secs, device,
           prom_before, prom_after, memory, trace) -> dict:
    seconds = args.seconds
    traffic = cell.traffic
    verdict = check(cell, args, data, mix, records)
    falls = srv.fallbacks(prom_after, memory)
    attempted = sum(r["queries"] for r in records)
    failed = sum(r["queries"] for r in records if not r["ok"])
    limits = {"mismatches": (verdict["mismatches"], 0),
              "failed": (failed, 0),
              "fallbacks": (sum(falls.values()), 0)}
    correct = all(v <= lim for v, lim in limits.values()) \
        and verdict["compared"] > 0
    say("compared", verdict["compared"], "answers with the reference in",
        f"{verdict['reference_s']:.1f}s")
    for k, (v, lim) in limits.items():
        say(f"check {k}: {v:g} (limit {lim})")
    if "control_mismatches" in verdict:
        say(f"control mismatches: {verdict['control_mismatches']} of "
            f"{verdict['control_compared']} (a sound control has > 0)")
    if sum(falls.values()):
        say("fallback counters:", falls)

    lat = stats.latencies_ms(records, penalty_ms=seconds * 1e3)
    whole = traffic["loop"] == "closed" and int(traffic["clients"]) == 1
    qps, counted = stats.completed_qps(records, t_open, seconds, whole)
    values = {"setup_s": setup_s, "completed_qps": qps}
    if lat:
        values["latency_p50_ms"] = stats.percentile(lat, 0.50)
        values["latency_p95_ms"] = stats.percentile(lat, 0.95)
    say(f"window {seconds:g}s: {len(records)} requests, {attempted} "
        f"queries attempted, {failed} failed, {counted} counted for "
        f"completed_qps; latency samples {len(lat)}"
        + ("" if stats.supports(len(lat), 0.95)
           else " (fewer than a p95 wants)"))
    if lat:
        slow = max(range(len(lat)), key=lat.__getitem__)
        say(f"slowest request: the {slow + 1}. of {len(lat)}, "
            f"{lat[slow]:.0f} ms")
    say("end to end:", {k: round(v, 4) for k, v in values.items()})

    late = [(r["sent"] - r["due"]) * 1e3 for r in records]
    host = dict(secs)
    if late and traffic["loop"] == "open":
        host["gen_late_p95_ms"] = stats.percentile(late, 0.95)
    ctx = {"prom_before": prom_before, "prom_after": prom_after,
           "host": host, "trace": trace, "config": cell.config,
           "traffic": traffic, "device": device, "root": HERE,
           "completed_qps": qps,
           "sizes": importlib.import_module(
               f"generators.{cell.config['generator']}").sizes(data)}
    layer = {}
    for m in cell.metrics("per_layer"):
        spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
        reader = importlib.import_module(f"readers.{spec['reader']}")
        v = reader.read(ctx, **spec.get("args", {}))
        if v is not None:
            layer[m["name"]] = v
    say("per layer:", {k: round(v, 4) for k, v in layer.items()})
    built = built_in_window(prom_before, prom_after)
    if built:
        say("programs built inside the window:", built)

    units = {m["name"]: m["unit"] for g in ("end_to_end", "per_layer")
             for m in cell.bench[g]}
    if args.trace:
        chosen = layer
    else:
        chosen = {m["name"]: values[m["name"]]
                  for m in cell.metrics("end_to_end")
                  if m["name"] in values}
    result = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in chosen.items()},
        "device": {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"],
                   "memory_peak_bytes": srv.memory_peak_bytes(memory)},
    }
    if args.trace and trace:
        result["device"]["busy_s"] = trace["busy_s"]
        result["device"]["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
        say("trace: busy", round(trace["busy_s"], 4), "of",
            round(trace["window_s"], 4), "s; device plane:",
            trace["device_plane"])
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in limits.items()}
    result["checks"]["compared"] = verdict["compared"]
    for k in ("control_compared", "control_mismatches"):
        if k in verdict:
            result["checks"][k] = verdict[k]
    result["all"] = {**values, **layer}
    return result


def sweep(cell: Cell, args, mix, server) -> dict:
    """One set-up, stepped rates: where the open loop stops keeping up."""
    rows = []
    for rate in [float(x) for x in args.sweep.split(",")]:
        t_open, recs = window(cell, args, mix, server, args.seconds, rate)
        lat = stats.latencies_ms(recs, args.seconds * 1e3)
        qps, _n = stats.completed_qps(recs, t_open, args.seconds, False)
        late = [(r["sent"] - r["due"]) * 1e3 for r in recs]
        drain = max(r["done"] for r in recs) - t_open - args.seconds
        row = {"rate": rate, "requests": len(recs),
               "failed": sum(not r["ok"] for r in recs),
               "completed_qps": qps,
               "p50_ms": stats.percentile(lat, 0.5),
               "p95_ms": stats.percentile(lat, 0.95),
               "gen_late_p95_ms": stats.percentile(late, 0.95),
               "drain_s": drain}
        say("sweep", json.dumps(row))
        rows.append(row)
        if drain > args.seconds:
            say("the backlog outlived the window: past the knee, stopping")
            break
    return {"sweep": rows}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="relax ONLY the platform check (CPU, tiny size)")
    ap.add_argument("--scale", default=None,
                    help="rehearsal only: JSON that overrides "
                         "generator_params, e.g. '{\"sf\": 0.02}'")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated open-loop rates to step through "
                         "after one set-up (prints no result line)")
    ap.add_argument("--control", action="store_true",
                    help="also put the control in the program's place on "
                         "the run's sample and print its mismatches")
    ap.add_argument("--break-answer", action="store_true",
                    dest="break_answer",
                    help="self-test: alter one answer; correct must be "
                         "false")
    ap.add_argument("--keep", action="store_true",
                    help="keep the run's working directory")
    ap.add_argument("--keep-trace", default=None, dest="keep_trace",
                    help="keep the run's trace here, cut small "
                         "(.json.gz, see harness/trace_reduce.py)")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "dgraph_tpu")):
        print("[bench] no dgraph_tpu/ beside benchmark/: nothing to "
              "measure", file=sys.stderr)
        return 3
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = Cell(bench, args.workload)

    def on_term(_signum, _frame):
        # unwind through the `finally` that kills the server
        raise SystemExit(143)
    signal.signal(signal.SIGTERM, on_term)
    try:
        result = measure(cell, args)
    except srv.HarnessError as e:
        print("[bench] FAILED:", e, file=sys.stderr, flush=True)
        return 1
    if "jax" in sys.modules:
        print("[bench] the parent imported jax", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
