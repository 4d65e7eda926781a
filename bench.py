"""North-star benchmark: edges traversed/sec on multi-hop @recurse.

Reference parity: BASELINE.json's north star — @recurse traversal
throughput (query/recurse.go expandRecurse), measured the way the
reference's benchmarks run it: a CONCURRENT MIX of queries (LDBC SNB IC
style), not one query at a time. The reference serves the mix with
per-query goroutines walking posting lists; the CPU baseline here is the
same algorithm vectorised per query in numpy — a stronger per-query
engine than Go per-uid loops — measured DIRECTLY over all B queries at
the SAME concurrency as the device run (no extrapolation).

The device numerator is ops/bfs.py::ell_recurse: B traversals packed into
the bit-lanes of a frontier mask, the whole depth-4 batch as ONE fused XLA
program. Per hop: pure ELL gathers + bitwise ORs (no scatter — measured
~10 ns per random row access on v5e regardless of row width, so the
kernel amortises each access over B=4096 lanes) + one MXU matvec for the
exact per-query edge counters.

Process layout (one process per chip): the PARENT never initialises a
jax backend. It measures the numpy baseline, then runs — one after
another, never two at once — the device child (`--child`: stages
stage0..featprop, each with its own deadline and its own JSON line on
the child's stdout), and only after that child has exited and freed the
chip, the mesh scaling points (`--mesh-child N`) and the two fused A/B
arms (`--fused-child`). A child that holds the chip never spawns a
child that needs it.
    stage0  backend init + 128^2 matmul smoke
    stage1  small-graph ell_recurse (tiny compile)
    stage2  full workload
so the output distinguishes "init hung" from "compile slow" from a real
number, and a partial result (stage1) is still reported if stage2 dies.

The environment chooses the device; there is no re-run on another
backend. Every result names the device it ran on (`platform`,
`device_kind`, `n_devices`, as jax reports them), roofline fields are
printed only for a device_kind in DEVICE_PEAKS (an unknown kind is an
error, a CPU run prints none), and the exit code is non-zero when any
stage that ran left an `error`. XLA compile artifacts persist in the
compile cache (utils/jaxcompat.enable_compile_cache: where
JAX_COMPILATION_CACHE_DIR says, else .jax_cache). One parseable JSON
line is printed in every outcome; errors ride along in an "error" field.

Prints ONE JSON line:
  {"metric": ..., "value": ..., "unit": "edges/s", "vs_baseline": ...}
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

N_NODES = 1 << 20          # ~1M nodes
AVG_DEG = 16.0             # ~16M directed edges
DEPTH = 4
SEEDS_PER_QUERY = 4
B_DEV = 4096               # device lanes (128 uint32 words per row)
SMALL_N = 1 << 16          # stage1 graph
DEV_REPS = 4
MAINT_N = 220              # maintenance-stage store size (host-side)

METRIC = f"edges_traversed_per_sec_{DEPTH}hop_recurse_{B_DEV}q"
GLOBAL_DEADLINE_S = 780
STAGE_DEADLINES = {"stage0": 150.0, "stage1": 240.0, "stage2": 330.0,
                   "maintenance": 60.0, "pressure": 60.0,
                   "sched": 240.0, "graphrag": 120.0, "featprop": 120.0}

# graphrag stage (ISSUE 18): deadline-bound similar_to + @recurse
# retrieval over a Zipfian hot set under admission, a background
# live-loader mutating the store throughout; all embeddings use small
# integer-valued f32 components so every route is bit-identical and
# the fixed-seed response digest is stable across machines
GRAPHRAG_N = 192
GRAPHRAG_DIM = 8
GRAPHRAG_REPS = 15

# featprop stage (ISSUE 19): @msgpass feature traversal — the same
# fixed-seed Zipfian graph discipline, measuring feature_bytes/s
# alongside edges/s with a digest pinned across reps
FEATPROP_N = 160
FEATPROP_DIM = 8
FEATPROP_REPS = 12

# whole-query fusion A/B (ISSUE 15): the same fixed-seed small-query
# template mix served with DGRAPH_TPU_FUSED toggled in a child each —
# small-query p50/p99 + mean kernel_launches/launch_gap_us per shape,
# and a response digest pinning the two paths bit-identical
FUSED_AB_REPS = 20
FUSED_CHILD_TIMEOUT_S = 110.0

# mesh stage: reshard-free chained hops over 1/2/4 devices (ISSUE 10) —
# one child of the PARENT per device count, run after the device child
# has exited. On a CPU backend XLA_FLAGS (set before the child's jax
# import) fakes the devices; a TPU backend ignores the flag and shards
# over the chips the host really has, so every point reports the device
# count it actually ran on
MESH_STAGE_DEVICES = (1, 2, 4)
MESH_N = 1 << 16
MESH_DEG = 8.0
MESH_DEPTH = 3
MESH_SEEDS = 512
MESH_REPS = 3
MESH_CHILD_TIMEOUT_S = 90.0

# Published peaks, keyed by jax's `device_kind`. A device that is not in
# the table is an error for the roofline fields, not a default.
# Source: Google Cloud documentation, "TPU v5e" system architecture —
# 819 GB/s of HBM2e bandwidth per chip ("TPU v5 lite" is how jax names
# the v5e; read off the chip in PR 21).
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0},
}

_emitted = threading.Event()

# bench flight-recorder arming (ISSUE 13): generous thresholds — only
# a stage wedged past its deadline, or a request grossly past its
# prediction, convicts; the bundle path rides the stage's JSON line
BENCH_STALL_FACTOR = 50.0
BENCH_STALL_FLOOR_MS = 5000.0


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def emit(obj) -> None:
    if _emitted.is_set():
        return
    _emitted.set()
    print(json.dumps(obj), flush=True)


def device_info() -> dict:
    """The device this process's jax runs on, as jax reports it — on
    every result, so a number can never be read under another device's
    name."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "n_devices": len(devs)}


def roofline_fields(bytes_per_run: int, secs: float, info: dict) -> dict:
    """Achieved HBM bytes/s and its share of the device's published
    peak. A CPU run prints neither (CPU bytes/s under a device metric's
    name is how the old records came to claim 0.005 of a v5e); an
    accelerator that is not in DEVICE_PEAKS is an error."""
    if info["platform"] == "cpu":
        return {}
    if info["device_kind"] not in DEVICE_PEAKS:
        raise KeyError(
            f"device_kind {info['device_kind']!r} is not in "
            f"bench.DEVICE_PEAKS — add its published peaks (with their "
            f"source) before reporting a roofline share")
    gbps = bytes_per_run / secs / 1e9
    return {"hbm_gbps": round(gbps, 1),
            "hbm_frac_of_peak": round(
                gbps / DEVICE_PEAKS[info["device_kind"]]["hbm_gbps"], 3)}


def build_graph(n, avg, seed=42):
    from dgraph_tpu.models.synthetic import powerlaw_rel
    return powerlaw_rel(n, avg, seed=seed)


def make_seeds(n, B, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, n, SEEDS_PER_QUERY) for _ in range(B)]


def cpu_recurse(indptr, indices, seeds, depth):
    """Vectorised numpy loop=false recurse for ONE query (the reference's
    per-goroutine walk). Returns edges traversed."""
    frontier = np.unique(seeds).astype(np.int64)
    seen_mask = np.zeros(indptr.shape[0] - 1, bool)
    seen_mask[frontier] = True
    edges = 0
    for _ in range(depth):
        if not len(frontier):
            break
        starts = indptr[frontier].astype(np.int64)
        deg = (indptr[frontier + 1] - indptr[frontier]).astype(np.int64)
        total = int(deg.sum())
        base = np.repeat(np.cumsum(deg) - deg, deg)
        pos = np.repeat(starts, deg) + (np.arange(total) - base)
        nbrs = indices[pos]
        edges += total
        nxt = np.unique(nbrs)
        nxt = nxt[~seen_mask[nxt]]
        seen_mask[nxt] = True
        frontier = nxt
    return edges


# ---------------------------------------------------------------------------
# child: staged device measurement; one JSON line per stage on stdout

# the stdout protocol is one JSON line per stage, read by name in the
# parent — the watchdog's on_dump callback may print from its own
# thread, so every line goes out under one lock, never interleaved
_stage_lock = threading.Lock()


def _stage(obj) -> None:
    with _stage_lock:
        print(json.dumps(obj), flush=True)


def _arm_flight_recorder():
    """Arm the flight recorder for the whole child (ISSUE 13): any
    stage that dies leaves a bundle via the error path below, and any
    stage that WEDGES past its deadline is convicted by the watchdog —
    whose on_dump hook prints the stage's error line (with the bundle
    path) so the BENCH JSON still names the evidence even though the
    stage itself will never print."""
    from dgraph_tpu.utils import flightrec

    def on_dump(record, bundle):
        reason = record.get("reason") or {}
        op = reason.get("op") or {}
        name = op.get("name", "")
        if reason.get("kind") == "wedged" and name.startswith("bench."):
            _stage({"stage": name.split(".", 1)[1],
                    "error": "stage stalled past its deadline "
                             "(flight watchdog)",
                    "bundle": record.get("path")})

    flightrec.arm(diag_dir=os.path.join(ROOT, ".bench_diag"),
                  stall_factor=BENCH_STALL_FACTOR,
                  stall_floor_ms=BENCH_STALL_FLOOR_MS,
                  poll_s=0.5, min_dump_interval_s=10.0,
                  on_dump=on_dump)
    return flightrec


def _run_stage(flightrec, name: str, fn) -> bool:
    """Run one bench stage under flight-recorder tracking: a raised
    error dumps a bundle and prints {stage, error, bundle} — the
    PARTIAL run's telemetry survives in the bundle instead of dying
    with the stage — and the child continues to the next stage.
    Returns whether the stage produced a result (the child's exit code
    is non-zero when any did not)."""
    mark = len(flightrec.dumps())
    try:
        with flightrec.track(f"bench.{name}",
                             budget_s=STAGE_DEADLINES.get(name)):
            doc = fn()
    except Exception as e:  # noqa: BLE001 — a dead stage must not kill the rest
        out = flightrec.dump(
            trigger="error",
            reason={"stage": name,
                    "error": f"{type(e).__name__}: {e}"})
        _stage({"stage": name,
                "error": f"{type(e).__name__}: {e}",
                "bundle": out["path"]})
        return False
    new = [d["path"] for d in flightrec.dumps()[mark:] if d["path"]]
    if new:
        doc["flight_dumps"] = new
    _stage({**device_info(), **doc})
    return True


def _stage_telemetry(stage: str) -> dict:
    """Per-stage compile/transfer/execute breakdown sourced from the
    SHARED observability registry (utils/tracing spans — the same
    objects /debug/traces serves in a server process), so a dead stage
    diagnoses from the stage JSON: a missing `compile_us` means
    the hang predates XLA, a huge one means Mosaic/XLA compile, a huge
    `transfer_us` means the HBM upload. Execute reports the best rep
    (what the throughput number is computed from); the rest sum."""
    from dgraph_tpu.utils import tracing
    from dgraph_tpu.utils.metrics import METRICS
    out: dict[str, int] = {}
    for s in tracing.recent(512):
        if not s.name.startswith("bench.") or \
                s.attrs.get("stage") != stage:
            continue
        phase = s.name.split(".", 1)[1]
        k = phase + "_us"
        if phase == "execute":
            out[k] = min(out.get(k, s.dur_us), s.dur_us)
        else:
            out[k] = out.get(k, 0) + s.dur_us
        METRICS.observe("bench_stage_us", s.dur_us, stage=stage,
                        phase=phase)
    return out


def child_main(expect_path: str) -> None:
    B = B_DEV
    import jax

    # persistent compile cache: the expensive gather programs compile
    # once per cache directory; later runs hit disk
    from dgraph_tpu.utils.jaxcompat import enable_compile_cache
    enable_compile_cache()

    import jax.numpy as jnp
    from dgraph_tpu.ops.bfs import (build_ell, device_ell, make_ell_count,
                                    make_ell_recurse, pack_seed_masks)
    from dgraph_tpu.ops.pallas_hop import pallas_enabled
    from dgraph_tpu.utils import tracing
    from dgraph_tpu.utils.jitcache import Memo
    from dgraph_tpu.utils.metrics import METRICS

    flightrec = _arm_flight_recorder()

    # -- stage0: backend alive + MXU smoke ----------------------------------
    def stage0():
        t0 = time.perf_counter()
        x = jnp.ones((128, 128), jnp.bfloat16)
        np.asarray(x @ x)
        return {"stage": "stage0",
                "secs": round(time.perf_counter() - t0, 2)}

    # -- stage1: small graph, small compile ---------------------------------
    def stage1():
        t0 = time.perf_counter()
        rel_s = build_graph(SMALL_N, AVG_DEG, seed=5)
        g_s = build_ell(rel_s.indptr, rel_s.indices)
        seeds_s = make_seeds(SMALL_N, 256, seed=3)
        mask_s = pack_seed_masks(g_s, seeds_s)
        with tracing.span("bench.transfer", stage="stage1"):
            dev_ell_s = device_ell(g_s)
            jax.block_until_ready([e for _k, e, _r in dev_ell_s.parts
                                   if e is not None])
        fn_s = make_ell_recurse(dev_ell_s, g_s.outdeg, g_s.n,
                                mask_s.shape[1])
        t_c = time.perf_counter()
        with tracing.span("bench.compile", stage="stage1"):
            _l, _s, edges_s = fn_s(jax.device_put(mask_s), DEPTH)
            edges_s = np.asarray(edges_s)
        compile_s = time.perf_counter() - t_c
        want = cpu_recurse(rel_s.indptr, rel_s.indices, seeds_s[17],
                           DEPTH)
        assert int(edges_s[17]) == want, (int(edges_s[17]), want)
        ts = []
        for _ in range(3):
            t_r = time.perf_counter()
            with tracing.span("bench.execute", stage="stage1"):
                _l, _s, e2 = fn_s(jax.device_put(mask_s), DEPTH)
                np.asarray(e2)
            ts.append(time.perf_counter() - t_r)
        small_edges = int(edges_s.astype(np.int64).sum())
        return {"stage": "stage1",
                "secs": round(time.perf_counter() - t0, 2),
                "compile_secs": round(compile_s, 2),
                "run_ms": round(min(ts) * 1e3, 1),
                "edges_per_sec": round(small_edges / min(ts)),
                "telemetry": _stage_telemetry("stage1")}

    # -- stage2: full workload ----------------------------------------------
    def stage2():
        # synthetic-graph GENERATION is data-gen, not system cost:
        # billed to gen_secs, never build_secs (ISSUE 7 satellite)
        t0 = time.perf_counter()
        rel = build_graph(N_NODES, AVG_DEG)
        seeds = make_seeds(N_NODES, B)
        gen_s = time.perf_counter() - t0

        # ELL/plan amortization, measured the way the serving path
        # caches it (engine/batch._ell_for per snapshot + the plan
        # memo): a cold build pays the vectorized CSR-transpose +
        # block fill once; a warm re-plan is a memo hit
        ell_memo = Memo("bench.ell_plan", capacity=4)

        def ell_plan(r):
            key = (id(r), r.nnz)
            hit = ell_memo.get(key)
            if hit is not None:
                METRICS.inc("plan_cache_hits_total", cache="bench")
                return hit
            METRICS.inc("plan_cache_misses_total", cache="bench")
            with tracing.span("batch.build_ell", pred="bench"):
                built = build_ell(r.indptr, r.indices)
            ell_memo.put(key, built)
            return built

        t0 = time.perf_counter()
        g = ell_plan(rel)
        build_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        g2 = ell_plan(rel)
        build_warm_s = time.perf_counter() - t0
        assert g2 is g

        # lane words: uint64 (half the gather elements per row at
        # identical bytes); the Pallas hop is uint32-only, so the A/B
        # flag pins 32
        word_bits = 32 if pallas_enabled() else 64

        with jax.enable_x64(word_bits == 64):
            mask0 = pack_seed_masks(g, seeds, word_bits=word_bits)
            W = mask0.shape[1]
            t0 = time.perf_counter()
            with tracing.span("bench.transfer", stage="stage2"):
                dev = device_ell(g)
                jax.block_until_ready([e for _k, e, _r in dev.parts
                                       if e is not None])
            put_s = time.perf_counter() - t0

            # count_edges=False: the exact per-query counters come
            # from ONE post-hoc matvec over (seen, last) — measurement
            # apparatus, not traversal, so it no longer rides inside
            # every timed hop
            fn = make_ell_recurse(dev, g.outdeg, g.n, W,
                                  count_edges=False,
                                  word_bits=word_bits)
            count_fn = make_ell_count(g.outdeg, g.n, W,
                                      word_bits=word_bits)
            t0 = time.perf_counter()
            with tracing.span("bench.compile", stage="stage2"):
                out = fn(jax.device_put(mask0), DEPTH)
                jax.block_until_ready(out)
            compile_s = time.perf_counter() - t0

            ts = []
            for _ in range(DEV_REPS):
                # the kernel DONATES its seed mask (buffer reuse
                # across hops), so each rep re-puts outside the timed
                # region
                md = jax.device_put(mask0)
                jax.block_until_ready(md)
                t0 = time.perf_counter()
                with tracing.span("bench.execute", stage="stage2"):
                    out = fn(md, DEPTH)
                    jax.block_until_ready(out)
                ts.append(time.perf_counter() - t0)
            last_d, seen_d, _e = out
            edges = np.asarray(count_fn(last_d,
                                        seen_d)).astype(np.int64)
        dev_s = min(ts)

        # identical-work check against the parent's numpy walks
        expect = np.load(expect_path)["edges"][:B]
        assert np.array_equal(edges, expect), \
            "device/cpu edge counts diverge"

        total_edges = int(edges.sum())
        snap = METRICS.snapshot()["counters"]
        plan_cache = {
            "hits": sum(v for k, v in snap.items()
                        if k.startswith("plan_cache_hits_total")),
            "misses": sum(v for k, v in snap.items()
                          if k.startswith("plan_cache_misses_total"))}
        # HBM traffic model per hop: level-1 index reads + mask-row
        # gathers + mask elementwise (4 arrays); the edge counter runs
        # once outside the timed region and is excluded
        row_bytes = W * (word_bits // 8)
        gather_bytes = g.padded_edges * (4 + row_bytes)
        elem_bytes = 4 * (g.n + 1) * row_bytes
        bytes_per_run = DEPTH * (gather_bytes + elem_bytes)
        return {"stage": "stage2", "B": B,
                "word_bits": word_bits,
                "gen_secs": round(gen_s, 2),
                "build_secs": round(build_s, 2),
                "build_secs_warm": round(build_warm_s, 4),
                "plan_cache": plan_cache,
                "device_put_secs": round(put_s, 2),
                "compile_secs": round(compile_s, 2),
                "dev_s": round(dev_s, 4),
                "total_edges": total_edges,
                "edges_per_sec": round(total_edges / dev_s),
                **roofline_fields(bytes_per_run, dev_s, device_info()),
                "padded_edges": g.padded_edges,
                "padded_frac": round(
                    g.padded_edges / max(total_edges, 1), 3),
                "telemetry": _stage_telemetry("stage2")}

    # every stage rides _run_stage (ISSUE 13): a raised error dumps a
    # flight bundle and prints {stage, error, bundle} instead of
    # losing the partial run's telemetry; the child continues. This
    # process holds the chip, so nothing here spawns a process that
    # needs one: the mesh points and the fused arms are the parent's
    ok = [_run_stage(flightrec, name, fn)
          for name, fn in (("stage0", stage0), ("stage1", stage1),
                           ("stage2", stage2),
                           ("maintenance", maintenance_stage),
                           ("pressure", pressure_stage),
                           ("sched", sched_stage),
                           ("graphrag", graphrag_stage),
                           ("featprop", featprop_stage))]
    os._exit(0 if all(ok) else 1)


def mesh_child_main(n_dev: int) -> None:
    """One mesh scaling point: depth-MESH_DEPTH visit-once expansion as
    chained reshard-free hops (parallel/dhop.chain_hop — the mesh
    serving path's kernel) over `n_dev` devices, same workload at every
    device count. The spawner (the jax-free parent, after the device
    child exited) set XLA_FLAGS before this process imported jax, so a
    CPU backend fakes `n_dev` host devices; a real TPU backend ignores
    the flag and shards over the chips it has. Prints ONE JSON line:
    edges/s, shard balance, resident bytes, and the reshard counter
    (the steady-path zero-copy contract, asserted)."""
    import jax

    from dgraph_tpu.utils.jaxcompat import enable_compile_cache
    enable_compile_cache()

    from dgraph_tpu.ops.uidalgebra import SENTINEL32
    from dgraph_tpu.parallel.dhop import chain_hop
    from dgraph_tpu.parallel.mesh import make_mesh, reshard_count
    from dgraph_tpu.parallel.pshard import device_put_rel, shard_rel

    d = min(n_dev, len(jax.devices()))
    mesh = make_mesh(d)
    rel = build_graph(MESH_N, MESH_DEG, seed=17)
    host_srel = shard_rel(rel, d)
    nnz = host_srel.indptr_s[:, -1].astype(np.int64)
    srel = device_put_rel(host_srel, mesh)

    out_cap = MESH_N
    seen_cap = 2 * MESH_N
    edge_cap = 1
    while edge_cap < max(int(nnz.max()), 1):
        edge_cap <<= 1
    rng = np.random.default_rng(3)
    seeds = np.unique(rng.integers(0, MESH_N, MESH_SEEDS)).astype(
        np.int32)

    def pad(a, size):
        out = np.full(size, SENTINEL32, np.int32)
        out[:len(a)] = a
        return out

    def run_chain(check: bool):
        fr, seen = pad(seeds, out_cap), pad(seeds, seen_cap)
        edges = []
        for _h in range(MESH_DEPTH):
            fr, seen, e, needs, *_rest = chain_hop(
                mesh, srel, fr, seen, edge_cap, out_cap, seen_cap)
            if check:
                need = np.asarray(needs)
                assert need[0] <= out_cap and need[1] <= seen_cap \
                    and need[2] <= edge_cap, need.tolist()
            edges.append(e)
        return int(sum(np.asarray(e) for e in edges))

    t0 = time.perf_counter()
    total_edges = run_chain(check=True)  # compile + cap proof
    compile_s = time.perf_counter() - t0
    ts = []
    for _ in range(MESH_REPS):
        t0 = time.perf_counter()
        got = run_chain(check=False)
        ts.append(time.perf_counter() - t0)
        assert got == total_edges
    best = min(ts)
    resharded = reshard_count()
    assert resharded == 0, resharded  # the steady-path contract
    per_shard_bytes = int(host_srel.indptr_s[0].nbytes
                          + host_srel.indices_s[0].nbytes + 4)
    from dgraph_tpu.utils import tracing as _tracing
    print(json.dumps({
        "n_dev": d, **device_info(),
        "depth": MESH_DEPTH, "total_edges": total_edges,
        "compile_secs": round(compile_s, 2),
        "run_ms": round(best * 1e3, 1),
        "edges_per_sec": round(total_edges / best),
        "resharded": resharded,
        "shard_balance": round(float(nnz.max())
                               / max(float(nnz.mean()), 1.0), 3),
        "shard_bytes": per_shard_bytes,
        # per-node trace health (ISSUE 14): this child is one "node"
        # of the mesh run; the parent folds these into BENCH "fleet"
        "spans": _tracing.stats()}), flush=True)
    os._exit(0)


def _run_point(args: list, env: dict, timeout_s: float) -> dict:
    """One measurement child of the PARENT (a mesh point or a fused
    arm): its last stdout line is its JSON result. A child that dies,
    times out or prints nothing leaves an `error` — which the parent's
    exit code reports; it is never carried past as a missing key."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + args,
            capture_output=True, text=True, cwd=ROOT, env=env,
            timeout=timeout_s)
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001 — per-point isolation
        return {"error": f"{type(e).__name__}: {e}"}


def mesh_stage() -> dict:
    """Mesh-sharded serving scaling (ISSUE 10): the SAME chained-hop
    workload at 1/2/4 devices, each point its own subprocess so
    XLA_FLAGS binds before jax initializes. Run by the jax-free parent,
    one point at a time. Reports edges/s per device count plus scaling
    (4-dev / 1-dev) and parallel efficiency (scaling / 4) — virtual CPU
    devices share the host's cores, so there the number says nothing
    about a mesh of chips; each point names the device it ran on."""
    t0 = time.perf_counter()
    devices: dict[str, dict] = {}
    for n in MESH_STAGE_DEVICES:
        env = dict(os.environ)
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={n}"])
        devices[str(n)] = _run_point(["--mesh-child", str(n)], env,
                                     MESH_CHILD_TIMEOUT_S)
    out = {"stage": "mesh",
           "secs": round(time.perf_counter() - t0, 2),
           "devices": devices}
    errs = {n: v["error"] for n, v in devices.items() if "error" in v}
    if errs:
        out["error"] = f"mesh point(s) failed: {errs}"
    e1 = devices.get("1", {}).get("edges_per_sec")
    e4 = devices.get("4", {}).get("edges_per_sec")
    if e1 and e4:
        out["scaling_4v1"] = round(e4 / e1, 3)
        out["efficiency_4"] = round(e4 / e1 / 4, 3)
        out["resharded"] = sum(v.get("resharded", 0)
                               for v in devices.values())
    fleet = _fleet_block({n: v.get("spans") for n, v in devices.items()
                          if isinstance(v, dict)})
    if fleet is not None:
        out["fleet"] = fleet
    return out


def _fleet_block(per_node: dict) -> dict | None:
    """Fold per-node tracing.stats() docs into the BENCH "fleet"
    summary (ISSUE 14): per-node span counts + the overall
    propagated-trace fraction, so every bench run records cross-node
    trace health for free."""
    nodes = {str(n): s for n, s in per_node.items() if s}
    if not nodes:
        return None
    total = sum(s["spans_total"] for s in nodes.values())
    prop = sum(s["propagated_total"] for s in nodes.values())
    return {"nodes": nodes, "spans_total": total,
            "propagated_total": prop,
            "propagated_frac": round(prop / total, 4) if total else 0.0}


def lint_stage() -> dict:
    """graftlint finding/waiver counts per rule + facts totals —
    the static-analysis debt tracked alongside throughput (ISSUE 6),
    and the kernel/span inventory the cost-model item consumes.
    Equivalent CLI: python -m dgraph_tpu.analysis --format=json."""
    try:
        from dgraph_tpu.analysis import run as lint_run
        a = lint_run()
        return {**a.counts(), "facts": a.facts["totals"]}
    except Exception as e:  # noqa: BLE001 — bench must not die on lint
        return {"error": f"{type(e).__name__}: {e}"}


def run_sched_workload(priors_on: bool, chain_n: int = 2000,
                       n_expensive: int = 3, n_cheap: int = 6,
                       queue_depth: int = 4, seed: int = 23) -> dict:
    """Mixed cheap/expensive serving under admission pressure — the
    cost-prior A/B harness shared by the bench "sched" stage and the
    tier-1 acceptance test (tests/test_costprior.py).

    One token, a bounded queue: an EXPENSIVE query (shortest-path grind
    over a `chain_n` uid chain hunting an unreachable island) holds the
    token while more expensive queries queue; CHEAP name lookups then
    arrive. With priors OFF the cheap arrivals queue FIFO behind the
    expensive ones or get shed at the full queue (sheds land on cheap
    work). With priors ON the scheduler predicts each arrival's cost
    from its warmed shape prior: cheap queries displace queued
    expensive ones (sheds land on the expensive work) and drain first
    (SJF handoff). Reports cheap p50/p99 µs over COMPLETED cheap
    queries, shed counts by kind, and shed precision = expensive sheds
    / total sheds."""
    import threading as _threading

    from dgraph_tpu.server.admission import ServerOverloaded
    from dgraph_tpu.server.api import Alpha
    from dgraph_tpu.store import StoreBuilder, parse_schema
    from dgraph_tpu.utils import costprior, costprofile

    costprior.reset()
    costprofile.reset()
    floor0 = costprior.PRIORS.sample_floor
    costprior.PRIORS.sample_floor = 2  # 2 warm runs arm a prior
    try:
        b = StoreBuilder(parse_schema(
            "link: [uid] @reverse .\nname: string @index(exact) ."))
        uids = np.arange(1, chain_n, dtype=np.int64)
        b.add_edges("link", uids, uids + 1)
        for i in range(1, 65):
            b.add_value(i, "name", f"p{i}")
        b.add_value(chain_n + 5, "name", "island")  # unreachable
        alpha = Alpha(base=b.finalize(), device_threshold=10**9)
        alpha.cost_priors = priors_on

        exp_q = ("{ path as shortest(from: 0x1, to: 0x%x, depth: %d) "
                 "{ link } }" % (chain_n + 5, chain_n))
        rng = np.random.default_rng(seed)
        cheap_qs = ['{ q(func: eq(name, "p%d")) { name } }' % i
                    for i in rng.integers(1, 65, n_cheap)]

        # warm uncontended: parse caches + (priors on) text→shape memo
        # and per-shape priors past the (lowered) sample floor
        for _ in range(2):
            alpha.query(exp_q)
            for q in cheap_qs:
                alpha.query(q)

        adm = alpha.attach_admission(max_inflight=1,
                                     queue_depth=queue_depth)
        results = {"cheap_us": [], "shed": {"cheap": 0, "expensive": 0},
                   "ok": {"cheap": 0, "expensive": 0}}
        lock = _threading.Lock()

        def run(q: str, kind: str):
            t0 = time.perf_counter()
            try:
                alpha.query(q)
                us = (time.perf_counter() - t0) * 1e6
                with lock:
                    results["ok"][kind] += 1
                    if kind == "cheap":
                        results["cheap_us"].append(us)
            except ServerOverloaded:
                with lock:
                    results["shed"][kind] += 1

        threads = []

        def submit(q, kind):
            t = _threading.Thread(target=run, args=(q, kind))
            t.start()
            threads.append(t)

        def wait_for(pred, timeout=10.0):
            end = time.monotonic() + timeout
            while time.monotonic() < end:
                if pred():
                    return True
                time.sleep(0.002)
            return False

        lane = adm.lanes["read"]

        def lane_state():
            # under the lane lock: request threads mutate these and the
            # race sanitizer (rightly) convicts an unlocked poll
            with lane.lock:
                return lane.inflight, len(lane.waiters)

        submit(exp_q, "expensive")
        wait_for(lambda: lane_state()[0] >= 1)
        for _ in range(n_expensive - 1):
            submit(exp_q, "expensive")
        wait_for(lambda: lane_state()[1] >= n_expensive - 1)
        for q in cheap_qs:
            submit(q, "cheap")
            time.sleep(0.01)
        for t in threads:
            t.join(60)

        lats = sorted(results["cheap_us"])
        sheds = results["shed"]["cheap"] + results["shed"]["expensive"]
        out = {
            "priors": priors_on,
            "cheap_completed": len(lats),
            "cheap_p50_us": round(lats[len(lats) // 2]) if lats else 0,
            "cheap_p99_us": round(lats[min(len(lats) - 1,
                                           int(len(lats) * 0.99))])
            if lats else 0,
            "shed_cheap": results["shed"]["cheap"],
            "shed_expensive": results["shed"]["expensive"],
            "shed_precision": (results["shed"]["expensive"] / sheds
                               if sheds else None),
            "expensive_ok": results["ok"]["expensive"],
        }
        if priors_on:
            st = costprior.status()
            out["prior"] = {"hits": st["hits"],
                            "fallbacks": st["fallbacks"],
                            "error": st["error"]}
        return out
    finally:
        costprior.PRIORS.sample_floor = floor0


def fused_child_main() -> None:
    """One arm of the whole-query-fusion A/B (ISSUE 15): serve the
    fixed-seed small-query template mix with DGRAPH_TPU_FUSED as the
    parent set it (the flag must bind per-process — route selection is
    sticky-cached per shape). device_threshold=0 forces device kernels
    at every level, so the staged arm pays the real launch chain the
    fused arm collapses. Prints ONE JSON line: p50/p99 over the mix,
    mean kernel_launches + launch_gap_us overall and per shape, route
    counts, and a sha256 over the raw response bytes (the parent pins
    the two arms' digests equal — bit-identity is part of the A/B)."""
    import hashlib

    from dgraph_tpu.server.api import Alpha
    from dgraph_tpu.utils import costprofile
    from dgraph_tpu.utils.jaxcompat import enable_compile_cache
    from dgraph_tpu.utils.metrics import METRICS

    enable_compile_cache()
    fused_on = os.environ.get("DGRAPH_TPU_FUSED", "1") != "0"
    a = Alpha(device_threshold=0)
    a.alter("friend: [uid] @reverse .\nname: string @index(exact) .")
    rng = np.random.default_rng(11)
    lines = []
    for i in range(1, 257):
        lines.append(f'<{i}> <name> "p{i % 23}" .')
        for j in rng.integers(1, 257, 4):
            if i != int(j):
                lines.append(f"<{i}> <friend> <{int(j)}> .")
    a.mutate(set_nquads="\n".join(lines))
    qs = [
        '{ q(func: uid(0x2)) { uid friend { uid friend { uid } } } }',
        '{ q(func: eq(name, "p7")) { name friend '
        '@filter(eq(name, "p3")) { name } } }',
        '{ q(func: uid(0x5)) { friend (first: 3) { uid } '
        '~friend { uid } } }',
        '{ q(func: uid(0x9)) @recurse(depth: 3) { uid friend } }',
        '{ q(func: uid(0x4)) { c as count(friend) friend { uid } } '
        'm() { max(val(c)) } }',
    ]
    # warm both arms identically: parse caches, jit compiles, and the
    # fused cap memo stay out of the measurement (steady-state serving
    # is the claim, not first-request compile cost)
    for q in qs:
        a.query(q)
        a.query(q)
    costprofile.reset()
    lat: list = []
    digest = hashlib.sha256()
    for _ in range(FUSED_AB_REPS):
        for q in qs:
            t0 = time.perf_counter()
            raw = a.query_raw(q)
            lat.append((time.perf_counter() - t0) * 1e6)
            digest.update(raw)
    lat.sort()
    shapes = {}
    w_launch = w_gap = w_n = 0.0
    for shape, st in costprofile.summary(top_n=64)["shapes"].items():
        feats = st.get("features", {})
        shapes[shape] = {
            "count": st["count"],
            "mean_kernel_launches": feats.get("kernel_launches", 0),
            "mean_launch_gap_us": feats.get("launch_gap_us", 0)}
        w_launch += feats.get("kernel_launches", 0) * st["count"]
        w_gap += feats.get("launch_gap_us", 0) * st["count"]
        w_n += st["count"]
    n = len(lat)
    print(json.dumps({
        "fused": fused_on, **device_info(),
        "queries": n,
        "p50_us": round(lat[n // 2]),
        "p99_us": round(lat[min(n - 1, int(n * 0.99))]),
        "mean_kernel_launches": round(w_launch / max(w_n, 1), 2),
        "mean_launch_gap_us": round(w_gap / max(w_n, 1)),
        "shapes": shapes,
        "routes": {r: METRICS.get("fused_route_total", route=r)
                   for r in ("fused", "staged", "fallback")},
        "digest": digest.hexdigest(),
    }), flush=True)
    os._exit(0)


def fused_ab_stage() -> dict:
    """Whole-query fusion ON/OFF on the same fixed-seed workload
    (ISSUE 15): spawn the two arms (same workload, same seed, the flag
    toggled in each child's env) from the jax-free parent, one after
    the other, and join the headline: p50 speedup, launch collapse, and
    the bit-identity digest check."""
    t0 = time.perf_counter()
    arms = {arm: _run_point(["--fused-child"],
                            dict(os.environ, DGRAPH_TPU_FUSED=flag),
                            FUSED_CHILD_TIMEOUT_S)
            for arm, flag in (("off", "0"), ("on", "1"))}
    out = {"stage": "fused_ab", "off": arms["off"], "on": arms["on"]}
    on, off = arms["on"], arms["off"]
    errs = {a: v["error"] for a, v in arms.items() if "error" in v}
    if errs:
        out["error"] = f"fused arm(s) failed: {errs}"
    if "digest" in on and "digest" in off:
        out["identical"] = on["digest"] == off["digest"]
        if not out["identical"]:
            out["error"] = "fused ON and OFF response digests differ"
        if on.get("p50_us"):
            out["p50_speedup"] = round(off["p50_us"] / on["p50_us"], 3)
        out["launch_collapse"] = {
            "off_mean": off["mean_kernel_launches"],
            "on_mean": on["mean_kernel_launches"]}
    out["secs"] = round(time.perf_counter() - t0, 2)
    return out


def sched_stage() -> dict:
    """Cost-prior scheduling A/B (ISSUE 9 headline): the mixed workload
    with priors on vs off — cheap-query p50/p99 and shed precision —
    plus the prior fit summary and the batch planner's cost-pack
    imbalance gauges from a mixed two-family kernel batch."""
    from dgraph_tpu.server.api import Alpha
    from dgraph_tpu.utils import costprior, costprofile, slo, timeseries
    from dgraph_tpu.utils.metrics import METRICS

    t0 = time.perf_counter()
    # retained-history + SLO verdicts over the stage's own traffic
    # (ISSUE 17): a fast-cadence sampler with test-scaled windows
    # watches the whole A/B run; its series summary and per-objective
    # burn-rate verdicts land in the BENCH JSON
    sampler = timeseries.arm(
        interval_s=0.2, ring_points=600,
        slo_engine=slo.SloEngine(fast_window_s=10.0, slow_window_s=60.0),
        forecast=False)
    off = run_sched_workload(priors_on=False)
    on = run_sched_workload(priors_on=True)
    fit = costprior.refit()  # fit over the on-run's digests

    # cost-packed batch planning: two structurally-distinct recurse
    # groups in one batch → plan_pack_imbalance{stage=count|predicted}
    costprofile.reset()
    a = Alpha(device_threshold=10**9)
    a.alter("fan: [uid] @reverse .\nthin: [uid] @reverse .")
    rng = np.random.default_rng(5)
    lines = []
    for i in range(1, 128):
        for j in rng.integers(1, 128, 6):
            if i != int(j):
                lines.append(f"<{i}> <fan> <{int(j)}> .")
    for i in range(1, 16):
        lines.append(f"<{i}> <thin> <{i + 1}> .")
    a.mutate(set_nquads="\n".join(lines))
    fan_qs = ["{ q(func: uid(%d)) @recurse(depth: 3) { fan uid } }" % i
              for i in range(1, 9)]
    thin_qs = ["{ q(func: uid(%d)) @recurse(depth: 2) { thin uid } }"
               % i for i in range(1, 9)]
    # HOMOGENEOUS warm batches: each kernel family digests under its
    # own shape key (enough times to clear the sample floor), so the
    # mixed batch's groups each have a trusted prior
    from dgraph_tpu.utils.costprior import SAMPLE_FLOOR
    for _ in range(SAMPLE_FLOOR):
        a.query_batch(fan_qs)
        a.query_batch(thin_qs)
    costprior.refit()
    a.query_batch(fan_qs + thin_qs)
    gauges = METRICS.snapshot()["gauges"]
    imb = {stage: gauges.get('plan_pack_imbalance{stage="%s"}' % stage)
           for stage in ("count", "predicted")}

    from dgraph_tpu.utils import tracing as _tracing
    sampler.tick()  # one final point so the tail of the run is retained
    states = (sampler.engine.evaluate(sampler.ring)
              if sampler.engine is not None else {})
    ts_summary = sampler.ring.summary(60.0)
    timeseries.disarm()
    out = {"stage": "sched",
           "secs": round(time.perf_counter() - t0, 2),
           "priors_off": off, "priors_on": on,
           "prior_fit": fit,
           "pack_imbalance": imb,
           "timeseries": ts_summary,
           "slo": {name: {win: {"burn": w["burn"],
                                "breached": w["breached"]}
                          for win, w in st["windows"].items()}
                   for name, st in states.items()},
           "scheduler": costprior.status(top_n=5)}
    fleet = _fleet_block({"local": _tracing.stats()})
    if fleet is not None:
        out["fleet"] = fleet
    return out


def _graphrag_fixture():
    """Fixed-seed GraphRAG store: every node carries an `emb` vector
    (small integer components — exactly representable, so host/device/
    mesh score identically) and Zipfian `friend` edges concentrating
    expansion on a hot hub set. Returns (alpha, query mix, grind)."""
    from dgraph_tpu.server.api import Alpha

    a = Alpha(device_threshold=0)  # device kernels at every level —
    # the launch chain the fused knn stage collapses is the claim
    a.alter("emb: float32vector @dim(%d) .\n"
            "friend: [uid] @reverse .\n"
            "name: string @index(exact) ." % GRAPHRAG_DIM)
    rng = np.random.default_rng(29)
    lines = []
    for i in range(1, GRAPHRAG_N + 1):
        v = rng.integers(0, 7, GRAPHRAG_DIM)
        lines.append('<%d> <emb> "[%s]" .'
                     % (i, ", ".join(str(int(x)) for x in v)))
        lines.append(f'<{i}> <name> "p{i % 17}" .')
        for j in rng.zipf(1.4, 5):  # Zipf targets: low uids are hubs
            t = int(min(j, GRAPHRAG_N))
            if t != i:
                lines.append(f"<{i}> <friend> <{t}> .")
    a.mutate(set_nquads="\n".join(lines))
    qs = []
    for _ in range(10):  # vector-literal seeds, fixed-seed k
        v = rng.integers(0, 7, GRAPHRAG_DIM)
        lit = "[%s]" % ", ".join(str(int(x)) for x in v)
        k = int(rng.integers(3, 9))
        qs.append('{ q(func: similar_to(emb, %d, "%s")) '
                  '@recurse(depth: 2) { uid friend } }' % (k, lit))
    for _ in range(4):  # uid-form seeds over the Zipfian hot set
        u = int(min(rng.zipf(1.5), GRAPHRAG_N))
        qs.append('{ q(func: similar_to(emb, 4, %d)) '
                  '{ uid name friend { uid } } }' % u)
    # the grind: many wide-k retrieval blocks in one query — the
    # expensive arrival that holds the admission token while the
    # small reads queue behind it
    grind = "{ %s }" % " ".join(
        'g%d(func: similar_to(emb, 48, %d)) @recurse(depth: 4) '
        '{ uid friend }' % (i, i + 1) for i in range(8))
    return a, qs, grind


def graphrag_stage() -> dict:
    """GraphRAG retrieval serving (ISSUE 18): the fixed-seed
    similar_to + @recurse mix measured two ways — an unloaded digest
    pass (bit-identity across reps + launches/query, the fused-knn
    collapse headline) and a deadline-bound pass under admission with
    wide-k grinds contending and a live-loader mutating throughout
    (p50/p99 over admitted reads, shed precision)."""
    import hashlib
    import threading as _threading

    from dgraph_tpu.server.admission import ServerOverloaded
    from dgraph_tpu.utils import costprior, costprofile
    from dgraph_tpu.utils.metrics import METRICS

    t0 = time.perf_counter()
    a, qs, grind = _graphrag_fixture()
    for q in qs:  # warm: parse caches + fused compiles stay out
        a.query(q)
        a.query(q)
    costprofile.reset()
    digest = hashlib.sha256()
    rep_digests, lats = [], []
    for _ in range(GRAPHRAG_REPS):
        rep = hashlib.sha256()
        for q in qs:
            t = time.perf_counter()
            raw = a.query_raw(q)
            lats.append((time.perf_counter() - t) * 1e6)
            digest.update(raw)
            rep.update(raw)
        rep_digests.append(rep.hexdigest())
    lats.sort()
    launches = w_n = 0.0
    for st in costprofile.summary(top_n=64)["shapes"].values():
        launches += st.get("features", {}).get(
            "kernel_launches", 0) * st["count"]
        w_n += st["count"]

    # deadline-bound serving under admission + live mutations: grinds
    # hold the token and fill the queue; small reads arrive with
    # warmed priors, displace the queued grinds (sheds land on the
    # expensive work), and drain inside the latency budget
    costprior.reset()
    floor0 = costprior.PRIORS.sample_floor
    costprior.PRIORS.sample_floor = 2
    results = {"us": [], "shed": {"cheap": 0, "expensive": 0},
               "ok": {"cheap": 0, "expensive": 0}}
    lock = _threading.Lock()
    stop = _threading.Event()
    mutated = [0]
    try:
        a.cost_priors = True
        for _ in range(2):  # arm the lowered sample floor
            a.query(grind)
            for q in qs:
                a.query(q)
        adm = a.attach_admission(max_inflight=1, queue_depth=6)

        def live_load():
            i = 0
            while not stop.is_set():
                a.mutate(set_nquads=f'_:w{i} <name> "w{i}" .\n'
                                    f'_:w{i} <friend> <3> .')
                i += 1
                mutated[0] = i
                time.sleep(0.02)

        loader = _threading.Thread(target=live_load, daemon=True)
        loader.start()

        def run(q: str, kind: str):
            t = time.perf_counter()
            try:
                a.query(q)
                us = (time.perf_counter() - t) * 1e6
                with lock:
                    results["ok"][kind] += 1
                    if kind == "cheap":
                        results["us"].append(us)
            except ServerOverloaded:
                with lock:
                    results["shed"][kind] += 1

        threads = []

        def submit(q, kind):
            th = _threading.Thread(target=run, args=(q, kind))
            th.start()
            threads.append(th)

        lane = adm.lanes["read"]

        def lane_state():
            with lane.lock:
                return lane.inflight, len(lane.waiters)

        def wait_for(pred, timeout=10.0):
            end = time.monotonic() + timeout
            while time.monotonic() < end:
                if pred():
                    return True
                time.sleep(0.002)
            return False

        submit(grind, "expensive")
        wait_for(lambda: lane_state()[0] >= 1)
        for _ in range(3):
            submit(grind, "expensive")
        wait_for(lambda: lane_state()[1] >= 3)
        for q in qs[6:]:  # 8 small reads: literal + uid-form seeds
            submit(q, "cheap")
        for th in threads:
            th.join(60)
    finally:
        stop.set()
        costprior.PRIORS.sample_floor = floor0
    adm_lats = sorted(results["us"])
    sheds = results["shed"]["cheap"] + results["shed"]["expensive"]
    n, m = len(lats), len(adm_lats)
    return {
        "stage": "graphrag", "secs": round(time.perf_counter() - t0, 2),
        "queries": n, "nodes": GRAPHRAG_N, "dim": GRAPHRAG_DIM,
        # unloaded digest pass: the fused-knn serving headline
        "serve_p50_us": round(lats[n // 2]),
        "serve_p99_us": round(lats[min(n - 1, int(n * 0.99))]),
        "launches_per_query": round(launches / max(w_n, 1), 2),
        "digest": digest.hexdigest(),
        "identical_reps": len(set(rep_digests)) == 1,
        "routes": {r: METRICS.get("knn_route_total", route=r)
                   for r in ("host", "device", "mesh")},
        "fused_routes": {r: METRICS.get("fused_route_total", route=r)
                         for r in ("fused", "staged", "fallback")},
        # admission pass: deadline-bound reads under live mutations
        "admitted": results["ok"]["cheap"],
        "p50_us": round(adm_lats[m // 2]) if m else 0,
        "p99_us": round(adm_lats[min(m - 1, int(m * 0.99))]) if m else 0,
        "shed_cheap": results["shed"]["cheap"],
        "shed_expensive": results["shed"]["expensive"],
        "shed_precision": (results["shed"]["expensive"] / sheds
                           if sheds else None),
        "live_mutations": mutated[0],
    }


def _featprop_fixture():
    """Fixed-seed feature-traversal store: every node carries an `emb`
    vector (small integer components — sums exactly representable, so
    host/device/mesh aggregate bit-identically) plus Zipfian `friend`
    edges. Returns (alpha, query mix) where the mix covers all three
    aggregators composed with @recurse and with similar_to seeds."""
    from dgraph_tpu.server.api import Alpha

    a = Alpha(device_threshold=0)  # device kernels at every level —
    # the hop chain the fused featprop stage collapses is the claim
    a.alter("emb: float32vector @dim(%d) .\n"
            "friend: [uid] @reverse .\n"
            "name: string @index(exact) ." % FEATPROP_DIM)
    rng = np.random.default_rng(31)
    lines = []
    for i in range(1, FEATPROP_N + 1):
        v = rng.integers(0, 7, FEATPROP_DIM)
        lines.append('<%d> <emb> "[%s]" .'
                     % (i, ", ".join(str(int(x)) for x in v)))
        lines.append(f'<{i}> <name> "p{i % 13}" .')
        for j in rng.zipf(1.4, 5):  # Zipf targets: low uids are hubs
            t = int(min(j, FEATPROP_N))
            if t != i:
                lines.append(f"<{i}> <friend> <{t}> .")
    a.mutate(set_nquads="\n".join(lines))
    qs = []
    for agg in ("sum", "mean", "max"):  # vector-literal seeds, each agg
        for _ in range(3):
            v = rng.integers(0, 7, FEATPROP_DIM)
            lit = "[%s]" % ", ".join(str(int(x)) for x in v)
            k = int(rng.integers(3, 9))
            qs.append('{ q(func: similar_to(emb, %d, "%s")) '
                      '@recurse(depth: 2) @msgpass(pred: emb, agg: %s) '
                      '{ uid friend } }' % (k, lit, agg))
    for _ in range(4):  # uid seeds over the Zipfian hot set, deeper
        u = int(min(rng.zipf(1.5), FEATPROP_N))
        agg = ("sum", "mean", "max")[u % 3]
        qs.append('{ q(func: uid(%d)) @recurse(depth: 3) '
                  '@msgpass(pred: emb, agg: %s) { uid friend } }'
                  % (u, agg))
    return a, qs


def featprop_stage() -> dict:
    """Feature-bearing traversal (ISSUE 19): the fixed-seed @msgpass
    mix over similar_to/uid seeds — a digest pass pins bit-identity
    across reps, launches/query shows the fused featprop collapse, and
    the throughput pair the compare gate watches is feature_bytes/s
    (aggregated neighbour-feature traffic) alongside edges/s."""
    import hashlib

    from dgraph_tpu.utils import costprofile
    from dgraph_tpu.utils.metrics import METRICS

    t0 = time.perf_counter()
    a, qs = _featprop_fixture()
    for q in qs:  # warm: parse caches + fused compiles stay out
        a.query(q)
        a.query(q)
    costprofile.reset()
    bytes0 = METRICS.get("feat_bytes_total")
    edge_paths = ("numpy", "device", "mesh", "remote", "empty", "fused")
    edges0 = sum(METRICS.get("edges_traversed_total", path=p)
                 for p in edge_paths)
    digest = hashlib.sha256()
    rep_digests, lats = [], []
    tm0 = time.perf_counter()
    for _ in range(FEATPROP_REPS):
        rep = hashlib.sha256()
        for q in qs:
            t = time.perf_counter()
            raw = a.query_raw(q)
            lats.append((time.perf_counter() - t) * 1e6)
            digest.update(raw)
            rep.update(raw)
        rep_digests.append(rep.hexdigest())
    elapsed = time.perf_counter() - tm0
    lats.sort()
    feat_bytes = METRICS.get("feat_bytes_total") - bytes0
    edges = sum(METRICS.get("edges_traversed_total", path=p)
                for p in edge_paths) - edges0
    launches = w_n = 0.0
    for st in costprofile.summary(top_n=64)["shapes"].values():
        launches += st.get("features", {}).get(
            "kernel_launches", 0) * st["count"]
        w_n += st["count"]
    n = len(lats)
    return {
        "stage": "featprop", "secs": round(time.perf_counter() - t0, 2),
        "queries": n, "nodes": FEATPROP_N, "dim": FEATPROP_DIM,
        "serve_p50_us": round(lats[n // 2]),
        "serve_p99_us": round(lats[min(n - 1, int(n * 0.99))]),
        "launches_per_query": round(launches / max(w_n, 1), 2),
        # the watched throughput pair: aggregated feature traffic and
        # the raw edge walk it rode on, over the same timed pass
        "feature_bytes_per_s": round(feat_bytes / max(elapsed, 1e-9)),
        "edges_per_s": round(edges / max(elapsed, 1e-9)),
        "digest": digest.hexdigest(),
        "identical_reps": len(set(rep_digests)) == 1,
        "routes": {r: METRICS.get("feat_route_total", route=r)
                   for r in ("host", "device", "mesh", "fused")},
        "fused_routes": {r: METRICS.get("fused_route_total", route=r)
                         for r in ("fused", "staged", "fallback")},
    }


def maintenance_stage() -> dict:
    """Pause-impact telemetry (ISSUE 3): serve a query mix against an
    out-of-core store while the background scheduler streams rollups +
    checkpoints, and report the latency penalty maintenance imposes —
    median and p99 with maintenance idle vs active, plus the scheduler's
    own job/pause counters out of the shared registry."""
    import shutil
    import statistics
    import tempfile

    from dgraph_tpu.server.api import Alpha
    from dgraph_tpu.utils.metrics import METRICS

    t0 = time.perf_counter()
    rng = np.random.default_rng(13)
    seed_alpha = Alpha(device_threshold=10**9)
    seed_alpha.alter("name: string @index(exact) .\n"
                     "follows: [uid] @reverse .\nknows: [uid] @reverse .")
    lines = [f'_:p{i} <name> "p{i}" .' for i in range(MAINT_N)]
    for pred in ("follows", "knows"):
        for i in range(MAINT_N):
            for j in rng.choice(MAINT_N, 10, replace=False):
                if i != j:
                    lines.append(f"_:p{i} <{pred}> _:p{j} .")
    seed_alpha.mutate(set_nquads="\n".join(lines))
    workdir = tempfile.mkdtemp(prefix="bench_maint_")
    p_dir = os.path.join(workdir, "p")
    seed_alpha.checkpoint_to(p_dir)
    from dgraph_tpu.store import checkpoint as _ckpt
    resolved = _ckpt.resolve(p_dir)
    disk = sum(os.path.getsize(os.path.join(resolved, f))
               for f in os.listdir(resolved))
    alpha = Alpha.open(p_dir, device_threshold=10**9, sync=False,
                       memory_budget=disk // 3)

    mix = ['{ q(func: eq(name, "p7")) { name follows { name } } }',
           '{ q(func: eq(name, "p11")) { knows { name } } }',
           '{ q(func: eq(name, "p3")) { follows { ~follows '
           '(first: 3) { name } } } }']

    def measure(seconds: float) -> list[float]:
        lats, i, end = [], 0, time.perf_counter() + seconds
        while time.perf_counter() < end:
            t = time.perf_counter()
            alpha.query(mix[i % len(mix)])
            lats.append((time.perf_counter() - t) * 1e6)
            i += 1
        return lats

    idle = measure(3.0)
    jobs0 = sum(v for k, v in METRICS.snapshot()["counters"].items()
                if k.startswith("maintenance_jobs_total"))
    sched = alpha.attach_maintenance(p_dir, rollup_after=2,
                                     checkpoint_every_s=0.5,
                                     pacing_ms=1)
    stop = threading.Event()

    def write_load():
        i = 0
        while not stop.is_set():
            alpha.mutate(set_nquads=f'_:w{i} <name> "w{i}" .')
            i += 1
            time.sleep(0.02)

    w = threading.Thread(target=write_load, daemon=True)
    w.start()
    during = measure(5.0)
    stop.set()
    w.join()
    sched.stop(drain=True)
    snap = METRICS.snapshot()["counters"]
    jobs = sum(v for k, v in snap.items()
               if k.startswith("maintenance_jobs_total")) - jobs0
    shutil.rmtree(workdir, ignore_errors=True)

    def pcts(lats):
        lats = sorted(lats)
        return {"p50_us": round(statistics.median(lats)),
                "p99_us": round(lats[min(len(lats) - 1,
                                         int(len(lats) * 0.99))])}

    i_p, d_p = pcts(idle), pcts(during)
    # shape-keyed cost records of the served mix (the cost-model
    # dataset the stage just generated): per-shape percentiles + the
    # most expensive shapes, out of the same aggregator /debug/costs
    # serves in a server process — bench and serving records merge
    from dgraph_tpu.utils import costprofile
    return {"stage": "maintenance",
            "secs": round(time.perf_counter() - t0, 2),
            "queries_idle": len(idle), "queries_during": len(during),
            "idle": i_p, "during": d_p,
            "pause_impact_p50": round(d_p["p50_us"] /
                                      max(i_p["p50_us"], 1), 3),
            "pause_impact_p99": round(d_p["p99_us"] /
                                      max(i_p["p99_us"], 1), 3),
            "maintenance_jobs": jobs,
            "pauses": snap.get("maintenance_pauses_total", 0.0),
            "evictions": snap.get("maintenance_evictions_total", 0.0),
            "cost_records": costprofile.summary(top_n=5)}


def pressure_stage() -> dict:
    """Budgeted-serving proof (ISSUE 16): serve a fixed-seed query mix
    against an out-of-core store twice — unbudgeted first (recording a
    digest per query), then with the memory governor's budgets pinned to
    HALF the measured cache footprint, so the working set is ~2× the
    budget and every fill pays the evict-to-watermark path. Reports
    p50/p99 for both passes, the eviction and OOM-retry counters the
    pressure generated, and the contract the governor exists for:
    every budgeted response digest-identical to its unbudgeted twin,
    ZERO aborted requests, resident bytes at or under budget once the
    mix drains."""
    import hashlib
    import shutil
    import statistics
    import tempfile

    from dgraph_tpu.server.api import Alpha
    from dgraph_tpu.utils import memgov
    from dgraph_tpu.utils.metrics import METRICS

    def evict_total() -> float:
        return sum(v for k, v in METRICS.snapshot()["counters"].items()
                   if k.startswith("cache_evictions_total"))

    t0 = time.perf_counter()
    rng = np.random.default_rng(23)
    seed_alpha = Alpha(device_threshold=10**9)
    seed_alpha.alter("name: string @index(exact) .\n"
                     "follows: [uid] @reverse .\nknows: [uid] @reverse .")
    lines = [f'_:p{i} <name> "p{i}" .' for i in range(MAINT_N)]
    for pred in ("follows", "knows"):
        for i in range(MAINT_N):
            for j in rng.choice(MAINT_N, 10, replace=False):
                if i != j:
                    lines.append(f"_:p{i} <{pred}> _:p{j} .")
    seed_alpha.mutate(set_nquads="\n".join(lines))
    workdir = tempfile.mkdtemp(prefix="bench_press_")
    p_dir = os.path.join(workdir, "p")
    seed_alpha.checkpoint_to(p_dir)
    alpha = Alpha.open(p_dir, device_threshold=10**9, sync=False)

    # wide fixed-seed mix: enough distinct anchors that the tablet /
    # plan / residency caches accumulate a real working set
    anchors = rng.choice(MAINT_N, 24, replace=False)
    mix = []
    for i in anchors:
        mix.append('{ q(func: eq(name, "p%d")) '
                   '{ name follows { name } } }' % i)
        mix.append('{ q(func: eq(name, "p%d")) { knows { name } '
                   'follows { ~follows (first: 3) { name } } } }' % i)

    def digest(resp) -> str:
        return hashlib.sha256(
            json.dumps(resp, sort_keys=True).encode()).hexdigest()

    def run_mix():
        """One full pass over the mix: (digests, latencies_us, aborts)."""
        digs, lats, aborts = [], [], 0
        for q in mix:
            t = time.perf_counter()
            try:
                resp = alpha.query(q)
            except Exception:  # noqa: BLE001 — an abort is the FINDING
                aborts += 1
                digs.append(None)
                continue
            lats.append((time.perf_counter() - t) * 1e6)
            digs.append(digest(resp))
        return digs, lats, aborts

    def pcts(lats):
        lats = sorted(lats)
        return {"p50_us": round(statistics.median(lats)),
                "p99_us": round(lats[min(len(lats) - 1,
                                         int(len(lats) * 0.99))])}

    # -- pass 1: unbudgeted — the digests are the ground truth, the
    # quiescent footprint is what the budget halves
    run_mix()                       # warm: compiles/fills outside timing
    want, idle_lats, idle_aborts = run_mix()
    assert idle_aborts == 0, f"{idle_aborts} aborts with NO budget set"
    st0 = memgov.GOVERNOR.status()
    budgets = {k: max(st0["budgets"][k]["resident_bytes"] // 2, 4096)
               for k in ("device", "host")}
    ev0, oom0 = evict_total(), memgov.GOVERNOR.oom_stats()

    # -- pass 2: working set ~2× budget — same mix, same digests required
    memgov.GOVERNOR.set_budgets(device_bytes=budgets["device"],
                                host_bytes=budgets["host"])
    try:
        got, press_lats, aborts = run_mix()
        got2, press_lats2, aborts2 = run_mix()
        press_lats += press_lats2
        aborts += aborts2
        # quiescent point: one synchronous pass drains any overhang the
        # last fills left between maybe_evict hooks, then residency must
        # sit within budget (or the registry must be empty-handed)
        for kind in ("device", "host"):
            memgov.GOVERNOR.evict_to_low(kind)
        st1 = memgov.GOVERNOR.status()
        resident = {k: st1["budgets"][k]["resident_bytes"]
                    for k in ("device", "host")}
    finally:
        memgov.GOVERNOR.set_budgets(0, 0)  # later stages run unbudgeted

    assert aborts == 0, f"{aborts} requests aborted under memory budget"
    mismatched = [i for i, (a, b) in enumerate(zip(want, got))
                  if a != b] + \
                 [i for i, (a, b) in enumerate(zip(want, got2)) if a != b]
    assert not mismatched, \
        f"budgeted responses diverge from unbudgeted at mix{mismatched}"
    oom1 = memgov.GOVERNOR.oom_stats()
    shutil.rmtree(workdir, ignore_errors=True)

    i_p, p_p = pcts(idle_lats), pcts(press_lats)
    return {"stage": "pressure",
            "secs": round(time.perf_counter() - t0, 2),
            "queries": len(mix) * 2, "aborts": aborts,
            "digest_match": True,
            "budget_bytes": budgets,
            "working_set_bytes": {
                k: st0["budgets"][k]["resident_bytes"]
                for k in ("device", "host")},
            "resident_after_bytes": resident,
            "within_budget": {k: resident[k] <= budgets[k]
                              for k in ("device", "host")},
            "evictions": round(evict_total() - ev0),
            "oom_retries": oom1["retries"] - oom0["retries"],
            "oom_degraded": oom1["degraded"] - oom0["degraded"],
            "unbudgeted": i_p, "pressured": p_p,
            "pressure_impact_p50": round(p_p["p50_us"] /
                                         max(i_p["p50_us"], 1), 3),
            "pressure_impact_p99": round(p_p["p99_us"] /
                                         max(i_p["p99_us"], 1), 3)}


# ---------------------------------------------------------------------------
# parent: staged child supervision

def _stage_ok(doc) -> bool:
    """A stage counts as produced only when it ran to completion — an
    error line (with its bundle path) is evidence, not a result."""
    return doc is not None and "error" not in doc


CHILD_STAGES = ("stage0", "stage1", "stage2", "maintenance", "pressure",
                "sched", "graphrag", "featprop")


def run_child_staged(expect_path: str,
                     budget_s: float) -> tuple[dict, str | None]:
    """Run the staged device child; returns (stages dict, error|None).
    Reads the child's stdout line by line so a later-stage hang still
    leaves the earlier stages' results in hand. Per-stage deadlines are
    clamped so the whole child fits in `budget_s`. The child is always
    gone when this returns: whatever the parent starts next may take
    the chip."""
    import tempfile
    errf = tempfile.NamedTemporaryFile(
        mode="w+", suffix=".benchlog", delete=False)
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child",
         expect_path],
        stdout=subprocess.PIPE, stderr=errf, text=True, cwd=ROOT)
    stages: dict[str, dict] = {}
    err = None
    t_start = time.perf_counter()
    try:
        for name in CHILD_STAGES:
            remaining = budget_s - (time.perf_counter() - t_start)
            deadline = min(STAGE_DEADLINES[name], max(remaining, 1.0))
            line = _read_line(proc, deadline)
            if line is None:
                err = (f"{name} produced no output within {deadline:.0f}s "
                       f"(rc={proc.poll()})")
                errf.flush()
                with open(errf.name) as f:
                    tail = [ln.strip() for ln in f.readlines()[-4:]
                            if ln.strip()]
                if tail:
                    err += "; child stderr: " + " | ".join(tail)
                break
            doc = json.loads(line)
            stages[doc.get("stage", name)] = doc
            log(f"  [child] {line.strip()}")
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait()
        errf.close()
        try:
            os.unlink(errf.name)
        except OSError:
            pass
    return stages, err


def _read_line(proc, timeout_s: float):
    """Blocking line read with a timeout (portable via a reader thread)."""
    result = []
    done = threading.Event()

    def reader():
        line = proc.stdout.readline()
        if line:
            result.append(line)
        done.set()

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    done.wait(timeout_s)
    return result[0] if result else None


def main() -> None:
    def last_resort():
        emit({"metric": METRIC, "value": 0, "unit": "edges/s",
              "vs_baseline": 0.0,
              "error": f"global deadline {GLOBAL_DEADLINE_S}s hit"})
        sys.stdout.flush()
        os._exit(3)

    watchdog = threading.Timer(GLOBAL_DEADLINE_S, last_resort)
    watchdog.daemon = True
    watchdog.start()
    t_main = time.perf_counter()

    t0 = time.perf_counter()
    rel = build_graph(N_NODES, AVG_DEG)
    seeds = make_seeds(N_NODES, B_DEV)
    log(f"graph: {N_NODES} nodes, {rel.nnz} edges ({time.perf_counter()-t0:.1f}s); "
        f"workload: {B_DEV} concurrent depth-{DEPTH} recurses")

    # -- CPU baseline: ALL B queries measured directly ----------------------
    t0 = time.perf_counter()
    cpu_edges = np.array([cpu_recurse(rel.indptr, rel.indices, s, DEPTH)
                          for s in seeds], np.int64)
    cpu_s = time.perf_counter() - t0
    total_edges = int(cpu_edges.sum())
    cpu_eps = total_edges / cpu_s
    log(f"cpu baseline: {B_DEV} queries, {total_edges} edges in "
        f"{cpu_s:.2f}s = {cpu_eps:,.0f} edges/s")

    expect_path = os.path.join(ROOT, ".bench_expect.npz")
    np.savez(expect_path, edges=cpu_edges)

    # the parent must reach this point without a jax backend: a process
    # that has touched jax holds the chip, and the children need it
    xb = sys.modules.get("jax._src.xla_bridge")
    assert xb is None or not xb.backends_are_initialized(), \
        "bench parent initialised a jax backend before its children"

    # one process on the chip at a time: the device child first; only
    # after it has exited, the mesh points and the fused arms, each its
    # own child of THIS process, sequentially. Whatever device the
    # environment gives is the device measured — there is no re-run on
    # another backend.
    def left() -> float:
        return GLOBAL_DEADLINE_S - (time.perf_counter() - t_main) - 15.0

    reserve = (len(MESH_STAGE_DEVICES) * MESH_CHILD_TIMEOUT_S
               + 2 * FUSED_CHILD_TIMEOUT_S) / 2
    stages, err = run_child_staged(expect_path, left() - reserve)
    if left() > 30:
        stages["mesh"] = mesh_stage()
    if left() > 30:
        stages["fused_ab"] = fused_ab_stage()
    for name in ("mesh", "fused_ab"):
        if name not in stages:
            stages[name] = {"stage": name, "error":
                            "not run: the global deadline was spent"}
        log(f"  [parent] {json.dumps(stages[name])}")
    dev = {k: stages.get("stage0", {}).get(k)
           for k in ("platform", "device_kind", "n_devices")}

    out = {"metric": METRIC, "unit": "edges/s", **dev,
           "cpu_edges_per_sec": round(cpu_eps),
           "stages": {k: v for k, v in stages.items()}}
    # flight-recorder evidence (ISSUE 13): every bundle a stage left —
    # error-path dumps and watchdog convictions alike — is named in
    # the BENCH JSON so a dead/stalled stage is diagnosable offline
    bundles = sorted(
        {doc["bundle"] for doc in stages.values() if doc.get("bundle")}
        | {p for doc in stages.values()
           for p in doc.get("flight_dumps", ())})
    if bundles:
        out["flight_dumps"] = bundles
    s2 = stages.get("stage2")
    if _stage_ok(s2):
        b = s2["B"]
        dev_total = s2["total_edges"]
        dev_eps = dev_total / s2["dev_s"]
        # baseline at the SAME concurrency (per-query numpy cost is
        # B-independent; measured counts prove identical work)
        base_eps = (cpu_edges[:b].sum() / cpu_s * (len(cpu_edges) / b)
                    if b != len(cpu_edges) else cpu_eps)
        out.update(value=round(dev_eps),
                   vs_baseline=round(dev_eps / base_eps, 2),
                   telemetry=s2.get("telemetry", {}))
        # roofline fields exist only where stage2 could name the
        # device's published peak (never on a CPU run)
        out.update({k: s2[k] for k in ("hbm_gbps", "hbm_frac_of_peak")
                    if k in s2})
        sm = stages.get("maintenance")
        if sm is not None and "error" not in sm:
            # pause-impact of background rollup+checkpoint on the serving
            # path (ISSUE 3 maintenance stage)
            out["maintenance"] = {k: sm[k] for k in
                                  ("pause_impact_p50", "pause_impact_p99",
                                   "maintenance_jobs", "pauses")
                                  if k in sm}
    elif _stage_ok(stages.get("stage1")):
        s1 = stages["stage1"]
        out.update(value=s1["edges_per_sec"], vs_baseline=0.0,
                   error=(err or "") + "; value is the SMALL-graph stage1 "
                   "number (stage2 did not complete)")
    else:
        out.update(value=0, vs_baseline=0.0, error=err)
    if err and "error" not in out:
        out["error"] = err
    # a stage that ran and left an `error` (its own, or a mesh point's
    # or fused arm's folded into it) fails the run: the JSON line is
    # still printed, the exit code says it is not a result
    stage_errors = {name: doc["error"] for name, doc in stages.items()
                    if isinstance(doc, dict) and doc.get("error")}
    if stage_errors:
        out["stage_errors"] = stage_errors
    failed = bool(err or stage_errors or out.get("error"))
    # cost-record summary (ISSUE 8): the maintenance stage's served mix
    # is the child's cost dataset; an absent stage reports the (empty)
    # parent aggregate rather than dropping the key
    sm_costs = (stages.get("maintenance") or {}).get("cost_records")
    if sm_costs is not None:
        out["cost_records"] = sm_costs
    else:
        from dgraph_tpu.utils import costprofile
        out["cost_records"] = costprofile.summary(top_n=5)
    # cost-prior scheduling headline (ISSUE 9): priors on vs off on the
    # mixed workload — cheap p50/p99, shed precision, prior fit, pack
    # imbalance — straight off the child's sched stage
    ss = stages.get("sched")
    if ss is not None and "error" not in ss:
        out["sched"] = {k: ss[k] for k in
                        ("priors_on", "priors_off", "prior_fit",
                         "pack_imbalance") if k in ss}
        # retained-history digest + SLO verdicts over the sched stage's
        # traffic (ISSUE 17) — the bench-compare gate and dashboards
        # read these top-level
        if ss.get("timeseries"):
            out["timeseries"] = ss["timeseries"]
        if ss.get("slo"):
            out["slo"] = ss["slo"]
    # mesh-sharded serving scaling (ISSUE 10): edges/s per device count,
    # 4-vs-1 scaling + efficiency, shard balance, reshard counter —
    # straight off the child's mesh stage
    sme = stages.get("mesh")
    if sme is not None and "error" not in sme:
        out["mesh"] = {k: sme[k] for k in
                       ("devices", "scaling_4v1", "efficiency_4",
                        "resharded") if k in sme}
    # GraphRAG retrieval serving (ISSUE 18): deadline-bound similar_to
    # + @recurse p50/p99 under admission, shed precision, fused-knn
    # launches/query, and the fixed-seed response digest — the
    # bench-compare gate watches all four numbers direction-aware
    sg = stages.get("graphrag")
    if sg is not None and "error" not in sg:
        out["graphrag"] = {k: sg[k] for k in
                           ("p50_us", "p99_us", "serve_p50_us",
                            "serve_p99_us", "shed_precision",
                            "launches_per_query", "digest",
                            "identical_reps", "routes")
                           if k in sg and sg[k] is not None}
    # feature traversal (ISSUE 19): @msgpass propagation throughput —
    # feature_bytes/s (higher-better watched key) alongside edges/s,
    # the fused featprop launches/query, and the fixed-seed digest
    sf = stages.get("featprop")
    if sf is not None and "error" not in sf:
        out["featprop"] = {k: sf[k] for k in
                           ("serve_p50_us", "serve_p99_us",
                            "feature_bytes_per_s", "edges_per_s",
                            "launches_per_query", "digest",
                            "identical_reps", "routes")
                           if k in sf and sf[k] is not None}
    # whole-query fusion A/B (ISSUE 15): the two arms' headline
    sfa = stages.get("fused_ab")
    if sfa is not None and "error" not in sfa:
        out["fused_ab"] = {k: sfa[k] for k in
                           ("identical", "p50_speedup",
                            "launch_collapse") if k in sfa}
    # cross-node trace health (ISSUE 14): per-node span counts +
    # propagated-trace fraction off the mesh/sched stages
    fleet = {name: doc["fleet"] for name, doc in
             (("mesh", sme), ("sched", ss)) if isinstance(doc, dict)
             and doc.get("fleet")}
    if fleet:
        out["fleet"] = fleet
    out["lint"] = lint_stage()
    emit(out)
    watchdog.cancel()
    sys.stdout.flush()
    os._exit(1 if failed else 0)


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        child_main(sys.argv[2] if len(sys.argv) > 2
                   else os.path.join(ROOT, ".bench_expect.npz"))
    elif len(sys.argv) >= 3 and sys.argv[1] == "--mesh-child":
        mesh_child_main(int(sys.argv[2]))
    elif len(sys.argv) >= 2 and sys.argv[1] == "--fused-child":
        fused_child_main()
    else:
        main()
