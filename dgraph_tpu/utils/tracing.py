"""Tracing: per-request trace ids, per-hop spans + device profiling.

Reference parity: OpenCensus spans around each `ProcessTaskOverNetwork`
leg with Jaeger export (SURVEY §5). TPU equivalent: lightweight in-process
spans (queryable ring buffer + per-trace index, served by
`/debug/traces` and — as Chrome trace-event JSON, Perfetto-loadable —
`/debug/events`) and on-demand `jax.profiler` captures (`profile_start`/
`profile_stop`, POST /debug/profile; `--trace_dir` is their default
directory). A span is the one clock of the served path, with three
outlets: the ring, `phase_us{span=,endpoint=}` for the spans flagged as
phases of a request (what Prometheus and the benchmark read), and —
while a capture runs — a `jax.profiler.TraceAnnotation`, so the program's
spans lie in the `.xplane.pb` on the device trace's clock and name its
idle gaps. A span times the host: one that must cover device work waits
for it inside (`block_until_ready`, as `batch.device_wait` does).

What the thread was doing (ISSUE 40): a phase and a request's root span
also read the thread's CPU clocks at open and close, so a span knows how
much of its wall time its thread ran, in user and in system mode
(`Span.cpu_us`, `Span.sys_us`; `phase_cpu_us_total`,
`phase_offcpu_us_total`, and on the root `request_ctx_switches_total`,
`request_page_faults_total`, `request_other_cpu_us_total`). The total is
`time.thread_time_ns()`, which Linux keeps to the nanosecond; the
user/system split is the kernel's, sampled by ticks, applied to that
total: sound as a window's sum, not for one phase of one request. (A
sandbox kernel may count the total in ticks too, 10 ms on the chip's
machine: every number here is then a window's sum and no more, and
`_bill_thread` carries what a tick overdraws so that the sums hold.) What
takes a thread off its CPU is counted where it happens: a collector
pause by the `gc.callbacks` hook that `arm()` installs (`gc_pause_us`,
and a `gc.collect` span for a pause of a millisecond or more), a tick
of one of the program's daemons by `background(name)`.

Identity model: every span gets a process-unique integer `span_id`;
nesting is a thread-local STACK of span ids, so concurrent (or nested)
spans that share a name can never alias each other — the historical
name-keyed parent tracking did exactly that. A span belongs to the
trace id established by the enclosing `trace()` context (one per
request on the serving path); spans opened outside any trace carry
trace_id "" and only live in the ring buffer.

`set_enabled(False)` turns span recording into a near-no-op (one flag
check) — the observability layer must never become the regression
(tier-1 guards the query-path overhead at <5%). The cost-profile fields
fed from spans (`plan_us`, `execute_us`, `build_us`) read 0 while
disarmed.
"""

from __future__ import annotations

import contextlib
import gc
import itertools
import os
import threading
import time
import uuid
from collections import OrderedDict, deque
from dataclasses import dataclass, field

from dgraph_tpu.utils import locks
from dgraph_tpu.utils.metrics import METRICS

try:
    import resource
    _RUSAGE_THREAD = resource.RUSAGE_THREAD
except (ImportError, AttributeError):   # no per-thread usage here
    resource = None

_TRACE_DIR: str | None = None
_BUF: deque = deque(maxlen=4096)
_TRACES: "OrderedDict[str, list]" = OrderedDict()
_MAX_TRACES = 256          # retained per-trace span lists
_MAX_TRACE_SPANS = 4096    # spans retained per trace
_LOCK = locks.make_lock("tracing.registry")
_TLS = threading.local()
# span ids must stay unique when spans from SEVERAL processes merge into
# one trace (cross-process propagation, /debug/fleet): the counter is
# salted with the pid in the high bits, so a worker span's parent_id
# (a coordinator-issued id forwarded over gRPC metadata) can never
# collide with a locally-issued id. CPython: count.__next__ is atomic.
_PID = os.getpid()
_IDS = itertools.count(((_PID & 0xFFFF) << 40) | 1)
_ENABLED = True
_SINKS: list = []          # live-export subscribers (utils/push.py)
# cross-process trace-health counters (the bench "fleet" block):
# spans recorded, and spans recorded under a PROPAGATED (attach'd)
# trace context — both under _LOCK with the registries
_STAT = {"spans": 0, "propagated": 0}


@dataclass
class Span:
    name: str
    span_id: int = 0
    parent_id: int = 0          # 0 = root of its thread's stack
    trace_id: str = ""          # "" = outside any trace() context
    start_us: int = 0           # wall-clock epoch µs (Chrome `ts`)
    dur_us: int = 0
    tid: int = 0                # OS thread id (Chrome track)
    pid: int = 0                # OS process id (Chrome process row)
    attrs: dict = field(default_factory=dict)
    # of dur_us, what the span's thread spent on a CPU, in user and in
    # system mode: phases and request roots only, 0 on every other span
    cpu_us: int = 0
    sys_us: int = 0

    def to_dict(self) -> dict:
        return {"name": self.name, "span_id": self.span_id,
                "parent_id": self.parent_id, "trace_id": self.trace_id,
                "start_us": self.start_us, "dur_us": self.dur_us,
                "tid": self.tid, "pid": self.pid,
                "attrs": dict(self.attrs),
                "cpu_us": self.cpu_us, "sys_us": self.sys_us}


# reused sink for disabled spans: callers may still write attrs into it
_NULL_SPAN = Span(name="")


def set_enabled(flag: bool) -> None:
    """Globally arm/disarm span recording (metrics have their own
    switch). Disabled spans cost one attribute load per enter/exit."""
    global _ENABLED
    _ENABLED = bool(flag)


def enabled() -> bool:
    return _ENABLED


def enable_device_trace(trace_dir: str) -> None:
    """Set `profile_start`'s default capture directory (`--trace_dir`)."""
    global _TRACE_DIR
    _TRACE_DIR = trace_dir


# -- on-demand device profiling (POST /debug/profile) ------------------------
# jax.profiler trace capture is process-global and NOT reentrant:
# start/stop are single-flight behind a lock, so two operators hitting
# /debug/profile concurrently can never corrupt a capture.
_PROFILE_LOCK = locks.make_lock("tracing.profile")
_PROFILE_DIR: str | None = None
PROFILE_STOP_MARKER = "tracing.profile.stop_trace"


def profile_start(trace_dir: str | None = None) -> str:
    """Start a jax.profiler trace capture under `trace_dir` (default:
    the dir `enable_device_trace`/`--trace_dir` armed). Raises when no
    dir is configured or a capture is already running (single-flight).
    Returns the capture dir."""
    global _PROFILE_DIR
    d = trace_dir or _TRACE_DIR
    if not d:
        raise ValueError("no trace dir configured — start the server "
                         "with --trace_dir or pass {\"dir\": ...}")
    with _PROFILE_LOCK:
        if _PROFILE_DIR is not None:
            raise RuntimeError(
                f"a device profile is already capturing under "
                f"{_PROFILE_DIR} — stop it first (single-flight)")
        import jax
        # no Python tracer: its frames were 20 MB for 4 s, slowed a batch
        # by 0.12 s and made the stop hold the interpreter 0.4-1.6 s
        # (PERF.md, PR 23). XLA's own host events stay (host tracer level
        # at its default), and the program's spans annotate themselves.
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        _PROFILE_DIR = d
        METRICS.inc("device_profile_captures_total", outcome="started")
        return d


def profile_stop() -> str:
    """Stop the running capture and return its dir; the XLA-level
    timeline lands under `<dir>/plugins/profile/` (Perfetto/
    TensorBoard-loadable)."""
    global _PROFILE_DIR
    with _PROFILE_LOCK:
        if _PROFILE_DIR is None:
            raise RuntimeError("no device profile is running")
        d, _PROFILE_DIR = _PROFILE_DIR, None
        import jax
        try:
            # the capture's last host event: a reducer ends the traced
            # window where the profiler's own teardown begins (the
            # benchmark's `trace_reduce.STOP_EVENT` matches the suffix)
            with jax.profiler.TraceAnnotation(PROFILE_STOP_MARKER):
                pass
            jax.profiler.stop_trace()
        except Exception:
            METRICS.inc("device_profile_captures_total",
                        outcome="error")
            raise
        METRICS.inc("device_profile_captures_total", outcome="ok")
        return d


def profile_status() -> dict:
    with _PROFILE_LOCK:
        return {"running": _PROFILE_DIR is not None,
                "dir": _PROFILE_DIR}


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


def current_trace_id() -> str:
    return getattr(_TLS, "trace_id", "")


def current_span_id() -> int:
    """The innermost open span's id on this thread (0 = none) — what an
    outbound RPC forwards as the remote child's parent id."""
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else 0


@contextlib.contextmanager
def attach(trace_id: str, parent_id: int = 0):
    """Re-establish a PROPAGATED trace context on this thread: spans
    opened inside index under `trace_id`, and (when `parent_id` is
    given) parent to that FOREIGN span id — so a worker-side handler's
    spans become genuine children of the coordinator's request trace,
    and a maintenance job joins the admin request that triggered it.
    Empty `trace_id` is a no-op (the common un-traced RPC path)."""
    if not trace_id:
        yield
        return
    METRICS.inc("trace_propagated_total")
    prev = getattr(_TLS, "trace_id", "")
    _TLS.trace_id = trace_id
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    pushed = bool(parent_id)
    if pushed:
        stack.append(parent_id)
    _TLS.attach_depth = getattr(_TLS, "attach_depth", 0) + 1
    try:
        yield
    finally:
        _TLS.attach_depth -= 1
        if pushed:
            stack.pop()
        _TLS.trace_id = prev


@contextlib.contextmanager
def trace(name: str = "request", trace_id: str | None = None,
          endpoint: str = "", **attrs):
    """Establish a trace context: every span opened on this thread while
    inside (the root `name` span included) is indexed under the yielded
    trace id — the id the serving path echoes to clients and
    `/debug/traces?trace_id=` resolves. `endpoint` names the request's
    entry point for the phases inside (`phase_us{endpoint=}`). The root
    span reads the thread's clocks like a phase, and feeds the
    `request_*{endpoint=}` series."""
    tid = trace_id or new_trace_id()
    prev = getattr(_TLS, "trace_id", ""), getattr(_TLS, "endpoint", "")
    _TLS.trace_id, _TLS.endpoint = tid, endpoint
    try:
        with _open(name, False, True, attrs):
            yield tid
    finally:
        _TLS.trace_id, _TLS.endpoint = prev


def span(name: str, phase: bool = False, **attrs):
    """Time a region; nests via a thread-local stack of span IDS (names
    never participate in parent tracking — same-name spans, nested or
    concurrent, stay distinct). Yields the Span so callers can attach
    attrs discovered mid-region (edge counts, chosen code path); its
    `dur_us` is set when the region closes.

    `phase=True` marks one of the served path's named phases: on close
    the span also feeds `phase_us{span=<name>, endpoint=<the enclosing
    trace()'s endpoint, "" outside a request>}`, and beside it what its
    thread was doing: `phase_cpu_us_total{mode=user|sys}` and
    `phase_offcpu_us_total`, the wall time the thread held the phase
    open and ran nowhere. Flag only spans opened a constant number of
    times per request, under names fixed in code: the label sets must
    stay under the registry's cap, and the thread's clocks are read
    twice a phase.

    While a profiler capture runs, every span is also entered as a
    `jax.profiler.TraceAnnotation`; with none running that costs one
    module-global load.
    """
    return _open(name, phase, False, attrs)


def _thread_clocks() -> tuple:
    """One reading of what the calling thread has used so far: (thread
    CPU ns, process CPU ns, user µs, system µs, voluntary and
    involuntary context switches, minor and major page faults). The one
    place the clocks are read: at the open and the close of a phase or
    a root, and nowhere else."""
    if resource is None:
        return (time.thread_time_ns(), time.process_time_ns(),
                0, 0, 0, 0, 0, 0)
    ru = resource.getrusage(_RUSAGE_THREAD)
    return (time.thread_time_ns(), time.process_time_ns(),
            int(ru.ru_utime * 1e6), int(ru.ru_stime * 1e6),
            ru.ru_nvcsw, ru.ru_nivcsw, ru.ru_minflt, ru.ru_majflt)


_OFFCPU_OWED: dict = {}     # (span, endpoint) -> µs billed beyond wall
# span x endpoint x mode: two series for every one of `phase_us`
METRICS.set_label_limit("phase_cpu_us_total", 2 * METRICS.max_label_sets)


def _bill_thread(s: Span, c0: tuple, c1: tuple, phase: bool,
                 root: bool) -> None:
    """Set a closed span's CPU fields from two readings of the thread's
    clocks and feed a phase's and a root's series, `phase_us` first."""
    total = (c1[0] - c0[0]) // 1000
    user, sys_ = c1[2] - c0[2], c1[3] - c0[3]
    # the kernel's user/system split is sampled by ticks, its total as
    # a rule is not: the split's ratio over the span, applied to the
    # total (all user where no tick fell inside)
    s.sys_us = total * sys_ // (user + sys_) if sys_ > 0 else 0
    s.cpu_us = total - s.sys_us
    endpoint = getattr(_TLS, "endpoint", "")
    if phase:
        METRICS.observe("phase_us", s.dur_us, span=s.name,
                        endpoint=endpoint)
        METRICS.inc("phase_cpu_us_total", s.cpu_us, span=s.name,
                    endpoint=endpoint, mode="user")
        METRICS.inc("phase_cpu_us_total", s.sys_us, span=s.name,
                    endpoint=endpoint, mode="sys")
        # where the thread's clock ticks coarser than a phase lasts, the
        # phase a tick lands in is billed all of it: what that overdraws
        # is owed by the series' next spans, so a window's sum stays
        # wall less CPU where one span's cannot (nothing is ever owed
        # under a clock that counts nanoseconds)
        key = (s.name, endpoint)
        with _LOCK:
            off = s.dur_us - total - _OFFCPU_OWED.get(key, 0)
            _OFFCPU_OWED[key] = max(-off, 0)
        METRICS.inc("phase_offcpu_us_total", max(off, 0), span=s.name,
                    endpoint=endpoint)
    vol, invol, minor, major = (b - a for a, b in zip(c0[4:], c1[4:]))
    for key, v in (("ctx_vol", vol), ("ctx_invol", invol),
                   ("minflt", minor), ("majflt", major)):
        if v:
            s.attrs[key] = v
    if root:
        METRICS.inc("request_ctx_switches_total", vol, endpoint=endpoint,
                    kind="voluntary")
        METRICS.inc("request_ctx_switches_total", invol,
                    endpoint=endpoint, kind="involuntary")
        METRICS.inc("request_page_faults_total", minor, endpoint=endpoint,
                    kind="minor")
        METRICS.inc("request_page_faults_total", major, endpoint=endpoint,
                    kind="major")
        # what every OTHER thread of the process (daemons, XLA's
        # runtime, gRPC) burnt while this request was open
        METRICS.inc("request_other_cpu_us_total",
                    max((c1[1] - c0[1]) // 1000 - total, 0),
                    endpoint=endpoint)


@contextlib.contextmanager
def _open(name: str, phase: bool, root: bool, attrs: dict):
    if not _ENABLED:
        yield _NULL_SPAN
        return
    sid = next(_IDS)
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    s = Span(name=name, span_id=sid,
             parent_id=stack[-1] if stack else 0,
             trace_id=getattr(_TLS, "trace_id", ""),
             # graftlint: allow(wall-clock): span start is an EPOCH timestamp —
             # Perfetto/OTLP exports align traces across processes by wall clock
             start_us=int(time.time() * 1e6),
             tid=threading.get_ident(), pid=_PID, attrs=attrs)
    stack.append(sid)
    ann = None
    if _PROFILE_DIR is not None:
        import jax
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
    clocks = _thread_clocks() if phase or root else None
    t0 = time.perf_counter()
    try:
        yield s
    finally:
        s.dur_us = int((time.perf_counter() - t0) * 1e6)
        if clocks is not None:
            _bill_thread(s, clocks, _thread_clocks(), phase, root)
        if ann is not None:
            ann.__exit__(None, None, None)
        stack.pop()
        _record(s)


def _record(s: Span) -> None:
    """A closed span, and before it the pauses the collector's hook
    left, into the ring, their traces' lists and the sinks."""
    if _GC_PENDING:
        _flush_gc()
    propagated = getattr(_TLS, "attach_depth", 0) > 0
    with _LOCK:
        _STAT["spans"] += 1
        if propagated:
            _STAT["propagated"] += 1
        _BUF.append(s)
        if s.trace_id:
            spans = _TRACES.get(s.trace_id)
            if spans is None:
                spans = _TRACES[s.trace_id] = []
                while len(_TRACES) > _MAX_TRACES:
                    _TRACES.popitem(last=False)
            if len(spans) < _MAX_TRACE_SPANS:
                spans.append(s)
    if _SINKS:
        # live push (outside the lock): sinks buffer-and-return —
        # the request path never blocks on a collector
        for sink in tuple(_SINKS):
            try:
                sink(s)
            except Exception:  # noqa: BLE001 — a sink must never fail a span
                pass


# -- what takes a thread off its CPU -----------------------------------------

@contextlib.contextmanager
def background(thread: str):
    """Around ONE tick of one of the program's daemon loops: the tick
    bills its thread's CPU time to `background_cpu_us_total{thread=}`
    and counts itself in `background_ticks_total{thread=}`."""
    if not _ENABLED:
        yield
        return
    t0 = time.thread_time_ns()
    try:
        yield
    finally:
        METRICS.inc("background_cpu_us_total",
                    (time.thread_time_ns() - t0) / 1e3, thread=thread)
        METRICS.inc("background_ticks_total", thread=thread)


BACKGROUND_THREADS = ("flightrec", "timeseries", "maintenance",
                      "heartbeat", "push")
GC_SPAN_MIN_US = 1000      # a pause this long is a span, not only a count
# a collection starts wherever its thread happens to be, under any lock
# of the program: the hook takes none. It leaves the pause here, and the
# next span to close, or the next reader of the registry, books it
_GC_PENDING: deque = deque()
_GC_OPEN: list = []        # [start perf_counter, epoch µs, annotation]


def _gc_hook(phase: str, info: dict) -> None:
    """`gc.callbacks` entry: time every collection of the process, on
    the thread it runs on. Two clock reads a collection; a profiler
    capture also sees it as a `gc.collect` host event, so the device
    trace's labeller can name an idle gap it caused."""
    if phase == "start":
        if not _ENABLED:
            return
        ann = None
        if _PROFILE_DIR is not None:
            import jax
            ann = jax.profiler.TraceAnnotation("gc.collect")
            ann.__enter__()
        # graftlint: allow(wall-clock): a gc.collect span's start is an epoch timestamp like every span's
        _GC_OPEN[:] = [time.perf_counter(), int(time.time() * 1e6), ann]
        return
    if not _GC_OPEN:
        return
    t0, start_us, ann = _GC_OPEN
    del _GC_OPEN[:]
    dur_us = int((time.perf_counter() - t0) * 1e6)
    if ann is not None:
        ann.__exit__(None, None, None)
    stack = getattr(_TLS, "stack", None)
    trace_id = getattr(_TLS, "trace_id", "")
    _GC_PENDING.append(
        (info.get("generation", 0), info.get("collected", 0), dur_us,
         start_us, threading.get_ident(), stack[-1] if stack else 0,
         trace_id))


def _flush_gc() -> None:
    """Book the pauses the hook left: `gc_pause_us{gen=,on=}` for each,
    a `gc.collect` span for a long one, child of the span that was
    innermost on the collecting thread."""
    while True:
        try:
            gen, collected, dur_us, start_us, tid, parent, trace_id = \
                _GC_PENDING.popleft()
        except IndexError:
            return
        METRICS.observe("gc_pause_us", dur_us, gen=gen,
                        on="request" if trace_id else "background")
        if dur_us >= GC_SPAN_MIN_US:
            _record(Span(name="gc.collect", span_id=next(_IDS),
                         parent_id=parent, trace_id=trace_id,
                         start_us=start_us, dur_us=dur_us, tid=tid,
                         pid=_PID,
                         attrs={"gen": gen, "collected": collected}))


def arm() -> None:
    """What a serving process does once, at start (`cmd_alpha`): time
    the collector's pauses, and create at 0 the series no request
    feeds, so a window without a collection or a daemon reads 0 and
    not nothing. Not done at import: a process that only uses spans
    keeps its collector unobserved."""
    if _gc_hook not in gc.callbacks:
        gc.callbacks.append(_gc_hook)
    METRICS.add_collector(_flush_gc)
    for gen in range(3):
        for on in ("request", "background"):
            METRICS.declare_hist("gc_pause_us", gen=gen, on=on)
    for thread in BACKGROUND_THREADS:
        METRICS.inc("background_cpu_us_total", 0.0, thread=thread)
        METRICS.inc("background_ticks_total", 0.0, thread=thread)
    # the shortest launch's look-ahead (engine/batch.py): a window in
    # which no lane closed under a rule reads 0 for it
    for by in ("seed", "seed2", "ahead", "ahead2", "exhausted"):
        METRICS.inc("kernel_lanes_closed_total", 0.0, family="shortest",
                    by=by)
    METRICS.inc("kernel_near2_edges_total", 0.0)
    METRICS.inc("kernel_near2_capped_total", 0.0)
    # the out-edges the launches' pushed hops expanded (ops/bfs.py
    # _pull_or_push): a window whose every hop pulled reads 0
    for family in ("shortest", "tree"):
        METRICS.inc("kernel_push_slots_total", 0.0, family=family)


def add_sink(fn) -> None:
    """Subscribe to completed spans (the live push pipeline). Sinks run
    on the closing thread and must be non-blocking."""
    if fn not in _SINKS:
        _SINKS.append(fn)


def remove_sink(fn) -> None:
    with contextlib.suppress(ValueError):
        _SINKS.remove(fn)


def recent(n: int = 100) -> list[Span]:
    with _LOCK:
        return list(_BUF)[-n:]


def trace_spans(trace_id: str) -> list[Span]:
    """Completed spans of one trace, in completion order (children close
    before parents, so the root span is last)."""
    with _LOCK:
        return list(_TRACES.get(trace_id, ()))


def stats() -> dict:
    """Cross-process trace health: spans recorded and the fraction
    recorded under a propagated (attach'd) trace context — the bench
    "fleet" block and the /debug/fleet per-node fragments read this."""
    with _LOCK:
        spans, prop = _STAT["spans"], _STAT["propagated"]
    return {"spans_total": spans, "propagated_total": prop,
            "propagated_frac": round(prop / spans, 4) if spans else 0.0}


def to_chrome(spans: list[Span]) -> dict:
    """Chrome trace-event JSON (the `ph:"X"` complete-event form) —
    loadable in Perfetto / chrome://tracing. Span attrs ride in `args`;
    ts/dur are µs as the format requires."""
    return {
        "displayTimeUnit": "ms",
        "traceEvents": [
            {"name": s.name, "cat": "dgraph_tpu", "ph": "X",
             "ts": s.start_us, "dur": max(s.dur_us, 1),
             # each originating process is its own Perfetto process row,
             # so a merged cross-process trace renders both sides on one
             # timeline (historical spans without a pid fold under 1)
             "pid": s.pid or 1, "tid": s.tid,
             "args": {**{k: _jsonable(v) for k, v in s.attrs.items()},
                      "span_id": s.span_id, "parent_id": s.parent_id,
                      "trace_id": s.trace_id, **_cpu_args(s)}}
            for s in spans],
    }


def _cpu_args(s: Span) -> dict:
    """A measured span's CPU fields, as the exports carry them; nothing
    for a span that read no clock."""
    if not (s.cpu_us or s.sys_us):
        return {}
    return {"cpu_us": s.cpu_us, "sys_us": s.sys_us}


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


# -- OTLP/JSON export (ROADMAP: span export to an external collector) --------

def _otlp_any(v) -> dict:
    """Python value → OTLP AnyValue (the typed union OTLP mandates)."""
    if isinstance(v, bool):
        return {"boolValue": v}
    if isinstance(v, int):
        return {"intValue": str(v)}  # OTLP/JSON carries int64 as string
    if isinstance(v, float):
        return {"doubleValue": v}
    return {"stringValue": v if isinstance(v, str) else str(v)}


def _from_otlp_any(d: dict):
    if "boolValue" in d:
        return bool(d["boolValue"])
    if "intValue" in d:
        return int(d["intValue"])
    if "doubleValue" in d:
        return float(d["doubleValue"])
    return d.get("stringValue", "")


def _otlp_trace_id(tid: str) -> str:
    """Our 16-hex trace ids → the 32-hex (16-byte) ids OTLP requires.
    Left-padded with zeros; non-hex ids (tests pass arbitrary strings)
    fall back to a hex encoding of the string bytes."""
    if not tid:
        return "0" * 32
    try:
        return f"{int(tid, 16):032x}"
    except ValueError:
        return tid.encode().hex()[:32].ljust(32, "0")


def to_otlp(spans: list[Span]) -> dict:
    """OTLP/JSON (`ExportTraceServiceRequest` shape) — POSTable to any
    collector's `/v1/traces` as-is. Span ids hex-encode to the 8-byte
    spanId field; nanosecond timestamps derive from start_us + dur_us;
    attrs become typed keyValue pairs. The raw registry identifiers
    also ride as `dgraph.*` attributes so `from_otlp` round-trips
    losslessly (the round-trip test pins this)."""
    out = []
    for s in spans:
        attrs = [{"key": k, "value": _otlp_any(_jsonable(v))}
                 for k, v in s.attrs.items()]
        attrs.append({"key": "dgraph.trace_id",
                      "value": {"stringValue": s.trace_id}})
        attrs.append({"key": "dgraph.tid",
                      "value": {"intValue": str(s.tid)}})
        attrs.append({"key": "dgraph.pid",
                      "value": {"intValue": str(s.pid)}})
        attrs += [{"key": "dgraph." + k, "value": {"intValue": str(v)}}
                  for k, v in _cpu_args(s).items()]
        out.append({
            "traceId": _otlp_trace_id(s.trace_id),
            "spanId": f"{s.span_id:016x}",
            "parentSpanId": (f"{s.parent_id:016x}" if s.parent_id
                             else ""),
            "name": s.name,
            "kind": 1,  # SPAN_KIND_INTERNAL
            "startTimeUnixNano": str(s.start_us * 1000),
            "endTimeUnixNano": str((s.start_us + s.dur_us) * 1000),
            "attributes": attrs,
        })
    return {"resourceSpans": [{
        "resource": {"attributes": [
            {"key": "service.name",
             "value": {"stringValue": "dgraph_tpu"}}]},
        "scopeSpans": [{"scope": {"name": "dgraph_tpu"},
                        "spans": out}],
    }]}


def from_otlp(doc: dict) -> list[Span]:
    """Inverse of `to_otlp` (the round-trip contract): rebuild Span
    objects from an OTLP/JSON document."""
    spans = []
    for rs in doc.get("resourceSpans", ()):
        for ss in rs.get("scopeSpans", ()):
            for o in ss.get("spans", ()):
                attrs, tid, os_tid, os_pid, cpu = {}, "", 0, 0, {}
                for kv in o.get("attributes", ()):
                    v = _from_otlp_any(kv.get("value", {}))
                    if kv["key"] == "dgraph.trace_id":
                        tid = v
                    elif kv["key"] == "dgraph.tid":
                        os_tid = int(v)
                    elif kv["key"] == "dgraph.pid":
                        os_pid = int(v)
                    elif kv["key"] in ("dgraph.cpu_us", "dgraph.sys_us"):
                        cpu[kv["key"][7:]] = int(v)
                    else:
                        attrs[kv["key"]] = v
                start_us = int(o["startTimeUnixNano"]) // 1000
                spans.append(Span(
                    name=o["name"],
                    span_id=int(o["spanId"], 16),
                    parent_id=(int(o["parentSpanId"], 16)
                               if o.get("parentSpanId") else 0),
                    trace_id=tid,
                    start_us=start_us,
                    dur_us=int(o["endTimeUnixNano"]) // 1000 - start_us,
                    tid=os_tid, pid=os_pid, attrs=attrs, **cpu))
    return spans


def export_otlp(path: str, spans: list[Span] | None = None) -> int:
    """Write the span registry (default: the full ring buffer) as
    OTLP/JSON to `path` — the `--trace_export` flag's shutdown hook and
    an offline bridge to collectors. Returns the span count."""
    import json
    if spans is None:
        spans = recent(len(_BUF))
    with open(path, "w") as f:
        json.dump(to_otlp(spans), f)
    return len(spans)


def clear() -> None:
    with _LOCK:
        _BUF.clear()
        _TRACES.clear()
        _STAT["spans"] = _STAT["propagated"] = 0
