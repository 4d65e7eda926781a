"""graftlint rules R1–R6: the invariants PRs 1–5 established, as code.

Each rule is deliberately a HEURISTIC with a waiver escape hatch, not a
proof system: the goal is that breaking an invariant during a refactor
requires writing a visible, reasoned waiver instead of passing silently.

R1 hot-loop-checkpoint   while-loops in engine/, ops/, cluster/ call
                         `checkpoint()` once per iteration (PR-4).
R2 direct-io             no outbound socket/gRPC/HTTP constructors
                         outside server/task.py's Client (PR-5).
R3 wall-clock            no `time.time()` — deadline/backoff arithmetic
                         is monotonic-only (PR-4); wall clock needs a
                         reasoned waiver (external timestamps only).
R4 retry-deadline        a retry loop (sleep + broad except) must
                         exclude DEADLINE_EXCEEDED / DeadlineExceeded /
                         Cancelled from re-attempts (PR-5).
R5 metric-docs           metric names are string literals, label sets
                         are explicit kwargs (no **splat), and every
                         name has a README observability-table row
                         (subsumes the PR-4 doc-lint).
R6 jit-purity            no `.item()`/`.tolist()`/numpy host ops or
                         Python branches on tracer params inside
                         functions handed to `jax.jit`.
R7 shard-map-compat      `shard_map` resolves ONLY through
                         utils/jaxcompat.py — direct `jax.shard_map` /
                         `jax.experimental.shard_map` references
                         elsewhere scatter the one API the mesh layer
                         rides (a jax move once parked the whole
                         parallel/ layer in the failure set); with the
                         choke point, the next move is a one-file change.
R8 atomic-write          durable files under store/ (and
                         server/backup.py) land via tmp + fsync +
                         os.replace — a bare `open(..., "w"/"wb")`
                         there can tear under a kill where a reader
                         expects a whole file (ISSUE-11).

R9–R12 (lock discipline / data races) live in `guards.py` — the
Eraser-style static half of the race sanitizer (ISSUE 12).

R13 fused-host-callback  a jitted function in the fused-program layer
                         (engine/fused.py, ops/) may not call
                         costprofile/tracing/metrics/jit-accounting
                         host helpers inside the traced region — they
                         would run at TRACE time only (silent no-op on
                         cached executions) or force a host callback
                         into the one-launch program (ISSUE 15;
                         extends the R6 jit-purity facts to the fused
                         program inventory).
R14 cache-registration   byte-holding caches join the process memory
                         governor (ISSUE 16): every `Memo(...)` call
                         states its `governed=` decision explicitly,
                         and a file that grows a dict-typed `*_cache`
                         attribute must register with
                         `memgov.GOVERNOR.register` somewhere (or
                         waive with the reason its bytes are bounded)
                         — an unregistered cache is invisible to the
                         OOM evict-retry path and to /debug/memory.
R15 slo-spec             SLO names stay inside the utils/slo.SLO_SPECS
                         inventory (ISSUE 17): a literal `slo=` label
                         on a metric, a literal SLO_SPECS /
                         DEFAULT_TARGETS subscript, or a literal
                         `_evaluator("...")` registration naming an
                         objective the inventory doesn't carry would
                         split the burn-rate vocabulary — dashboards,
                         /debug/slo, and the watchdog conviction feed
                         would disagree on what objectives exist.
"""

from __future__ import annotations

import ast

from dgraph_tpu.analysis import FileContext, Finding, Rule

__all__ = ["default_rules", "HotLoopCheckpoint", "DirectIO", "WallClock",
           "RetryDeadline", "MetricDocs", "JitPurity", "ShardMapCompat",
           "FusedHostCallback", "AtomicWrite", "CacheRegistration",
           "SloSpec"]


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name for a call target: `a.b.c` or `name`;
    "" when the target is dynamic (subscript, call result, ...)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _walk_no_defs(node: ast.AST):
    """Walk a subtree without descending into nested function/class
    definitions (their bodies run in another context)."""
    todo = list(ast.iter_child_nodes(node))
    while todo:
        n = todo.pop()
        yield n
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef, ast.Lambda)):
            todo.extend(ast.iter_child_nodes(n))


# ---------------------------------------------------------------------------
class HotLoopCheckpoint(Rule):
    name = "hot-loop-checkpoint"
    doc = ("unbounded-iteration (`while`) loops on the serving path "
           "must call `deadline.checkpoint()` once per iteration so a "
           "pathological query cancels within one loop body of its "
           "budget (the PR-4 contract)")

    SCOPES = ("dgraph_tpu/engine/", "dgraph_tpu/ops/",
              "dgraph_tpu/cluster/")

    def applies(self, rel: str) -> bool:
        return rel.startswith(self.SCOPES)

    def check_file(self, ctx: FileContext) -> list[Finding]:
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.While):
                continue
            has_cp = any(
                isinstance(n, ast.Call)
                and _dotted(n.func).rsplit(".", 1)[-1]
                in ("checkpoint", "check")
                for n in ast.walk(node))
            if not has_cp:
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    "while-loop without a deadline checkpoint — call "
                    "deadline.checkpoint(stage) once per iteration, or "
                    "waive with the bound that makes it safe"))
        return out


# ---------------------------------------------------------------------------
class DirectIO(Rule):
    name = "direct-io"
    doc = ("outbound network constructors are allowed only inside "
           "server/task.py's Client — everything else must ride "
           "`Client._call` so breakers/retries/budget forwarding "
           "apply (the PR-5 contract)")

    BANNED = frozenset({
        "grpc.insecure_channel", "grpc.secure_channel",
        "socket.socket", "socket.create_connection",
        "urllib.request.urlopen", "http.client.HTTPConnection",
        "http.client.HTTPSConnection", "requests.get", "requests.post",
        "requests.put", "requests.delete", "requests.request",
        "requests.Session",
    })

    def applies(self, rel: str) -> bool:
        return (rel.startswith("dgraph_tpu/")
                and rel != "dgraph_tpu/server/task.py")

    def check_file(self, ctx: FileContext) -> list[Finding]:
        out = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                d = _dotted(node.func)
                if d in self.BANNED:
                    out.append(Finding(
                        self.name, ctx.rel, node.lineno,
                        f"direct network call {d}() outside "
                        f"server/task.py Client._call — outbound RPCs "
                        f"must ride the resilience wrapper"))
        return out


# ---------------------------------------------------------------------------
class WallClock(Rule):
    name = "wall-clock"
    doc = ("no `time.time()` in the package — deadline/backoff "
           "arithmetic uses monotonic clocks (utils/deadline.py "
           "helpers); wall clock is only for timestamps that leave "
           "the process, and says so in a waiver")

    def check_file(self, ctx: FileContext) -> list[Finding]:
        out = []
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Call)
                    and _dotted(node.func) in ("time.time",
                                               "_time.time")):
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    "wall-clock time.time() — deadline/backoff "
                    "arithmetic must use monotonic clocks "
                    "(utils/deadline.monotonic_s); waive only for "
                    "timestamps that cross process boundaries"))
        return out


# ---------------------------------------------------------------------------
class RetryDeadline(Rule):
    name = "retry-deadline"
    doc = ("a retry loop (sleep + broad exception handler) must "
           "exclude DEADLINE_EXCEEDED and application errors from "
           "re-attempts — the budget died, not the peer (the PR-5 "
           "retry contract)")

    BROAD = frozenset({"Exception", "BaseException", "OSError",
                       "ConnectionError", "RpcError", "grpc.RpcError"})
    EXCLUDERS = frozenset({"DeadlineExceeded", "Cancelled",
                           "DEADLINE_EXCEEDED"})

    def _broad_handler(self, h: ast.ExceptHandler) -> bool:
        if h.type is None:
            return True
        types = (h.type.elts if isinstance(h.type, ast.Tuple)
                 else [h.type])
        return any(_dotted(t) in self.BROAD
                   or _dotted(t).rsplit(".", 1)[-1] in self.BROAD
                   for t in types)

    def check_file(self, ctx: FileContext) -> list[Finding]:
        out = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.For, ast.While)):
                continue
            body = list(_walk_no_defs(node))
            has_sleep = any(
                isinstance(n, ast.Call)
                and _dotted(n.func).endswith("sleep")
                for n in body)
            broad = [n for n in body
                     if isinstance(n, ast.ExceptHandler)
                     and self._broad_handler(n)]
            if not (has_sleep and broad):
                continue
            names = {n.id for n in body if isinstance(n, ast.Name)}
            names |= {n.attr for n in body
                      if isinstance(n, ast.Attribute)}
            if not (names & self.EXCLUDERS):
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    "retry loop with a broad exception handler does "
                    "not exclude DEADLINE_EXCEEDED/DeadlineExceeded/"
                    "Cancelled — retries must never re-spend an "
                    "expired budget or re-apply an answered request"))
        return out


# ---------------------------------------------------------------------------
class MetricDocs(Rule):
    name = "metric-docs"
    doc = ("METRICS registrations use literal names and explicit "
           "label kwargs (the runtime cardinality guard bounds "
           "values; literals bound the NAME space), and every name "
           "has a backticked row in README's observability table")

    METHODS = frozenset({"inc", "observe", "set_gauge"})

    def __init__(self):
        self.names: set[str] = set()
        self.sites: list[dict] = []

    def applies(self, rel: str) -> bool:
        return rel.startswith("dgraph_tpu/")

    def check_file(self, ctx: FileContext) -> list[Finding]:
        out = []
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in self.METHODS
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "METRICS"):
                continue
            if not node.args or not (
                    isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    "metric name must be a string literal — a dynamic "
                    "name defeats both the README doc table and the "
                    "per-name cardinality guard"))
                continue
            name = node.args[0].value
            self.names.add(name)
            self.sites.append({"name": name, "kind": node.func.attr,
                               "file": ctx.rel, "line": node.lineno})
            if any(kw.arg is None for kw in node.keywords):
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    f"metric {name!r} expands a dynamic **label dict — "
                    f"label KEYS must be explicit kwargs so the label "
                    f"schema stays reviewable and bounded"))
        return out

    def finalize(self, analyzer) -> list[Finding]:
        from dgraph_tpu.utils.metrics import DROPPED_SERIES
        names = self.names | {DROPPED_SERIES}
        readme = analyzer.readme_text
        missing = sorted(n for n in names if f"`{n}" not in readme)
        if not missing:
            return []
        # message preserved verbatim from the PR-4 doc-lint
        # (tests/test_metrics.py) it subsumes
        return [Finding(
            self.name, "README.md", 1,
            f"metric name(s) emitted but undocumented in README's "
            f"observability table: {missing}")]


# ---------------------------------------------------------------------------
class JitPurity(Rule):
    name = "jit-purity"
    doc = ("functions handed to jax.jit stay pure: no `.item()`/"
           "`.tolist()` host syncs, no numpy host ops, no Python "
           "branches on tracer params (branch on static_argnames or "
           "use jnp.where) — an impure jit path either retraces per "
           "call or hard-faults on TPU")

    HOST_SYNCS = frozenset({"item", "tolist"})

    def _jitted_functions(self, tree: ast.Module):
        """(FunctionDef, static_argnames) for every function that ends
        up inside jax.jit: decorated directly, decorated via
        functools.partial(jax.jit, ...), or passed by name to a
        jax.jit(fn, ...) call anywhere in the module."""
        jit_by_name: dict[str, set[str]] = {}
        wrappers = ("jax.jit", "jit", "jax.shard_map", "shard_map",
                    "jax.pmap", "pmap", "pjit", "jax.experimental."
                    "shard_map.shard_map")
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and _dotted(node.func) in wrappers
                    and node.args
                    and isinstance(node.args[0], ast.Name)):
                jit_by_name[node.args[0].id] = self._statics(node)
        for node in ast.walk(tree):
            if not isinstance(node, ast.FunctionDef):
                continue
            for dec in node.decorator_list:
                if _dotted(dec) in ("jax.jit", "jit"):
                    yield node, set()
                    break
                if (isinstance(dec, ast.Call)
                        and _dotted(dec.func) == "functools.partial"
                        and dec.args
                        and _dotted(dec.args[0]) in ("jax.jit", "jit")):
                    yield node, self._statics(dec)
                    break
            else:
                if node.name in jit_by_name:
                    yield node, jit_by_name[node.name]

    @staticmethod
    def _statics(call: ast.Call) -> set[str]:
        for kw in call.keywords:
            if kw.arg in ("static_argnames", "static_argnums"):
                v = kw.value
                if isinstance(v, ast.Constant) and isinstance(v.value,
                                                              str):
                    return {v.value}
                if isinstance(v, (ast.Tuple, ast.List)):
                    return {e.value for e in v.elts
                            if isinstance(e, ast.Constant)
                            and isinstance(e.value, str)}
        return set()

    @staticmethod
    def _tracer_params(fn: ast.FunctionDef, statics: set[str]):
        """Param names that are tracers at trace time: not static, and
        not optional-None structure flags (default None ⇒ branching on
        them is a static pytree-structure decision)."""
        args = list(fn.args.posonlyargs) + list(fn.args.args)
        defaults = [None] * (len(args) - len(fn.args.defaults)) \
            + list(fn.args.defaults)
        out = set()
        for a, d in zip(args, defaults):
            if a.arg in statics or a.arg == "self":
                continue
            if isinstance(d, ast.Constant) and d.value is None:
                continue
            out.add(a.arg)
        for a, d in zip(fn.args.kwonlyargs, fn.args.kw_defaults):
            if a.arg in statics:
                continue
            if isinstance(d, ast.Constant) and d.value is None:
                continue
            out.add(a.arg)
        return out

    def _branch_names(self, test: ast.AST) -> set[str]:
        """Names a branch test DYNAMICALLY depends on: excludes
        `x is None` comparisons and names only reached through
        `len(...)` / `.shape` / `.ndim` / `.dtype` (static under
        tracing)."""
        skip: set[int] = set()
        for n in ast.walk(test):
            if (isinstance(n, ast.Compare)
                    and all(isinstance(op, (ast.Is, ast.IsNot))
                            for op in n.ops)):
                skip.update(id(x) for x in ast.walk(n))
            if (isinstance(n, ast.Call) and _dotted(n.func) == "len"):
                skip.update(id(x) for x in ast.walk(n))
            if (isinstance(n, ast.Attribute)
                    and n.attr in ("shape", "ndim", "dtype", "size")):
                skip.update(id(x) for x in ast.walk(n))
        return {n.id for n in ast.walk(test)
                if isinstance(n, ast.Name) and id(n) not in skip}

    def check_file(self, ctx: FileContext) -> list[Finding]:
        out = []
        for fn, statics in self._jitted_functions(ctx.tree):
            tracers = self._tracer_params(fn, statics)
            for node in ast.walk(fn):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in self.HOST_SYNCS):
                    out.append(Finding(
                        self.name, ctx.rel, node.lineno,
                        f"host sync .{node.func.attr}() inside jitted "
                        f"function {fn.name}() — blocks dispatch and "
                        f"faults under trace"))
                elif (isinstance(node, ast.Call)
                        and _dotted(node.func).startswith("np.")):
                    out.append(Finding(
                        self.name, ctx.rel, node.lineno,
                        f"numpy host op {_dotted(node.func)}() inside "
                        f"jitted function {fn.name}() — runs on host "
                        f"per trace, not on device"))
                elif isinstance(node, (ast.If, ast.While)):
                    hot = self._branch_names(node.test) & tracers
                    if hot:
                        out.append(Finding(
                            self.name, ctx.rel, node.lineno,
                            f"Python branch on tracer param(s) "
                            f"{sorted(hot)} inside jitted function "
                            f"{fn.name}() — declare static_argnames "
                            f"or use jnp.where/lax.cond"))
        return out


# ---------------------------------------------------------------------------
class ShardMapCompat(Rule):
    name = "shard-map-compat"
    doc = ("`shard_map` has moved across jax releases "
           "(jax.experimental.shard_map.shard_map with check_rep → "
           "jax.shard_map with check_vma); utils/jaxcompat.py is the "
           "only file allowed to touch either spelling — everywhere "
           "else imports it from there, so the next jax move is a "
           "one-file change")

    SHIM = "dgraph_tpu/utils/jaxcompat.py"

    def applies(self, rel: str) -> bool:
        return rel.startswith("dgraph_tpu/") and rel != self.SHIM

    def check_file(self, ctx: FileContext) -> list[Finding]:
        out = []
        flagged: set[int] = set()  # one finding per line, not per
        #                            nested Attribute of the same chain

        def flag(line: int, what: str) -> None:
            if line in flagged:
                return
            flagged.add(line)
            out.append(Finding(
                self.name, ctx.rel, line,
                f"direct {what} outside utils/jaxcompat.py — import "
                f"the versioned resolver instead "
                f"(from dgraph_tpu.utils.jaxcompat import shard_map)"))

        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Attribute):
                d = _dotted(node)
                if (d == "jax.shard_map"
                        or d.startswith("jax.experimental.shard_map")):
                    flag(node.lineno, f"`{d}` reference")
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod.startswith("jax.experimental.shard_map") or (
                        mod == "jax" and any(a.name == "shard_map"
                                             for a in node.names)):
                    flag(node.lineno, f"import from `{mod}`")
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if a.name.startswith("jax.experimental.shard_map"):
                        flag(node.lineno, f"import of `{a.name}`")
        return out


# ---------------------------------------------------------------------------
class FusedHostCallback(Rule):
    name = "fused-host-callback"
    doc = ("R13: jitted functions in the fused-program layer "
           "(engine/fused.py, ops/) must keep host accounting OUT of "
           "the traced region — a costprofile/tracing/METRICS/"
           "jit_call/deadline call inside runs once at trace time "
           "(then silently never again on cached executions) or drags "
           "a host round-trip into the single-launch program; account "
           "around the dispatch, never inside it")

    SCOPES = ("dgraph_tpu/ops/",)
    HOST_HELPERS = ("costprofile", "tracing", "METRICS", "deadline")
    HOST_CALLS = frozenset({"jit_call", "note_launch", "launch_frame"})

    def applies(self, rel: str) -> bool:
        return (rel.startswith(self.SCOPES)
                or rel == "dgraph_tpu/engine/fused.py")

    def check_file(self, ctx: FileContext) -> list[Finding]:
        out = []
        for fn, _statics in JitPurity()._jitted_functions(ctx.tree):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                d = _dotted(node.func)
                root = d.split(".", 1)[0]
                leaf = d.rsplit(".", 1)[-1]
                if root in self.HOST_HELPERS or leaf in self.HOST_CALLS:
                    out.append(Finding(
                        self.name, ctx.rel, node.lineno,
                        f"host accounting call {d}() inside jitted "
                        f"function {fn.name}() — it runs at trace "
                        f"time only; move it outside the traced "
                        f"region (around the dispatch site)"))
        return out


# ---------------------------------------------------------------------------
class AtomicWrite(Rule):
    name = "atomic-write"
    doc = ("persistence-layer files (store/, server/backup.py) must be "
           "written via the tmp+fsync+os.replace pattern "
           "(vault.atomic_write / write_bytes, or a function that "
           "itself fsyncs and replaces) — a kill mid-`open(..., 'w')` "
           "leaves a torn file where recovery expects a whole one")

    SCOPES = ("dgraph_tpu/store/",)

    def applies(self, rel: str) -> bool:
        return (rel.startswith(self.SCOPES)
                or rel == "dgraph_tpu/server/backup.py")

    @staticmethod
    def _atomic_spans(tree: ast.Module) -> list[tuple[int, int]]:
        """Line spans of functions that ARE the atomic pattern: they
        call both os.fsync and os.replace themselves, so their write
        handle is the tmp side of a replace."""
        spans = []
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            calls = {_dotted(n.func) for n in ast.walk(node)
                     if isinstance(n, ast.Call)}
            if "os.replace" in calls and "os.fsync" in calls:
                spans.append((node.lineno,
                              getattr(node, "end_lineno", node.lineno)))
        return spans

    def check_file(self, ctx: FileContext) -> list[Finding]:
        out = []
        spans = self._atomic_spans(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and _dotted(node.func) == "open"):
                continue
            mode = None
            if len(node.args) >= 2 and isinstance(node.args[1],
                                                  ast.Constant):
                mode = node.args[1].value
            for kw in node.keywords:
                if kw.arg == "mode" and isinstance(kw.value,
                                                   ast.Constant):
                    mode = kw.value.value
            if not (isinstance(mode, str) and mode.startswith("w")):
                continue  # reads/appends ("r", "rb", "ab", "r+b") pass
            if any(lo <= node.lineno <= hi for lo, hi in spans):
                continue
            out.append(Finding(
                self.name, ctx.rel, node.lineno,
                f"non-atomic file write open(..., {mode!r}) in the "
                f"persistence layer — route it through "
                f"vault.atomic_write/write_bytes (tmp+fsync+"
                f"os.replace), or waive with the reason a torn file "
                f"is safe here"))
        return out


# ---------------------------------------------------------------------------
class CacheRegistration(Rule):
    name = "cache-registration"
    doc = ("R14: byte-holding caches must join the process memory "
           "governor (utils/memgov.py) — every `Memo(...)` call "
           "carries an explicit `governed=` decision, and a file that "
           "creates a dict-typed `*_cache` attribute must call "
           "`memgov.GOVERNOR.register` somewhere (or waive with the "
           "reason its bytes are bounded); an unregistered cache is "
           "invisible to the OOM evict-retry path and /debug/memory")

    DICT_CTORS = frozenset({"dict", "OrderedDict",
                            "collections.OrderedDict"})

    def applies(self, rel: str) -> bool:
        # the governor itself and the Memo implementation are the
        # mechanism, not clients of it
        return (rel.startswith("dgraph_tpu/")
                and rel not in ("dgraph_tpu/utils/memgov.py",
                                "dgraph_tpu/utils/jitcache.py"))

    @staticmethod
    def _is_dict_value(node: ast.AST) -> bool:
        if isinstance(node, ast.Dict):
            return True
        return (isinstance(node, ast.Call)
                and _dotted(node.func)
                in CacheRegistration.DICT_CTORS)

    @staticmethod
    def _cache_targets(node: ast.stmt):
        """Attribute/name targets ending in `_cache` of an assignment
        whose value is a dict literal / dict() / OrderedDict()."""
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        else:
            return
        if not CacheRegistration._is_dict_value(value):
            return
        for t in targets:
            if isinstance(t, ast.Attribute) and t.attr.endswith("_cache"):
                yield t.attr
            elif isinstance(t, ast.Name) and t.id.endswith("_cache"):
                yield t.id

    def check_file(self, ctx: FileContext) -> list[Finding]:
        out = []
        registers = any(
            isinstance(n, ast.Call)
            and _dotted(n.func).endswith("GOVERNOR.register")
            for n in ast.walk(ctx.tree))
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Call)
                    and _dotted(node.func).rsplit(".", 1)[-1] == "Memo"
                    and not any(kw.arg == "governed"
                                for kw in node.keywords)):
                out.append(Finding(
                    self.name, ctx.rel, node.lineno,
                    "Memo(...) without an explicit governed= decision "
                    "— pass governed=\"<inventory name>\" to join the "
                    "memory governor, or governed=None with a waiver "
                    "stating why its bytes stay unbudgeted"))
            elif isinstance(node, ast.stmt) and not registers:
                for attr in self._cache_targets(node):
                    out.append(Finding(
                        self.name, ctx.rel, node.lineno,
                        f"dict-typed cache attribute `{attr}` in a "
                        f"file that never calls "
                        f"memgov.GOVERNOR.register — register its "
                        f"bytes/evict callbacks (GOVERNED_CACHES "
                        f"inventory), or waive with the bound that "
                        f"keeps it small"))
        return out


# ---------------------------------------------------------------------------
class SloSpec(Rule):
    name = "slo-spec"
    doc = ("R15: SLO objective names stay inside the utils/slo."
           "SLO_SPECS inventory — a literal `slo=` metric label, a "
           "literal SLO_SPECS/DEFAULT_TARGETS subscript, or a literal "
           "`_evaluator(\"...\")` registration outside the inventory "
           "splits the burn-rate vocabulary between dashboards, "
           "/debug/slo, and the watchdog's kind=slo conviction feed")

    SPEC_TABLES = frozenset({"SLO_SPECS", "DEFAULT_TARGETS"})

    def __init__(self):
        # jax-free by design (utils/slo.py imports no jax), so the
        # static-analysis CLI can load the inventory directly
        from dgraph_tpu.utils.slo import SLO_SPECS
        self.known = frozenset(SLO_SPECS)

    def applies(self, rel: str) -> bool:
        return rel.startswith("dgraph_tpu/")

    def check_file(self, ctx: FileContext) -> list[Finding]:
        out = []

        def flag(line: int, name: str, where: str) -> None:
            out.append(Finding(
                self.name, ctx.rel, line,
                f"SLO name {name!r} ({where}) is not in the "
                f"utils/slo.SLO_SPECS inventory — add it there with a "
                f"doc line (and an @_evaluator), or fix the literal; "
                f"known: {sorted(self.known)}"))

        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                for kw in node.keywords:
                    if (kw.arg == "slo"
                            and isinstance(kw.value, ast.Constant)
                            and isinstance(kw.value.value, str)
                            and kw.value.value not in self.known):
                        flag(node.lineno, kw.value.value,
                             "literal slo= label")
                if (_dotted(node.func).rsplit(".", 1)[-1]
                        == "_evaluator"
                        and node.args
                        and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)
                        and node.args[0].value not in self.known):
                    flag(node.lineno, node.args[0].value,
                         "evaluator registration")
            elif (isinstance(node, ast.Subscript)
                    and _dotted(node.value).rsplit(".", 1)[-1]
                    in self.SPEC_TABLES
                    and isinstance(node.slice, ast.Constant)
                    and isinstance(node.slice.value, str)
                    and node.slice.value not in self.known):
                flag(node.lineno, node.slice.value, "spec-table lookup")
        return out


def default_rules() -> list[Rule]:
    from dgraph_tpu.analysis.guards import guard_rules
    return [HotLoopCheckpoint(), DirectIO(), WallClock(),
            RetryDeadline(), MetricDocs(), JitPurity(),
            ShardMapCompat(), FusedHostCallback(),
            AtomicWrite(), CacheRegistration(),
            SloSpec()] + guard_rules()
