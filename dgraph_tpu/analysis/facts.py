"""Facts inventory: the static half of the cost-model direction.

ROADMAP's TpuGraphs-style item needs per-query-shape cost priors built
from recorded compile/execute spans; matching a recorded span back to
the kernel that produced it needs a ground-truth inventory of what the
codebase can launch and measure. graftlint already parses every file,
so the same pass extracts:

* **kernels** — every function handed to `jax.jit` (with its
  static_argnames: the retrace axes, i.e. the cost-model's categorical
  features) and every `jit_call("<kernel>", key)` launch site (the
  names `jit_compile_us{kernel=}` series carry).
* **spans** — every `tracing.span("<name>", ...)` site: the vocabulary
  of the trace/OTLP streams the predictor trains on.
* **metrics** — every literal registration (name, kind, site).
* **locks** — every `make_lock/make_rlock/make_condition` order class,
  the static side of the lock sanitizer's graph.
* **guarded_fields** — the lock-discipline inventory (ISSUE 12,
  `guards.py`): per class, which fields are written under which lock —
  what rules R9–R11 enforce statically and what `locks.guarded()` arms
  dynamically under DGRAPH_TPU_RACE_SANITIZER=1. `guarded_sites` lists
  every runtime `guarded(self, …)` arming call, so test_lint.py can
  pin the static inventory and the dynamic registry to each other in
  BOTH directions (the `cost_record_fields` pattern).
* **cost_record_fields** — the runtime cost-record schema
  (utils/costprofile.FIELDS, re-exported verbatim): the static
  inventory and the runtime records SHARE this vocabulary, so a
  recorded cost joins back to the kernels/spans that incurred it
  (tests/test_lint.py pins the two in sync — the join key for the
  future learned cost model).
* **governed_caches** — the memory-governor cache inventory (ISSUE 16,
  utils/memgov.GOVERNED_CACHES): every byte-holding cache name the
  process-wide governor budgets, pinned both ways against the runtime
  registration surface; rule R14 enforces that new caches join it.
* **slo_specs** — the SLO objective inventory (ISSUE 17,
  utils/slo.SLO_SPECS): every service-level objective the burn-rate
  engine can evaluate, pinned both ways against the runtime evaluator
  registry; rule R15 keeps `slo=` label literals inside it.
* **fused_stage_kinds** — the whole-query fused-program inventory
  (ISSUE 15, engine/fused.STAGE_KINDS): every stage kind the plan
  compiler can emit into one jitted program, pinned both ways
  against the runtime stage-emitter registry. Rule R13 extends the
  R6 jit-purity facts to these programs: a jitted fused stage may
  not call costprofile/tracing/metrics host helpers in the traced
  region.

Emitted under `"facts"` in `--format=json` output.
"""

from __future__ import annotations

import ast

__all__ = ["extract_facts"]

_LOCK_FNS = {"make_lock": "lock", "make_rlock": "rlock",
             "make_condition": "condition"}


def _guarded_sites(ctx) -> list[dict]:
    """Every `locks.guarded(self, "<lock>")` arming call, tagged with
    its enclosing class — the dynamic registry's static footprint."""
    out = []
    for cls in ast.walk(ctx.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        for node in ast.walk(cls):
            if not (isinstance(node, ast.Call)
                    and _dotted(node.func).rsplit(".", 1)[-1]
                    == "guarded"
                    and node.args
                    and isinstance(node.args[0], ast.Name)
                    and node.args[0].id == "self"):
                continue
            lock = (node.args[1].value
                    if len(node.args) > 1
                    and isinstance(node.args[1], ast.Constant)
                    else "?")
            out.append({"class": cls.name, "file": ctx.rel,
                        "line": node.lineno, "lock": lock})
    return out


def _dotted(node: ast.AST) -> str:
    from dgraph_tpu.analysis.rules import _dotted as d
    return d(node)


def extract_facts(contexts) -> dict:
    from dgraph_tpu.analysis.guards import class_inventory
    from dgraph_tpu.analysis.rules import JitPurity

    kernels, launches, spans, locks = [], [], [], []
    metrics: list[dict] = []
    guarded_fields: list[dict] = []
    guarded_sites: list[dict] = []
    jit_rule = JitPurity()
    for ctx in contexts:
        if not ctx.rel.startswith("dgraph_tpu/"):
            continue
        guarded_fields.extend(class_inventory(ctx))
        guarded_sites.extend(_guarded_sites(ctx))
        for fn, statics in jit_rule._jitted_functions(ctx.tree):
            kernels.append({
                "name": fn.name, "file": ctx.rel, "line": fn.lineno,
                "static_argnames": sorted(statics)})
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                d = _dotted(node.func)
                leaf = d.rsplit(".", 1)[-1]
                arg0 = (node.args[0].value
                        if node.args
                        and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)
                        else None)
                if leaf == "jit_call" and arg0:
                    launches.append({"kernel": arg0, "file": ctx.rel,
                                     "line": node.lineno})
                elif leaf == "span" and arg0:
                    spans.append({"name": arg0, "file": ctx.rel,
                                  "line": node.lineno})
                elif leaf in _LOCK_FNS and arg0:
                    locks.append({"name": arg0,
                                  "kind": _LOCK_FNS[leaf],
                                  "file": ctx.rel,
                                  "line": node.lineno})
                elif (leaf in ("inc", "observe", "set_gauge") and arg0
                      and isinstance(node.func, ast.Attribute)
                      and isinstance(node.func.value, ast.Name)
                      and node.func.value.id == "METRICS"):
                    metrics.append({"name": arg0, "kind": leaf,
                                    "file": ctx.rel,
                                    "line": node.lineno})
    # ONE vocabulary: the runtime cost-record schema is imported, not
    # re-declared — facts and records cannot drift apart silently
    from dgraph_tpu.utils.costprofile import FIELDS as COST_FIELDS
    cost_fields = [{"name": n, "kind": d["kind"], "doc": d["doc"]}
                   for n, d in sorted(COST_FIELDS.items())]
    # same discipline for the PRIOR model's regressors (ISSUE 9): the
    # feature vocabulary utils/costprior.py fits on is re-exported
    # verbatim; tests/test_lint.py pins it both ways against FIELDS —
    # a prior can never train on a feature no record carries, and a
    # feature field can never silently fall out of the model's reach
    from dgraph_tpu.utils.costprior import FEATURES as PRIOR_FEATURES
    prior_features = [{"name": n, "kind": COST_FIELDS[n]["kind"]}
                      for n in PRIOR_FEATURES]
    # same discipline for the DEBUG SURFACE (ISSUE 13): the endpoint
    # inventory server/http.py keys its runtime dispatch on is
    # re-exported verbatim (import-free module, so the analysis CLI
    # never pulls the server's jax/grpc chain); tests/test_lint.py
    # pins inventory ↔ runtime route table in both directions
    from dgraph_tpu.server.debug_routes import DEBUG_ENDPOINTS
    debug_endpoints = [{"path": p, "doc": d}
                       for p, d in sorted(DEBUG_ENDPOINTS.items())]
    # same discipline for the WHOLE-QUERY FUSED PROGRAM (ISSUE 15):
    # the stage-kind inventory the plan compiler can emit
    # (engine/fused.STAGE_KINDS — a jax-free import by design) is
    # re-exported verbatim; tests/test_lint.py pins it against the
    # runtime stage-emitter registry in both directions, so a stage
    # the compiler emits but the inventory doesn't name (or an
    # inventoried kind no emitter serves) fails tier-1
    from dgraph_tpu.engine.fused import STAGE_KINDS
    fused_stages = [{"kind": k, "doc": d}
                    for k, d in sorted(STAGE_KINDS.items())]
    # same discipline for the MEMORY GOVERNOR (ISSUE 16): the static
    # inventory of governed cache names (utils/memgov.GOVERNED_CACHES —
    # a jax-free import by design) is re-exported verbatim;
    # tests/test_lint.py pins it both ways against the runtime
    # registration surface, so a cache that registers under an
    # uninventoried name (or an inventoried name nothing registers)
    # fails tier-1 — rule R14 enforces that byte-holding caches
    # register at all
    from dgraph_tpu.utils.memgov import GOVERNED_CACHES
    governed_caches = [{"name": n, "doc": d}
                       for n, d in sorted(GOVERNED_CACHES.items())]
    # same discipline for the SLO ENGINE (ISSUE 17): the objective
    # inventory (utils/slo.SLO_SPECS — a jax-free import by design) is
    # re-exported verbatim; tests/test_lint.py pins it both ways
    # against the runtime evaluator registry, so an objective with no
    # evaluator (or an evaluator for an un-inventoried name) fails
    # tier-1 — rule R15 enforces that `slo=` label literals and spec
    # lookups stay inside this vocabulary
    from dgraph_tpu.utils.slo import SLO_SPECS
    slo_specs = [{"name": n, "doc": d}
                 for n, d in sorted(SLO_SPECS.items())]
    return {
        "kernels": kernels,
        "kernel_launch_sites": launches,
        "span_sites": spans,
        "metric_sites": metrics,
        "lock_classes": locks,
        "guarded_fields": guarded_fields,
        "guarded_sites": guarded_sites,
        "cost_record_fields": cost_fields,
        "cost_prior_features": prior_features,
        "debug_endpoints": debug_endpoints,
        "fused_stage_kinds": fused_stages,
        "governed_caches": governed_caches,
        "slo_specs": slo_specs,
        "totals": {
            "kernels": len(kernels),
            "kernel_launch_sites": len(launches),
            "span_names": len({s["name"] for s in spans}),
            "metric_names": len({m["name"] for m in metrics}),
            "lock_classes": len({x["name"] for x in locks}),
            "guarded_classes": len({(g["file"], g["class"])
                                    for g in guarded_fields}),
            "guarded_fields": sum(len(g["fields"])
                                  for g in guarded_fields),
            "guarded_sites": len(guarded_sites),
            "cost_record_fields": len(cost_fields),
            "cost_prior_features": len(prior_features),
            "debug_endpoints": len(debug_endpoints),
            "fused_stage_kinds": len(fused_stages),
            "governed_caches": len(governed_caches),
            "slo_specs": len(slo_specs),
        },
    }
