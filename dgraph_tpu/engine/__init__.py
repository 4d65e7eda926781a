"""Query engine: IR, executor, recurse/shortest/groupby/math, JSON output.

Reference parity: `query/` package. `Engine` is the query-side facade the
server layer (edgraph analog) calls.
"""

from __future__ import annotations

from dgraph_tpu.engine.execute import Executor, LevelNode
from dgraph_tpu.engine.ir import (
    FilterNode, FuncNode, Order, RecurseArgs, ShortestArgs, SubGraph,
)
from dgraph_tpu.engine.outputnode import to_json


def shape_of(blocks) -> str:
    """Compact structural fingerprint of a parsed query — the
    cost-profile shape key (utils/costprofile.py). Built from the
    BOUNDED vocabulary that predicts cost (root func name, modifiers,
    tree depth, recurse depth) — never from argument VALUES, so the
    shape space stays within the cardinality guard for any workload
    that reuses query templates."""
    parts = []
    for sg in blocks[:4]:
        p = sg.func.name if sg.func is not None else "uid"
        mods = ""
        if sg.recurse is not None:
            mods += f"~r{sg.recurse.depth or 0}"
        if sg.msgpass is not None:
            mods += "~m"
        if sg.shortest is not None:
            mods += "~sp"
        if sg.filters is not None:
            mods += "~f"
        if sg.var_name:
            mods += "~v"
        d, node = 0, sg
        # graftlint: allow(hot-loop-checkpoint): bounded by the parsed
        # tree's depth (parser-limited), no data-dependent iteration
        while node.children:
            d += 1
            node = node.children[0]
        parts.append(f"{p}{mods}~d{d}")
    if len(blocks) > 4:
        parts.append(f"+{len(blocks) - 4}")
    return "q:" + ",".join(parts)


class Engine:
    """Parse + execute + render DQL queries over a Store snapshot.

    Reference: the read path of edgraph.Server.Query →
    query.Request.ProcessQuery → outputnode (SURVEY §3.1).
    """

    def __init__(self, store, device_threshold: int = 512, mesh=None):
        self.store = store
        self.device_threshold = device_threshold
        self.mesh = mesh  # jax.sharding.Mesh | None → SPMD expansion

    def query(self, q: str, variables: dict | None = None) -> dict:
        out, _ex = self.query_with_vars(q, variables)
        return out

    def query_with_vars(self, q: str, variables: dict | None = None):
        """(json, executor): the executor carries the bound uid/val vars —
        the seam upsert blocks substitute from (reference: edgraph
        doQueryInUpsert returns the query's var map)."""
        res, ex = self._run(q, variables)
        if ex is None:
            return res, None
        return to_json(ex, res), ex

    def query_bytes(self, q: str, variables: dict | None = None) -> bytes:
        """Serialized response bytes — the serving path. Uses the native
        emitter (engine/emit.py) where the block shape allows, skipping
        per-object Python assembly entirely (reference: outputnode.go
        ToJson writes bytes, never a generic map)."""
        from dgraph_tpu.engine.emit import to_json_bytes
        res, ex = self._run(q, variables)
        if ex is None:
            import json
            return json.dumps(res, separators=(",", ":")).encode()
        return to_json_bytes(ex, res)

    def _run(self, q: str, variables: dict | None = None):
        """Parse + execute: (LevelNode roots, executor), or for schema{}
        introspection (dict, None) — callers needing vars (upserts)
        reject schema queries explicitly."""
        from dgraph_tpu.dql.parser import parse, parse_schema_query
        from dgraph_tpu.engine.varorder import execution_order

        sq = parse_schema_query(q)
        if sq is not None:
            return self._schema_query(*sq), None

        from dgraph_tpu.utils import costprofile, tracing
        blocks = parse(q, variables)
        costprofile.add_shape(shape_of(blocks))
        costprofile.add("queries", 1)
        ex = Executor(self.store, device_threshold=self.device_threshold,
                      mesh=self.mesh)
        results: dict[int, LevelNode] = {}
        with tracing.span("engine.query", phase=True,
                          blocks=len(blocks)):
            for i in execution_order(blocks):
                results[i] = ex.run_block(blocks[i])
        roots = [results[i] for i in range(len(blocks))]  # textual order out
        return roots, ex

    def _schema_query(self, preds, fields) -> dict:
        """schema{} introspection (reference: the schema node list the
        reference returns: predicate/type/index/tokenizer/... plus type
        definitions)."""
        out = []
        schema = self.store.schema
        for name in sorted(schema.predicates):
            if preds is not None and name not in preds:
                continue
            ps = schema.predicates[name]
            d = {"predicate": name, "type": ps.kind.value}
            if ps.is_list:
                d["list"] = True
            if ps.index_tokenizers:
                d["index"] = True
                d["tokenizer"] = list(ps.index_tokenizers)
            for flag in ("reverse", "count", "lang", "upsert", "unique"):
                if getattr(ps, flag):
                    d[flag] = True
            if fields is not None:
                d = {k: v for k, v in d.items()
                     if k in fields or k == "predicate"}
            out.append(d)
        resp = {"schema": out}
        if preds is None:
            types = [{"name": t,
                      "fields": [{"name": f} for f in td.fields]}
                     for t, td in sorted(schema.types.items())]
            if types:
                resp["types"] = types
        return resp


__all__ = [
    "Engine", "Executor", "LevelNode", "SubGraph", "FuncNode", "FilterNode",
    "Order", "RecurseArgs", "ShortestArgs", "to_json",
]
