"""A request (its query as data, see ``cb_traffic``) as the COOK DAG a
client sends: sources (a union when there are several), the filter,
one project node per projection stage, and the aggregate."""

from __future__ import annotations

from repro.core.dag import Dag
from repro.core.expr import Expr, col, lit

_OPS = {"add", "sub", "mul", "lt", "le", "gt", "ge", "eq", "ne", "and", "or"}


def expr(tree) -> Expr:
    if isinstance(tree, (int, float)):
        return lit(tree)
    op = tree[0]
    if op == "col":
        return col(tree[1])
    if op not in _OPS:
        raise ValueError(f"unknown operator {op!r}")
    return Expr(op, (expr(tree[1]), expr(tree[2])))


def build_dag(query: dict, request: dict, authority: str) -> Dag:
    bld = Dag.build()
    srcs = [bld.source(f"dacp://{authority}/ds/{t}") for t in request["sources"]]
    node = srcs[0] if len(srcs) == 1 else bld.add("union", {}, srcs)
    if request["filter"] is not None:
        node = bld.add("filter", {"predicate": expr(request["filter"])}, [node])
    for stage in query.get("project", []):
        node = bld.add("project", {"exprs": {k: expr(t) for k, t in stage.items()}, "keep": True}, [node])
    aggs = {name: dict(spec) for name, spec in query["aggs"].items()}
    node = bld.add("aggregate", {"keys": list(query["keys"]), "aggs": aggs}, [node])
    return bld.finish(node)
