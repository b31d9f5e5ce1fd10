"""The work of a request, counted from the query and the rows its filter
keeps, whatever implements it; the kernels' roofline share divides the
least time for this work by their device time.

- bytes: rows passing the filter x the source bytes of the columns the
  post-filter stage reads (keys, projection inputs, aggregate inputs), plus
  the result's bytes (groups x the width of a result row);
- operations: rows passing the filter x (arithmetic nodes of the
  projections + one per aggregate state a row updates: count, sum, min,
  max one each, mean two).

Neither counts the bit-planes, limbs or padding that kernels really move,
so a share computed from them is understated, never above 100 %.
"""

from __future__ import annotations

from cb_reference import source_columns

_AGG_OPS = {"count": 1, "sum": 1, "mean": 2, "min": 1, "max": 1}
_RESULT_BYTES = {"count": 8, "sum": 8, "mean": 8}
_PROJECTED_BYTES = 4  # projections in the device envelope are float32 or int32


def arith_nodes(tree) -> int:
    if isinstance(tree, (int, float)) or tree[0] == "col":
        return 0
    return 1 + sum(arith_nodes(a) for a in tree[1:])


def row_bytes(query: dict, itemsize: dict) -> int:
    return sum(itemsize[c] for c in source_columns(query))


def result_row_bytes(query: dict, itemsize: dict) -> int:
    out = sum(itemsize[k] for k in query["keys"])
    for spec in query["aggs"].values():
        fn = spec["fn"]
        out += _RESULT_BYTES.get(fn) or itemsize.get(spec.get("column"), _PROJECTED_BYTES)
    return out


def row_ops(query: dict) -> int:
    arith = sum(arith_nodes(t) for stage in query.get("project", []) for t in stage.values())
    return arith + sum(_AGG_OPS[s["fn"]] for s in query["aggs"].values())


def request_work(query: dict, itemsize: dict, rows: int, groups: int) -> tuple:
    """(bytes, operations) of one request."""
    return (
        rows * row_bytes(query, itemsize) + groups * result_row_bytes(query, itemsize),
        rows * row_ops(query),
    )


def least_time(bytes_: float, ops: float, peaks: dict) -> tuple:
    """(seconds, which bound) at the chip's peaks."""
    t_bytes = bytes_ / float(peaks["hbm_bytes_per_s"])
    t_ops = ops / float(peaks["ops_per_s"])
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
