"""The plain reference: each request's answer computed with numpy straight
from the generated columns, and the comparison that decides ``correct``.

It imports nothing of the system under test.  Semantics: the request's
source tables are concatenated, the filter keeps the rows where it holds,
each projection stage evaluates its expressions in the columns' own dtypes
(float32 arithmetic stays float32, as numpy rounds it), and the aggregate
groups by the key columns.  Counts are int64; sums and means of float
columns accumulate in float64; min and max keep the column's dtype.

``accumulate="float32"`` gives the control: the same answer with every
float sum accumulated in float32 within each 65,536-row block (numpy's
pairwise sum), and the blocks added in float64.  That is the step a later
change would be tempted to take (folding the sums on the device in 32-bit
lanes); the comparison has to fail it.

Compared numbers, over every sampled request:

- ``groups_wrong``: groups missing, extra, or with a count that differs;
- ``minmax_wrong``: min/max values that differ from the reference bit for bit;
- ``sum_rel_err``: the largest ``|served - reference| / sum(|x|)`` of a sum
  (for a mean, of the mean of ``|x|``) over its group: the error of a sum
  in units of its condition, which float64 accumulation in any order keeps
  near ``n * 1.1e-16`` and float32 accumulation near ``1e-7``.
"""

from __future__ import annotations

import numpy as np

_ARITH = {"add": np.add, "sub": np.subtract, "mul": np.multiply}
_CMP = {"lt": np.less, "le": np.less_equal, "gt": np.greater, "ge": np.greater_equal, "eq": np.equal, "ne": np.not_equal}
_LOGIC = {"and": np.logical_and, "or": np.logical_or}
BLOCK_ROWS = 65536
UNBOUNDED = 1e300  # an error with no finite measure (NaN, or a nonzero error on a zero sum)


def evaluate(tree, cols: dict):
    if isinstance(tree, (int, float)):
        return tree
    op = tree[0]
    if op == "col":
        return cols[tree[1]]
    a, b = evaluate(tree[1], cols), evaluate(tree[2], cols)
    if op in _ARITH:
        return _ARITH[op](a, b)
    if op in _CMP:
        return _CMP[op](a, b)
    if op in _LOGIC:
        return _LOGIC[op](a, b)
    raise ValueError(f"unknown operator {op!r}")


def _tree_columns(tree) -> set:
    if isinstance(tree, (int, float)):
        return set()
    if tree[0] == "col":
        return {tree[1]}
    out = set()
    for a in tree[1:]:
        out |= _tree_columns(a)
    return out


def source_columns(query: dict) -> list:
    """The source columns a request reads after its filter (keys,
    projection inputs and aggregate inputs), in a stable order."""
    produced, need = set(), set(query["keys"])
    for stage in query.get("project", []):
        for name, tree in stage.items():
            need |= _tree_columns(tree) - produced
        produced |= set(stage)
    for spec in query["aggs"].values():
        if spec.get("column") is not None and spec["column"] not in produced:
            need.add(spec["column"])
    return sorted(need)


def filter_mask(tables: dict, request: dict):
    """Per source table, the boolean mask of the rows the filter keeps
    (None: every row)."""
    flt = request["filter"]
    if flt is None:
        return [None for _ in request["sources"]]
    return [np.asarray(evaluate(flt, tables[t]["columns"]), bool) for t in request["sources"]]


def rows_passing(tables: dict, request: dict) -> int:
    masks = filter_mask(tables, request)
    return sum(
        int(tables[t]["columns"][next(iter(tables[t]["columns"]))].size) if m is None else int(m.sum())
        for t, m in zip(request["sources"], masks)
    )


def frame(tables: dict, request: dict, query: dict) -> dict:
    """The filtered, projected columns a request aggregates."""
    need = source_columns(query)
    pieces = []
    for t, m in zip(request["sources"], filter_mask(tables, request)):
        cols = tables[t]["columns"]
        pieces.append({c: cols[c] if m is None else cols[c][m] for c in need})
    cols = {c: np.concatenate([p[c] for p in pieces]) if len(pieces) > 1 else pieces[0][c] for c in need}
    for stage in query.get("project", []):
        cols.update({name: np.asarray(evaluate(tree, cols)) for name, tree in stage.items()})
    return cols


def group_index(key_cols: list, n: int):
    """(inverse index per row, number of groups, key tuples per group) of
    ``n`` rows; with no key columns, one group whose key is ``()``."""
    if not key_cols:
        return np.zeros(n, np.int64), 1, [()]
    code = np.zeros(n, np.int64)
    lows, spans = [], []
    total = 1
    for k in key_cols:
        v = k.astype(np.int64)
        lo = int(v.min()) if n else 0
        span = (int(v.max()) - lo + 1) if n else 1
        code = code * span + (v - lo)
        lows.append(lo)
        spans.append(span)
        total *= span
    if total <= 1 << 24:
        present = np.flatnonzero(np.bincount(code, minlength=total))
        remap = np.full(total, -1, np.int64)
        remap[present] = np.arange(present.size)
        inv = remap[code]
    else:
        present, inv = np.unique(code, return_inverse=True)
    keys = []
    for c in present.tolist():
        parts = []
        for lo, span in zip(reversed(lows), reversed(spans)):
            parts.append(lo + c % span)
            c //= span
        keys.append(tuple(reversed(parts)))
    return inv, len(keys), keys


def _sums(inv, x, g: int, accumulate: str):
    if x.dtype.kind in "iub":
        acc = np.zeros(g, np.int64)
        np.add.at(acc, inv, x.astype(np.int64))
        return acc
    if accumulate == "float64":
        return np.bincount(inv, weights=x.astype(np.float64), minlength=g)
    acc = np.zeros(g, np.float64)
    for s in range(0, inv.size, BLOCK_ROWS):
        ii, xx = inv[s : s + BLOCK_ROWS], x[s : s + BLOCK_ROWS].astype(np.float32)
        for gi in np.unique(ii):
            acc[gi] += float(xx[ii == gi].sum(dtype=np.float32))
    return acc


def _extreme(inv, x, g: int, fn: str):
    if x.dtype.kind == "f":
        init = np.inf if fn == "min" else -np.inf
    else:
        info = np.iinfo(x.dtype)
        init = info.max if fn == "min" else info.min
    acc = np.full(g, init, x.dtype)
    (np.minimum if fn == "min" else np.maximum).at(acc, inv, x)
    return acc


def aggregate(cols: dict, query: dict, accumulate: str = "float64") -> dict:
    """``{key tuple: {agg name: (value, scale)}}``; ``scale`` is the sum of
    ``|x|`` (a mean's: that over the count) for sums and means, else None."""
    keys = query["keys"]
    inv, g, key_rows = group_index([cols[k] for k in keys], len(next(iter(cols.values()))))
    count = np.bincount(inv, minlength=g).astype(np.int64)
    out = {k: {} for k in key_rows}
    for name, spec in query["aggs"].items():
        fn = spec["fn"]
        x = cols.get(spec.get("column"))
        scale = None
        if fn == "count":
            vals = count
        elif fn in ("sum", "mean"):
            vals = _sums(inv, x, g, accumulate)
            scale = np.bincount(inv, weights=np.abs(x.astype(np.float64)), minlength=g)
            if fn == "mean":
                vals = vals / count
                scale = scale / count
        elif fn in ("min", "max"):
            vals = _extreme(inv, x, g, fn)
        else:
            raise ValueError(f"unknown aggregate {fn!r}")
        for i, k in enumerate(key_rows):
            out[k][name] = (vals[i], None if scale is None else float(scale[i]))
    return out


def answer(tables: dict, request: dict, query: dict, accumulate: str = "float64") -> dict:
    return aggregate(frame(tables, request, query), query, accumulate)


def served_answer(columns: dict, query: dict) -> dict:
    """A served result (``{column: array}``) in the reference's form."""
    keys = query["keys"]
    n = len(columns[keys[0]]) if keys else 1
    out = {}
    for i in range(n):
        k = tuple(int(columns[c][i]) for c in keys)
        out[k] = {name: (columns[name][i], None) for name in query["aggs"]}
    return out


def compare(got: dict, want: dict, query: dict) -> dict:
    """The compared numbers for one answer against the reference's."""
    groups_wrong = len(set(got) ^ set(want))
    minmax_wrong = 0
    rel = 0.0
    counts = [n for n, s in query["aggs"].items() if s["fn"] == "count"]
    for k in set(got) & set(want):
        g, w = got[k], want[k]
        if any(int(g[n][0]) != int(w[n][0]) for n in counts):
            groups_wrong += 1
        for name, spec in query["aggs"].items():
            fn = spec["fn"]
            gv, (wv, scale) = g[name][0], w[name]
            if fn in ("min", "max"):
                gv, wv = np.asarray(gv), np.asarray(wv)
                if gv.dtype != wv.dtype or gv.tobytes() != wv.tobytes():
                    minmax_wrong += 1
            elif fn in ("sum", "mean"):
                err = abs(float(gv) - float(wv))
                if not np.isfinite(err):
                    rel = UNBOUNDED
                elif err > 0.0:
                    rel = max(rel, min(err / scale, UNBOUNDED) if scale > 0.0 else UNBOUNDED)
    return {"groups_wrong": groups_wrong, "minmax_wrong": minmax_wrong, "sum_rel_err": rel}


def merge_readings(readings: list) -> dict:
    """Worst of each compared number over several answers."""
    out = {"groups_wrong": 0, "minmax_wrong": 0, "sum_rel_err": 0.0}
    for r in readings:
        out["groups_wrong"] += r["groups_wrong"]
        out["minmax_wrong"] += r["minmax_wrong"]
        out["sum_rel_err"] = max(out["sum_rel_err"], r["sum_rel_err"])
    return out
