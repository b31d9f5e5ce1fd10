"""Operator library: morsel-pure evaluators + the reference pull driver
(paper §III-B, §IV-B).

The module is split in two layers since the executor refactor:

  * **morsel-pure functions** (``filter_morsel``, ``select_morsel``,
    ``project_morsel``, ``map_morsel``, ``join_probe_morsel``) — each maps
    one RecordBatch to at most one RecordBatch with no cross-batch state.
    They are the unit of work the morsel-driven parallel driver
    (``repro.core.executor``) hands to its workers, and they take a
    ``ComputeBackend`` so eligible morsels dispatch to Pallas kernels.
  * **streaming evaluators + ``execute``** — the reference lazy pull chain
    (reverse supply): building an executor does no work; iterating the
    output recursively pulls from inputs one batch at a time — the paper's
    §III-D execution model, single-threaded.  ``SDFEngine`` uses the
    parallel driver by default and keeps this path as the ``num_workers=0``
    reference/fallback.

``map`` operators reference functions from a **named registry** — the DAG
itself never carries code.  Each registered fn declares the columns it reads
and writes so the pushdown optimizer can reorder filters around it.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Iterator

import numpy as np

from repro.core.batch import Column, RecordBatch, concat_batches
from repro.core.dag import Dag, Node
from repro.core.dtypes import resolve as resolve_dtype
from repro.core.errors import PlanError, SchemaError
from repro.core.expr import Expr
from repro.core.schema import Field, Schema
from repro.core.sdf import StreamingDataFrame
from repro.core.trace import span

__all__ = [
    "MapFn",
    "register_map",
    "get_map",
    "MAP_REGISTRY",
    "execute",
    "execute_node",
    "filter_morsel",
    "select_morsel",
    "project_morsel",
    "project_schema",
    "map_morsel",
    "join_schema",
    "build_join_table",
    "join_probe_indices",
    "join_probe_morsel",
    "GroupState",
    "agg_out_fields",
]


# ---------------------------------------------------------------------------
# map-fn registry
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class MapFn:
    name: str
    fn: Callable  # (RecordBatch, **params) -> RecordBatch
    schema_fn: Callable  # (Schema, **params) -> Schema
    reads: tuple  # column names read ("*" = all)
    writes: tuple  # column names written/created


MAP_REGISTRY: dict = {}


def register_map(name: str, reads=("*",), writes=()):
    def deco(fn):
        def default_schema(schema: Schema, **params) -> Schema:
            return schema

        schema_fn = getattr(fn, "schema_fn", default_schema)
        MAP_REGISTRY[name] = MapFn(name, fn, schema_fn, tuple(reads), tuple(writes))
        return fn

    return deco


def get_map(name: str) -> MapFn:
    try:
        return MAP_REGISTRY[name]
    except KeyError:
        raise PlanError(f"map fn {name!r} is not registered on this server") from None


# a few built-in maps used by the data pipeline and tests -------------------------
def _schema_add(name: str, dtype: str):
    def sf(schema: Schema, **params) -> Schema:
        out = name if "out" not in params else params["out"]
        f = Field(out, resolve_dtype(dtype))
        if out in schema:
            return schema  # replaced in-place by with_column
        return schema.append(f)

    return sf


def _blob_lengths(batch: RecordBatch, column: str, out: str = "nbytes") -> RecordBatch:
    c = batch.column(column)
    if c.dtype.is_varwidth:
        lens = (c.offsets[1:] - c.offsets[:-1]).astype(np.int64)
    else:
        lens = np.full(batch.num_rows, c.dtype.width, dtype=np.int64)
    return batch.with_column(Field(out, resolve_dtype("int64")), Column.from_values(resolve_dtype("int64"), lens))


_blob_lengths.schema_fn = _schema_add("nbytes", "int64")
register_map("blob_lengths", reads=("*",), writes=("nbytes",))(_blob_lengths)


def _lowercase(batch: RecordBatch, column: str) -> RecordBatch:
    c = batch.column(column)
    vals = [v.lower() if isinstance(v, str) else v for v in c.to_pylist()]
    return batch.with_column(batch.schema.field(column), Column.from_values(c.dtype, vals))


register_map("lowercase", reads=("*",), writes=())(_lowercase)


# ---------------------------------------------------------------------------
# morsel-pure operator functions (shared by the pull chain and the parallel
# executor; each maps one batch -> one batch or None, no cross-batch state)
# ---------------------------------------------------------------------------
def filter_morsel(batch: RecordBatch, predicate: Expr) -> RecordBatch | None:
    """Surviving rows of one morsel, or None when fully masked (no empty
    frames downstream)."""
    mask = np.asarray(predicate.evaluate(batch), dtype=bool)
    if mask.all():
        return batch
    if not mask.any():
        return None
    return batch.filter(mask)


def select_morsel(batch: RecordBatch, columns: list) -> RecordBatch:
    return batch.select(columns)


def map_morsel(batch: RecordBatch, mf: "MapFn", fn_params: dict) -> RecordBatch:
    return mf.fn(batch, **fn_params)


def project_morsel(batch: RecordBatch, exprs: dict, out_schema: Schema) -> RecordBatch:
    """Evaluate projection exprs against one morsel, shaping the output to a
    precomputed schema (dtype-coerced — morsel workers must all agree)."""
    new_cols = {}
    for name, e in exprs.items():
        vals = np.asarray(e.evaluate(batch))
        if vals.ndim == 0:
            vals = np.full(batch.num_rows, vals[()])
        f = out_schema.field(name)
        if not f.dtype.is_varwidth and vals.dtype != f.dtype.np_dtype:
            vals = vals.astype(f.dtype.np_dtype)
        new_cols[name] = Column.from_values(f.dtype, vals)
    cols = [new_cols[f.name] if f.name in new_cols else batch.column(f.name) for f in out_schema]
    return RecordBatch(out_schema, cols)


# ---------------------------------------------------------------------------
# per-node streaming evaluators
# ---------------------------------------------------------------------------
def _eval_filter(node: Node, ins: list) -> StreamingDataFrame:
    (src,) = ins
    pred: Expr = node.params["predicate"]

    def gen() -> Iterator[RecordBatch]:
        for b in src.iter_batches():
            out = filter_morsel(b, pred)
            if out is not None:
                yield out

    return StreamingDataFrame(src.schema, gen)


def _eval_select(node: Node, ins: list) -> StreamingDataFrame:
    (src,) = ins
    cols = list(node.params["columns"])
    schema = src.schema.select(cols)

    def gen():
        for b in src.iter_batches():
            yield select_morsel(b, cols)

    return StreamingDataFrame(schema, gen)


def project_schema(src_schema: Schema, exprs: dict, keep: bool) -> Schema:
    return _infer_project_schema(src_schema, exprs, keep)


def _infer_project_schema(src_schema: Schema, exprs: dict, keep: bool) -> Schema:
    """Infer projection dtypes by evaluating on an empty batch (cheap, exact)."""
    from repro.core import dtypes as _dt

    empty = RecordBatch.empty(src_schema)
    fields = list(src_schema.fields) if keep else []
    names = {f.name for f in fields}
    for name, e in exprs.items():
        vals = np.asarray(e.evaluate(empty))
        if vals.ndim == 0:  # literal broadcast: dtype of the scalar
            vals = np.asarray([vals[()]])
        try:
            dt = _dt.from_numpy(vals.dtype)
        except KeyError:
            dt = _dt.STRING
        f = Field(name, dt)
        if name in names:
            fields[[x.name for x in fields].index(name)] = f
        else:
            fields.append(f)
            names.add(name)
    return Schema(fields)


def _eval_project(node: Node, ins: list) -> StreamingDataFrame:
    (src,) = ins
    exprs: dict = node.params["exprs"]
    keep: bool = bool(node.params.get("keep", True))

    schema_holder = {"schema": _infer_project_schema(src.schema, exprs, keep)}

    def _projected(b: RecordBatch):
        from repro.core import dtypes as _dt

        cols = []
        for name, e in exprs.items():
            vals = np.asarray(e.evaluate(b))
            if vals.ndim == 0:
                vals = np.full(b.num_rows, vals[()])
            dt = _dt.from_numpy(vals.dtype)
            cols.append((Field(name, dt), Column.from_values(dt, vals)))
        return cols

    def gen():
        for b in src.iter_batches():
            new_cols = _projected(b)
            if keep:
                out = b
                for f, c in new_cols:
                    out = out.with_column(f, c)
            else:
                out = RecordBatch(Schema([f for f, _ in new_cols]), [c for _, c in new_cols])
            schema_holder["schema"] = out.schema
            yield out

    return StreamingDataFrame(schema_holder["schema"], gen)


def _eval_map(node: Node, ins: list) -> StreamingDataFrame:
    (src,) = ins
    mf = get_map(node.params["fn"])
    fn_params = dict(node.params.get("fn_params", {}))
    schema = mf.schema_fn(src.schema, **fn_params)

    def gen():
        for b in src.iter_batches():
            yield map_morsel(b, mf, fn_params)

    return StreamingDataFrame(schema, gen)


def _eval_rebatch(node: Node, ins: list) -> StreamingDataFrame:
    (src,) = ins
    rows = int(node.params["rows"])
    if rows <= 0:
        raise PlanError("rebatch rows must be positive")

    def gen():
        pend: list = []
        pend_rows = 0
        for b in src.iter_batches():
            pend.append(b)
            pend_rows += b.num_rows
            while pend_rows >= rows:
                merged = concat_batches(pend)
                yield merged.slice(0, rows)
                rest = merged.slice(rows, merged.num_rows)
                pend = [rest] if rest.num_rows else []
                pend_rows = rest.num_rows
        if pend_rows:
            yield concat_batches(pend)

    return StreamingDataFrame(src.schema, gen)


def _eval_limit(node: Node, ins: list) -> StreamingDataFrame:
    (src,) = ins
    n = int(node.params["n"])

    def gen():
        seen = 0
        if n <= 0:
            return
        for b in src.iter_batches():
            if seen + b.num_rows >= n:
                yield b.slice(0, n - seen)  # no further upstream pulls
                return
            seen += b.num_rows
            yield b

    return StreamingDataFrame(src.schema, gen)


# ---------------------------------------------------------------------------
# aggregation (group_by().agg() — full / partial / final modes)
# ---------------------------------------------------------------------------
def _sum_dtype(dt):
    return resolve_dtype("int64") if dt.is_integer else resolve_dtype("float64")


def agg_out_fields(in_schema: Schema, keys: list, aggs: dict, mode: str) -> list:
    return _agg_out_fields(in_schema, keys, aggs, mode)


def _agg_out_fields(in_schema: Schema, keys: list, aggs: dict, mode: str) -> list:
    """Output fields for an aggregate node.  ``partial`` emits decomposed
    state (sum+count for mean) so partials union/exchange cleanly and a
    ``final`` stage can combine them."""
    fields = [in_schema.field(k) for k in keys]
    for out, spec in aggs.items():
        fn = spec["fn"]
        column = spec.get("column")
        if fn == "count":
            fields.append(Field(out, resolve_dtype("int64")))
        elif fn == "mean":
            if mode == "partial":
                fields.append(Field(f"{out}__psum", resolve_dtype("float64")))
                fields.append(Field(f"{out}__pcnt", resolve_dtype("int64")))
            else:
                fields.append(Field(out, resolve_dtype("float64")))
        elif fn == "sum":
            src = in_schema.field(_agg_src(out, spec, mode)).dtype
            fields.append(Field(out, _sum_dtype(src)))
        else:  # min / max keep the input dtype
            src = in_schema.field(_agg_src(out, spec, mode)).dtype
            fields.append(Field(out, src))
    return fields


def _agg_src(out: str, spec: dict, mode: str) -> str:
    """Column an agg reads: the user column, or the partial-state column when
    combining (mode=final reads the partial stage's output names)."""
    if mode == "final":
        return out
    return spec.get("column")


def _odd_float(a: np.ndarray) -> bool:
    """Whether a float key column holds a NaN or a ``-0.0``: keys whose bit
    images do not follow Python equality (NaN is unequal to itself, ``-0.0``
    equals ``+0.0``), so they group through the reference row loop."""
    return a is not None and a.dtype.kind == "f" and bool(np.isnan(a).any() or ((a == 0) & np.signbit(a)).any())


def _key_image(arrs: list, n: int) -> np.ndarray:
    """One comparable value per row for a set of fixed-width key columns:
    their bit patterns side by side (signed integers with the sign bit
    flipped), as one uint64 when they fit in 8 bytes, else as one
    fixed-size void.  Equal images are equal keys whenever no key is a NaN
    or ``-0.0`` (:func:`_odd_float`)."""
    widths = [a.dtype.itemsize for a in arrs]
    if sum(widths) <= 8:
        img = np.zeros(n, np.uint64)
        for a, w in zip(arrs, widths):
            u = a.view(f"u{w}").astype(np.uint64)
            if a.dtype.kind == "i":  # sign bit flipped: small signed ranges stay contiguous
                u ^= np.uint64(1 << (8 * w - 1))
            if w < 8:
                img <<= np.uint64(8 * w)
                img |= u
            else:
                img = u  # the only key: nothing to shift
        return img
    buf = np.empty((n, sum(widths)), np.uint8)
    pos = 0
    for a, w in zip(arrs, widths):
        buf[:, pos : pos + w] = a.view(np.uint8).reshape(n, w)
        pos += w
    return buf.view(f"V{pos}").reshape(n)


def _first_seen(img: np.ndarray) -> tuple:
    """``(first, inv)``: the row of each distinct image's first occurrence,
    in first-seen order, and each row's index into it.  Small integer
    ranges take a sort-free first-occurrence table; others ``np.unique``."""
    n = img.size
    if img.dtype == np.uint64 and n:
        lo = img.min()
        span = int(img.max() - lo) + 1
        if span <= max(1024, 4 * n):
            off = (img - lo).astype(np.intp)
            first_of = np.full(span, n, np.intp)
            first_of[off[::-1]] = np.arange(n - 1, -1, -1)  # the smallest row wins
            present = np.flatnonzero(first_of < n)
            order = np.argsort(first_of[present], kind="stable")
            lut = np.empty(span, np.intp)
            lut[present[order]] = np.arange(order.size)
            return first_of[present[order]], lut[off]
    _uniq, first, inv = np.unique(img, return_index=True, return_inverse=True)
    order = np.argsort(first, kind="stable")  # np.unique sorts; rank by first occurrence
    rank = np.empty(order.size, np.intp)
    rank[order] = np.arange(order.size)
    return first[order], rank[inv.reshape(-1)]


class GroupState:
    """Incremental hash-aggregation state across batches (streaming: the
    input is consumed batch-by-batch, never concatenated).

    ``vectorized=True`` (the parallel executor's mode) keeps fixed-width
    keys as **columns**, one array per key in group order, and maps a
    batch's keys to group ids with vectorized numpy over the keys' bit
    images (:func:`_key_image`): distinct keys in first-seen row order, then
    a binary search in the table's sorted images; only new groups are
    appended, and nothing runs per group in Python.  The reference is the
    row loop over key tuples (a dict from tuple to group id), which the
    state switches to for good when a batch's keys have a validity mask or a
    float key holds a NaN or ``-0.0``; var-width keys and
    ``vectorized=False`` use it from the start.  Either way groups come out
    in first-seen row order.

    Partial states combine with ``merge`` — the morsel driver builds one
    state per morsel and merges them in morsel order, so the grouped output
    is deterministic regardless of worker count.

    ``map_s`` is the host time spent mapping keys to group ids (span
    ``dacp.agg.map``).
    """

    def __init__(
        self,
        keys: list,
        aggs: dict,
        mode: str,
        in_schema: Schema,
        vectorized: bool = False,
    ):
        self.keys = keys
        self.aggs = aggs
        self.mode = mode
        self.in_schema = in_schema
        self.vectorized = vectorized and all(not in_schema.field(k).dtype.is_varwidth for k in keys)
        self.ngroups = 0
        self.map_s = 0.0
        # columnar keys: one array per key in group order, their images, and
        # a lazily built (sorted images, group ids) lookup index
        self._kcols = [np.zeros(0, in_schema.field(k).dtype.np_dtype) for k in keys] if self.vectorized else None
        self._img = None
        self._index = None
        # the row loop's keys: key tuple -> group id, and tuples in group order
        self._gids = None if self.vectorized else {}
        self._rows = None if self.vectorized else []
        # state name -> numpy accumulator (grown as groups appear)
        self.acc: dict = {name: np.zeros(0, dt) for name, (_, dt) in self._state_specs().items()}

    def _state_specs(self) -> dict:
        """state name -> (init value, accumulator numpy dtype).

        Integer sum/min/max accumulate in int64 (exact — float64 would
        silently corrupt values past 2^53); floats accumulate in float64.
        """
        specs = {}
        for out, spec in self.aggs.items():
            fn = spec["fn"]
            if fn == "mean":
                specs[f"{out}__psum"] = (0.0, np.float64)
                specs[f"{out}__pcnt"] = (0, np.int64)
            elif fn == "count":
                specs[out] = (0, np.int64)
            else:
                src_dt = self.in_schema.field(_agg_src(out, spec, self.mode)).dtype
                if src_dt.is_integer:
                    if fn in ("min", "max") and src_dt.name == "uint64":
                        # int64 accumulation would wrap values past 2^63 and
                        # compare them under signed order — min over
                        # [1, 2^63+5] must be 1, not the wrapped negative
                        init = {"min": np.iinfo(np.uint64).max, "max": 0}[fn]
                        specs[out] = (init, np.uint64)
                    else:
                        init = {"sum": 0, "min": np.iinfo(np.int64).max, "max": np.iinfo(np.int64).min}[fn]
                        specs[out] = (init, np.int64)
                else:
                    init = {"sum": 0.0, "min": np.inf, "max": -np.inf}[fn]
                    specs[out] = (init, np.float64)
        return specs

    # -- keys -----------------------------------------------------------------
    @property
    def key_rows(self) -> list:
        """Key tuples in group order (``None`` for a null key)."""
        if self._rows is not None:
            return list(self._rows)
        if not self.keys:
            return [()] * self.ngroups
        return list(zip(*[c.tolist() for c in self._kcols]))

    def key_columns(self) -> list:
        """The key columns in group order, one ``Column`` per key."""
        fields = [self.in_schema.field(k) for k in self.keys]
        if self._rows is None:
            return [Column(f.dtype, values=np.ascontiguousarray(c)) for f, c in zip(fields, self._kcols)]
        return [self._key_column(f, [row[i] for row in self._rows]) for i, f in enumerate(fields)]

    def set_keys(self, other: "GroupState", take: np.ndarray) -> None:
        """Make this (empty) state's groups ``other``'s groups ``take``, in
        that order."""
        if other._rows is None and self._rows is None:
            self._kcols = [c[take] for c in other._kcols]
            self._img = None if other._img is None else other._img[take]
        else:
            self._to_rows()
            rows = other.key_rows
            self._rows = [rows[g] for g in take.tolist()]
            self._gids = {kt: i for i, kt in enumerate(self._rows)}
        self.ngroups = len(take)

    def load_keys(self, cols: list) -> None:
        """Make this (empty) state's groups the rows of the key columns
        ``cols``: distinct keys, in group order."""
        n = len(cols[0]) if cols else 0
        if self._rows is None and all(c.validity is None for c in cols) and not any(_odd_float(c.values) for c in cols):
            self._kcols = [np.ascontiguousarray(c.values) for c in cols]
        else:
            self._to_rows()
            self._rows = list(zip(*[c.to_pylist() for c in cols]))
            self._gids = {kt: i for i, kt in enumerate(self._rows)}
        self.ngroups = n

    def _images(self) -> np.ndarray:
        if self._img is None:
            self._img = _key_image(self._kcols, self.ngroups)
        return self._img

    def _to_rows(self) -> None:
        """Switch to the row loop for good (its dict is the reference)."""
        if self._rows is None:
            self._rows = self.key_rows
            self._gids = {kt: i for i, kt in enumerate(self._rows)}
            self._kcols = self._img = self._index = None

    def _intern_rows(self, key_tuples) -> np.ndarray:
        """Row loop: map key tuples to (new or existing) group ids."""
        out = np.empty(len(key_tuples), dtype=np.int64)
        gids = self._gids
        for i, kt in enumerate(key_tuples):
            g = gids.get(kt)
            if g is None:
                g = len(gids)
                gids[kt] = g
                self._rows.append(kt)
            out[i] = g
        self.ngroups = len(self._rows)
        return out

    def _map(self, arrs: list, img: np.ndarray, distinct: bool) -> np.ndarray:
        """Columnar keys → group ids, appending new groups in first-seen
        order.  ``distinct``: every row's key differs (a partial state's)."""
        n, g = img.size, self.ngroups
        if n == g and np.array_equal(img, self._images()):
            return np.arange(n)  # the table's own keys, in its order
        if distinct:
            first, inv = np.arange(n), None
        else:
            first, inv = _first_seen(img)
        seen = img[first]
        if g == 0:
            ids = np.arange(seen.size)
            new = ids
        else:
            if self._index is None:
                order = np.argsort(self._images(), kind="stable")
                self._index = (self._img[order], order)
            sorted_img, sorted_gid = self._index
            pos = np.minimum(np.searchsorted(sorted_img, seen), g - 1)
            hit = sorted_img[pos] == seen
            ids = np.where(hit, sorted_gid[pos], -1)
            new = np.flatnonzero(~hit)
            ids[new] = np.arange(g, g + new.size)
        if new.size:
            rows = first[new]
            self._img = np.concatenate([self._images(), seen[new]])
            self._kcols = [np.concatenate([c, a[rows]]) for c, a in zip(self._kcols, arrs)]
            self._index = None
            self.ngroups = g + new.size
        return ids if inv is None else ids[inv]

    def _factorize(self, batch: RecordBatch) -> np.ndarray:
        """Per-row group ids for one batch, new groups interned in
        first-seen row order.  Key columns with a validity mask (null keys
        must stay distinct from the sentinel value) or a NaN / ``-0.0`` float
        key send the state to the row loop."""
        t0 = time.perf_counter()
        n = batch.num_rows
        with span("dacp.agg.map", rows=n):
            key_cols = [batch.column(k) for k in self.keys]
            gidx = None
            if self._rows is None and all(c.validity is None for c in key_cols):
                arrs = [np.ascontiguousarray(c.values) for c in key_cols]
                if not any(_odd_float(a) for a in arrs):
                    gidx = self._map(arrs, _key_image(arrs, n), distinct=False)
            if gidx is None:
                self._to_rows()
                key_lists = [c.to_pylist() for c in key_cols]
                gidx = self._intern_rows(list(zip(*key_lists)) if key_cols else [()] * n)
        self.map_s += time.perf_counter() - t0
        return gidx

    def _grow(self) -> None:
        """Grow every accumulator to the current group count in one shot."""
        ngroups = self.ngroups
        for name, (init, dt) in self._state_specs().items():
            cur = self.acc[name]
            if len(cur) < ngroups:
                self.acc[name] = np.concatenate([cur, np.full(ngroups - len(cur), init, dt)])

    def update(self, batch: RecordBatch) -> None:
        n = batch.num_rows
        if n == 0:
            return
        gidx = self._factorize(batch)
        self._grow()
        ngroups = self.ngroups
        counts = None

        def _counts():
            nonlocal counts
            if counts is None:
                counts = np.bincount(gidx, minlength=ngroups)
            return counts

        # scatter each batch's values straight into the (dtype-exact)
        # accumulators
        for out, spec in self.aggs.items():
            fn = spec["fn"]
            if fn == "count":
                if self.mode == "final":
                    vals = np.asarray(batch.column(out).values, dtype=np.int64)
                    np.add.at(self.acc[out], gidx, vals)
                else:
                    self.acc[out] += _counts()
            elif fn == "mean":
                pc, ps = f"{out}__pcnt", f"{out}__psum"
                if self.mode == "final":
                    np.add.at(self.acc[ps], gidx, np.asarray(batch.column(ps).values, np.float64))
                    np.add.at(self.acc[pc], gidx, np.asarray(batch.column(pc).values, np.int64))
                else:
                    vals = np.asarray(batch.column(spec["column"]).to_numpy(), dtype=np.float64)
                    np.add.at(self.acc[ps], gidx, vals)
                    self.acc[pc] += _counts()
            else:  # sum / min / max
                cur = self.acc[out]
                vals = np.asarray(batch.column(_agg_src(out, spec, self.mode)).to_numpy()).astype(cur.dtype)
                op = {"sum": np.add, "min": np.minimum, "max": np.maximum}[fn]
                op.at(cur, gidx, vals)

    def merge(self, other: "GroupState") -> "GroupState":
        """Combine another partial state into this one (same keys/aggs/mode).
        Each of ``other``'s groups maps to a distinct group here, so the
        combine is a plain fancy-indexed binary op per accumulator."""
        self.merge_indexed(other)
        return self

    def merge_indexed(self, other: "GroupState") -> np.ndarray:
        """``merge``, returning the group index of each of ``other``'s groups
        in this state (the spill path maps per-group metadata through it)."""
        m = other.ngroups
        if m == 0:
            return np.zeros(0, np.int64)
        t0 = time.perf_counter()
        with span("dacp.agg.map", rows=m):
            if self._rows is None and other._rows is None:
                if self.ngroups == 0:
                    self.set_keys(other, np.arange(m))
                    idx = np.arange(m)
                else:
                    idx = self._map(other._kcols, other._images(), distinct=True)
            else:
                self._to_rows()
                idx = self._intern_rows(other.key_rows)
        self.map_s += time.perf_counter() - t0
        self._grow()
        # groups in the same order (the common case: partials of one grid)
        # combine through a slice, in place
        at = slice(0, m) if m == self.ngroups and np.array_equal(idx, np.arange(m)) else idx
        for out, spec in self.aggs.items():
            fn = spec["fn"]
            if fn == "mean":
                for part in (f"{out}__psum", f"{out}__pcnt"):
                    self.acc[part][at] += other.acc[part][:m]
            else:
                op = {"sum": np.add, "count": np.add, "min": np.minimum, "max": np.maximum}[fn]
                cur = self.acc[out]
                cur[at] = op(cur[at], other.acc[out][:m])
        return idx

    def approx_nbytes(self) -> int:
        """Accounted size of this state: accumulator buffers plus an
        estimate of the python-side group directory (dict slot + key tuple
        + interned key values).  Used by the executor's memory budget — an
        estimate is fine, the budget is a spill trigger, not an allocator."""
        acc = sum(a.nbytes for a in self.acc.values())
        per_group = 56  # dict entry + tuple header
        for k in self.keys:
            dt = self.in_schema.field(k).dtype
            per_group += 24 if dt.is_varwidth else dt.width + 8
        return acc + self.ngroups * per_group

    def _key_column(self, f, vals: list) -> Column:
        """Key output column; null keys (masked input rows) materialize as a
        validity-masked column rather than crashing ``from_values``."""
        null = [v is None for v in vals]
        if not any(null):
            return Column.from_values(f.dtype, vals)
        fill = "" if f.dtype.name == "string" else (b"" if f.dtype.name == "binary" else 0)
        c = Column.from_values(f.dtype, [fill if m else v for v, m in zip(vals, null)])
        c.validity = np.asarray([not m for m in null], dtype=bool)
        return c

    def result(self, out_schema: Schema) -> RecordBatch:
        ngroups = self.ngroups
        data = dict(zip(self.keys, self.key_columns()))
        for out, spec in self.aggs.items():
            fn = spec["fn"]
            if fn == "mean":
                psum = self.acc[f"{out}__psum"]
                pcnt = self.acc[f"{out}__pcnt"]
                if self.mode == "partial":
                    data[f"{out}__psum"] = psum
                    data[f"{out}__pcnt"] = pcnt
                else:
                    data[out] = psum / np.maximum(pcnt, 1)
            else:
                f = out_schema.field(out)
                vals = self.acc[out]
                data[out] = vals.astype(f.dtype.np_dtype) if ngroups else np.zeros(0, f.dtype.np_dtype)
        cols = []
        for f in out_schema:
            vals = data[f.name]
            cols.append(vals if isinstance(vals, Column) else Column.from_values(f.dtype, np.asarray(vals, f.dtype.np_dtype)))
        return RecordBatch(out_schema, cols)


def _eval_aggregate(node: Node, ins: list) -> StreamingDataFrame:
    (src,) = ins
    keys = list(node.params["keys"])
    aggs = dict(node.params["aggs"])
    mode = node.params.get("mode", "full")
    missing = [k for k in keys if k not in src.schema]
    if missing:
        raise SchemaError(f"aggregate keys missing from input: {missing}")
    out_schema = Schema(_agg_out_fields(src.schema, keys, aggs, mode))

    def gen():
        state = GroupState(keys, aggs, mode, src.schema)
        for b in src.iter_batches():
            state.update(b)
        yield state.result(out_schema)

    return StreamingDataFrame(out_schema, gen)


# back-compat alias for the pre-refactor private name
_GroupState = GroupState


# ---------------------------------------------------------------------------
# join (inner equi-join: right side builds the hash table, left side probes)
# ---------------------------------------------------------------------------
def join_schema(left: Schema, right: Schema, on: list) -> tuple:
    return _join_schema(left, right, on)


def build_join_table(build: RecordBatch, on: list) -> dict:
    """key tuple -> row indices of the (materialized) build side."""
    table: dict = {}
    if build.num_rows:
        for i, kt in enumerate(zip(*[build.column(k).to_pylist() for k in on])):
            table.setdefault(kt, []).append(i)
    return table


def join_probe_indices(batch: RecordBatch, table: dict, on: list) -> tuple:
    """(probe row indices, build row indices) of the matches of one morsel —
    probe-major, build rows in build order within each probe row."""
    probe_keys = list(zip(*[batch.column(k).to_pylist() for k in on]))
    lidx, ridx = [], []
    for i, kt in enumerate(probe_keys):
        for j in table.get(kt, ()):
            lidx.append(i)
            ridx.append(j)
    return np.asarray(lidx, np.int64), np.asarray(ridx, np.int64)


def join_probe_morsel(
    batch: RecordBatch, build: RecordBatch, table: dict, on: list, payload: list, schema: Schema
) -> RecordBatch | None:
    """Probe one morsel against a prebuilt hash table; None when no matches."""
    if batch.num_rows == 0:
        return None
    lidx, ridx = join_probe_indices(batch, table, on)
    if len(lidx) == 0:
        return None
    lpart = batch.take(lidx)
    rpart = build.take(ridx)
    cols = list(lpart.columns)
    for name in payload:
        cols.append(rpart.column(name))
    return RecordBatch(schema, cols)


def _join_schema(left: Schema, right: Schema, on: list) -> tuple:
    """(schema, right_payload_names, rename_map).  Right non-key columns that
    collide with left names get an ``_r`` suffix."""
    for k in on:
        if k not in left or k not in right:
            raise SchemaError(f"join key {k!r} missing from an input")
    fields = list(left.fields)
    left_names = {f.name for f in fields}
    payload, rename = [], {}
    for f in right:
        if f.name in on:
            continue
        name = f.name
        if name in left_names:
            name = f"{f.name}_r"
            if name in left_names:
                raise SchemaError(f"join output column collision on {name!r}")
            rename[f.name] = name
        fields.append(Field(name, f.dtype, f.nullable, f.metadata))
        payload.append(f.name)
    return Schema(fields), payload, rename


def _eval_join(node: Node, ins: list) -> StreamingDataFrame:
    left, right = ins
    on = list(node.params["on"])
    schema, payload, _rename = _join_schema(left.schema, right.schema, on)

    def gen():
        # build: materialize the right side into key -> row indices
        build = right.collect()
        table = build_join_table(build, on)
        # probe: stream the left side, emitting matches per batch
        for b in left.iter_batches():
            out = join_probe_morsel(b, build, table, on, payload, schema)
            if out is not None:
                yield out

    return StreamingDataFrame(schema, gen)


def _eval_union(node: Node, ins: list) -> StreamingDataFrame:
    schema = ins[0].schema
    for s in ins[1:]:
        if not s.schema.equals(schema):
            raise SchemaError("union over mismatched schemas")

    def gen():
        for s in ins:
            yield from s.iter_batches()

    return StreamingDataFrame(schema, gen)


_EVAL = {
    "filter": _eval_filter,
    "select": _eval_select,
    "project": _eval_project,
    "map": _eval_map,
    "rebatch": _eval_rebatch,
    "limit": _eval_limit,
    "union": _eval_union,
    "aggregate": _eval_aggregate,
    "join": _eval_join,
}


def execute_node(node: Node, inputs: list) -> StreamingDataFrame:
    try:
        fn = _EVAL[node.op]
    except KeyError:
        raise PlanError(f"operator {node.op!r} has no local evaluator") from None
    return fn(node, inputs)


def execute(dag: Dag, source_resolver: Callable[[Node], StreamingDataFrame]) -> StreamingDataFrame:
    """Wire the DAG into a lazy pull pipeline and return the output SDF.

    ``source_resolver`` materializes ``source`` / ``exchange`` leaves — the
    server resolves URIs against its catalog; the scheduler resolves exchanges
    against remote pulls.
    """
    materialized: dict = {}
    for nid in dag.topological_order():
        node = dag.nodes[nid]
        if node.op in ("source", "exchange"):
            materialized[nid] = source_resolver(node)
        else:
            materialized[nid] = execute_node(node, [materialized[i] for i in node.inputs])
    return materialized[dag.output]
