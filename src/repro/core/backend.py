"""Pluggable compute backends for the morsel executor (paper §III-D).

A *backend* supplies the vectorized kernels that operator evaluators run on
each morsel: predicate evaluation, filtering, the fused filter+select that
the executor peepholes out of adjacent Filter→Select pairs, projection
arithmetic, and per-morsel segment reductions for partial aggregation.
Backends are looked up in a **kernel registry** keyed ``(backend name,
op name)``; resolution falls back to the numpy reference kernels, so a
backend only overrides the ops it accelerates and everything else keeps
reference semantics bit-for-bit.

Two backends ship in-tree:

  * ``numpy``  — the reference implementation (always present).
  * ``pallas`` — dispatches eligible morsels to the JAX/Pallas kernels in
    ``repro.kernels``.  Columns cross into the kernels as **int32
    bit-planes** (one plane per 4 bytes of width), so compaction and
    reduction matmuls move bit patterns exactly — the kernels are
    bit-identical to numpy for every fixed-width dtype, including
    ``-0.0``, NaN payloads, Inf, and full-range int64.  Eligibility is
    decided per morsel *and per column*, by explicit checks before a
    launch; anything outside a kernel's envelope — var-width columns,
    validity masks, unsupported literal / column dtype pairings — runs the
    numpy kernel, so results are identical either way.  A kernel that fails
    to compile or run fails the request: no error turns into a silent numpy
    answer, and resolving ``pallas`` without jax installed raises.

Dispatchable ops:

    filter_select   predicate ``col <cmp> lit`` with ``<cmp>`` in
                    {<, <=, >, >=, ==, !=}; predicate column float32 /
                    int32 / int64; projected columns any fixed-width dtype
    filter          the unfused form (projects every column)
    project         arithmetic Expr chains (+ - * over float32 or int32
                    columns, python-scalar literals)
    segment_reduce  per-group partial folds: count always, sum for integer
                    columns (8-bit-limb exact, wraparound-identical to
                    numpy), min/max for finite float32 without ``-0.0``,
                    int32-safe integer,
                    and the wide dtypes int64 / uint32 / uint64 / float64
                    via a two-word hi/lo compare — two masked-reduce kernel
                    passes over an order-preserving int64 key image (uint64:
                    top-bit flip; float64: sign-magnitude fold, NaN and
                    -0.0 ineligible), exact over the full 64-bit range;
                    float sums and mean partial sums fold through an
                    explicit **f64-accumulating reference path** (host-side
                    — kernel lanes are 32-bit — counted in
                    ``PallasBackend.f64_folds``) instead of silently falling
                    back.  Up to 256 groups a morsel the one-hot fold spans
                    every group; above that the rows are sorted by group
                    and the fused kernel folds them window by window (256
                    groups a window), so the device cost grows with rows,
                    not rows x groups (wide min/max then stay with numpy)

``get_backend("auto")`` selects pallas only when jax reports a real TPU;
interpret-mode Pallas on CPU is for correctness tests, not speed.  The
first kernel load points JAX's persistent compilation cache at
``JAX_COMPILATION_CACHE_DIR`` when set, else at the checkout's fixed
``.jax_cache`` (see :func:`configure_compile_cache`).
"""

from __future__ import annotations

import importlib.util
import math
import threading
from pathlib import Path
from typing import Callable

import numpy as np

from repro.core.batch import Column, RecordBatch
from repro.core.env import env_str
from repro.core.expr import Expr
from repro.core.trace import span

__all__ = [
    "ComputeBackend",
    "KERNELS",
    "register_kernel",
    "get_backend",
    "available_backends",
    "BACKENDS",
    "FUSED_INELIGIBLE",
    "FusedChainPlan",
    "plan_fused_chain",
    "configure_compile_cache",
    "COMPILE_CACHE_DIR",
    "resolve_device",
]


# ---------------------------------------------------------------------------
# kernel registry
# ---------------------------------------------------------------------------
KERNELS: dict = {"numpy": {}, "pallas": {}}


def register_kernel(backend: str, op: str):
    """Register ``fn(backend_instance, ...)`` as ``op`` for ``backend``."""

    def deco(fn: Callable) -> Callable:
        KERNELS.setdefault(backend, {})[op] = fn
        return fn

    return deco


class ComputeBackend:
    """Kernel dispatch facade.  Instances are thread-safe; their only state
    is counters.

    ``agg_morsels`` counts the grouped morsels the executor folded with this
    backend and ``agg_host_s`` the host seconds they spent mapping keys to
    group ids and merging into the request's state (``GroupState.map_s``
    and the breaker's merge)."""

    name = "numpy"

    def __init__(self):
        self._counter_lock = threading.Lock()
        self.agg_morsels = 0
        self.agg_host_s = 0.0

    def count(self, counter: str, k=1) -> None:
        """Add ``k`` to a counter, from any thread."""
        with self._counter_lock:
            setattr(self, counter, getattr(self, counter) + k)

    def kernel(self, op: str) -> Callable:
        impl = KERNELS.get(self.name, {}).get(op)
        if impl is None:
            impl = KERNELS["numpy"][op]
        return impl

    # -- morsel-level entry points (used by operator evaluators) ------------
    def eval_predicate(self, batch: RecordBatch, predicate: Expr) -> np.ndarray:
        return self.kernel("eval_predicate")(self, batch, predicate)

    def filter(self, batch: RecordBatch, predicate: Expr):
        """Apply a predicate; returns the surviving rows or ``None`` when the
        whole morsel is filtered out (no empty frames downstream)."""
        return self.kernel("filter")(self, batch, predicate)

    def filter_select(self, batch: RecordBatch, predicate: Expr, columns: list):
        """Fused filter + column projection (the executor's peephole)."""
        return self.kernel("filter_select")(self, batch, predicate, columns)

    def project(self, batch: RecordBatch, exprs: dict, out_schema):
        """Projection arithmetic over one morsel (shaped to ``out_schema``)."""
        return self.kernel("project")(self, batch, exprs, out_schema)

    def segment_reduce(self, gidx: np.ndarray, ngroups: int, specs: list, n_rows: int) -> dict:
        """Per-group partial reductions for one factorized morsel.

        ``specs`` is ``[(state_name, fn, values), ...]`` with ``fn`` in
        {count, sum, fsum, min, max} (``values`` is None for count; ``fsum``
        marks a float sum from a fresh state, foldable in the backend's
        f64-accumulating reference path).  Returns a dict mapping the state
        names the backend accelerated to per-group arrays of length
        ``ngroups``; callers scatter the rest with numpy.  The numpy
        backend accelerates nothing (``{}``)."""
        return self.kernel("segment_reduce")(self, gidx, ngroups, specs, n_rows)


# ---------------------------------------------------------------------------
# numpy reference kernels
# ---------------------------------------------------------------------------
@register_kernel("numpy", "eval_predicate")
def _np_eval_predicate(bk, batch: RecordBatch, predicate: Expr) -> np.ndarray:
    return np.asarray(predicate.evaluate(batch), dtype=bool)


@register_kernel("numpy", "filter")
def _np_filter(bk, batch: RecordBatch, predicate: Expr):
    mask = _np_eval_predicate(bk, batch, predicate)
    if mask.all():
        return batch
    if not mask.any():
        return None
    return batch.filter(mask)


@register_kernel("numpy", "filter_select")
def _np_filter_select(bk, batch: RecordBatch, predicate: Expr, columns: list):
    out = _np_filter(bk, batch, predicate)
    return None if out is None else out.select(columns)


@register_kernel("numpy", "project")
def _np_project(bk, batch: RecordBatch, exprs: dict, out_schema):
    from repro.core.operators import project_morsel

    return project_morsel(batch, exprs, out_schema)


@register_kernel("numpy", "segment_reduce")
def _np_segment_reduce(bk, gidx, ngroups, specs, n_rows) -> dict:
    return {}  # reference path: GroupState scatters with numpy ufuncs


class NumpyBackend(ComputeBackend):
    name = "numpy"


# ---------------------------------------------------------------------------
# int32 bit-plane column codec (host side of the pallas kernels)
# ---------------------------------------------------------------------------
_WIDE = {"float64", "int64", "uint64"}  # two planes: hi word, lo word
_NARROW_INT = {"int8", "int16", "uint8", "uint16", "bool"}  # widened exactly


def _plane_count(dtype_name: str) -> int:
    return 2 if dtype_name in _WIDE else 1


def _col_planes(values: np.ndarray, dtype_name: str) -> list:
    """Encode one fixed-width column into int32 bit-planes (lossless)."""
    v = np.ascontiguousarray(values)
    if dtype_name in _WIDE:
        b = v.view(np.int64)
        hi = (b >> 32).astype(np.int32)
        lo = (b & np.int64(0xFFFFFFFF)).astype(np.uint32).view(np.int32)
        return [hi, lo]
    if dtype_name == "float16":
        return [v.view(np.uint16).astype(np.int32)]
    if dtype_name in _NARROW_INT:
        return [v.astype(np.int32)]
    return [v.view(np.int32)]  # float32 / int32 / uint32


def _planes_to_values(planes: np.ndarray, dtype) -> np.ndarray:
    """Decode (n, planes) int32 back into the column's numpy dtype."""
    name = dtype.name
    if name in _WIDE:
        hi = planes[:, 0].astype(np.int64)
        lo = np.ascontiguousarray(planes[:, 1]).view(np.uint32).astype(np.int64)
        return ((hi << 32) | lo).view(dtype.np_dtype)
    if name == "float16":
        return planes[:, 0].astype(np.uint16).view(np.float16)
    if name in _NARROW_INT:
        return planes[:, 0].astype(dtype.np_dtype)
    return np.ascontiguousarray(planes[:, 0]).view(dtype.np_dtype)


# ---------------------------------------------------------------------------
# pallas backend
# ---------------------------------------------------------------------------
# fixed, inside the checkout: the cache key includes the directory, so a
# path that moved between runs would never hit
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Give the kernels a persistent compilation cache; returns its
    directory.  ``JAX_COMPILATION_CACHE_DIR`` (which jax reads at import)
    wins when set; otherwise the cache goes to the fixed
    :data:`COMPILE_CACHE_DIR`.  Mosaic compiles take a second or two, so
    every compile is cached, not only those above jax's default minimum."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
        compilation_cache.reset_cache()  # re-initialize on the next compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir


class PallasBackend(ComputeBackend):
    name = "pallas"
    tile = 256

    def __init__(self):
        super().__init__()
        self._kernel_mod = None
        self._lock = threading.Lock()
        self.kernel_calls = 0  # observability: kernel dispatch count
        # morsels whose float32 arithmetic left the kernels' exact envelope
        # (inf, NaN, subnormal, flushed underflow) and were recomputed by
        # the numpy reference
        self.envelope_rejects = 0
        # float sums folded through the f64-accumulating reference path
        # (host-side; the kernels' 32-bit lanes cannot hold f64) — the
        # explicit, counted successor of the old silent fallback
        self.f64_folds = 0
        # grouped morsels whose float key held a NaN or -0.0, folded by the
        # per-op path instead of the fused one
        self.key_rejects = 0

    def _ops(self):
        """Import the jit'd kernel wrappers once and place the compile
        cache; an import failure propagates."""
        if self._kernel_mod is None:
            with self._lock:
                if self._kernel_mod is None:
                    from repro.kernels import ops as kernel_ops

                    configure_compile_cache()
                    self._kernel_mod = kernel_ops
        return self._kernel_mod


# -- fused filter+select ----------------------------------------------------
_CMP_OPS = {"lt", "le", "gt", "ge", "eq", "ne"}
_PRED_KINDS = {"float32": "f32", "int32": "i32", "int64": "i64"}
_INT32_SIGN = 0x80000000


def _normalize_threshold(t, dtype_name: str, op: str):
    """Map a predicate literal onto kernel-comparable form for a column
    dtype.  Returns ``(kind, op, t_hi, t_lo)`` or ``None`` when the f32/int
    kernel comparison could not reproduce numpy's promotion semantics
    (e.g. a strong float64 scalar against a float32 column that is not
    exactly representable, or a float literal against an int64 column).
    Non-integer float literals against int32 columns rewrite to the
    equivalent integer comparison (``v > 2.5  ⇔  v > 2``)."""
    if isinstance(t, (bool, np.bool_)):
        return None
    if dtype_name == "float32":
        if isinstance(t, (int, float)) or (isinstance(t, np.floating) and t.dtype.itemsize <= 4):
            # weak python scalars (and <=32-bit float scalars) compare in
            # float32 under numpy-2 promotion — the kernel's native compare
            try:
                return ("f32", op, float(np.float32(t)), 0)
            except (OverflowError, ValueError):
                return None
        if isinstance(t, (np.integer, np.floating)):
            # strong 64-bit scalars promote the reference comparison to
            # float64; parity holds only for exactly-representable values
            thr = float(np.float32(t))
            return ("f32", op, thr, 0) if thr == t else None
        return None
    if dtype_name in ("int32", "int64"):
        if isinstance(t, np.uint64):
            return None  # numpy promotes int64 vs uint64 to float64
        if isinstance(t, (int, np.integer)):
            ti = int(t)
        elif isinstance(t, (float, np.floating)) and dtype_name == "int32":
            tf = float(t)
            if not np.isfinite(tf):
                return None
            if not tf.is_integer():
                if op in ("eq", "ne"):
                    return None  # constant mask; let numpy broadcast it
                # v <cmp> 2.5 is an integer comparison against floor(2.5)
                op = {"gt": "gt", "ge": "gt", "lt": "le", "le": "le"}[op]
                ti = int(np.floor(tf))
            else:
                ti = int(tf)
        else:
            return None  # float literals vs int64 compare in lossy float64
        lo, hi = (-(2**31), 2**31 - 1) if dtype_name == "int32" else (-(2**63), 2**63 - 1)
        if not (lo <= ti <= hi):
            return None  # reference raises (weak) or promotes (strong)
        if dtype_name == "int32":
            return ("i32", op, ti, 0)
        t_hi = ti >> 32
        t_lo = (ti & 0xFFFFFFFF) ^ _INT32_SIGN  # sign-flipped low word
        if t_lo >= 2**31:
            t_lo -= 2**32
        return ("i64", op, t_hi, t_lo)
    return None


def _fused_plan(batch: RecordBatch, predicate: Expr, columns: list):
    """Eligibility check for the Pallas fused kernel.  Returns
    ``(op, kind, t_hi, t_lo, pred_name)`` or ``None`` (→ numpy fallback)."""
    if not (
        isinstance(predicate, Expr)
        and predicate.op in _CMP_OPS
        and isinstance(predicate.args[0], Expr)
        and predicate.args[0].op == "col"
        and isinstance(predicate.args[1], Expr)
        and predicate.args[1].op == "lit"
    ):
        return None
    pred_name = predicate.args[0].args[0]
    schema = batch.schema
    if pred_name not in schema:
        return None
    pf = schema.field(pred_name)
    if pf.dtype.name not in _PRED_KINDS or batch.column(pred_name).validity is not None:
        return None
    norm = _normalize_threshold(predicate.args[1].args[0], pf.dtype.name, predicate.op)
    if norm is None:
        return None
    kind, op, t_hi, t_lo = norm
    for name in columns:
        if name not in schema:
            return None
        f = schema.field(name)
        if f.dtype.is_varwidth or batch.column(name).validity is not None:
            return None
    return op, kind, t_hi, t_lo, pred_name


@register_kernel("pallas", "filter_select")
def _pl_filter_select(bk: PallasBackend, batch: RecordBatch, predicate: Expr, columns: list):
    plan = _fused_plan(batch, predicate, columns)
    if plan is None or batch.num_rows == 0:
        return _np_filter_select(bk, batch, predicate, columns)
    op, kind, t_hi, t_lo, pred_name = plan
    tile = bk.tile
    n = batch.num_rows
    n_pad = -(-n // tile) * tile
    out_schema = batch.schema.select(columns)
    pred_planes = _col_planes(batch.column(pred_name).values, batch.schema.field(pred_name).dtype.name)
    pred_arr = np.zeros((n_pad, len(pred_planes)), np.int32)
    for j, p in enumerate(pred_planes):
        pred_arr[:n, j] = p
    spans = []  # (plane start, plane count) per output column
    pos = 0
    for f in out_schema:
        k = _plane_count(f.dtype.name)
        spans.append((pos, k))
        pos += k
    table = np.zeros((n_pad, pos), np.int32)
    for f, (start, _k) in zip(out_schema, spans):
        for j, p in enumerate(_col_planes(batch.column(f.name).values, f.dtype.name)):
            table[:n, start + j] = p
    t_hi_bits = int(np.array([t_hi], np.float32).view(np.int32)[0]) if kind == "f32" else int(t_hi)
    scalars = np.asarray([n, t_hi_bits, int(t_lo)], np.int32)
    out, counts = bk._ops().filter_select_planes(pred_arr, table, scalars, op, kind, tile=tile)
    bk.kernel_calls += 1
    counts = np.asarray(counts)
    n_sel = int(counts.sum())
    if n_sel == 0:
        return None
    out = np.asarray(out)
    compact = np.concatenate([out[i * tile : i * tile + int(c)] for i, c in enumerate(counts) if c])
    cols = [
        Column(f.dtype, values=_planes_to_values(compact[:, start : start + k], f.dtype))
        for f, (start, k) in zip(out_schema, spans)
    ]
    return RecordBatch(out_schema, cols)


@register_kernel("pallas", "filter")
def _pl_filter(bk: PallasBackend, batch: RecordBatch, predicate: Expr):
    # the unfused form projects every column through the plane kernel
    return _pl_filter_select(bk, batch, predicate, list(batch.schema.names))


# -- fused project arithmetic ----------------------------------------------
# int div/mod promote to float64 in numpy; a TPU's float32 division is not
# correctly rounded (off by an ulp on about a third of ordinary operands)
_ARITH = {"add", "sub", "mul"}


def _contraction_safe(op: str, a, b) -> bool:
    """XLA's CPU backend contracts a float ``mul`` feeding ``add``/``sub``
    into a single-rounding FMA during LLVM codegen (nothing at the HLO level
    survives to prevent it), while numpy rounds the product separately — a
    1-ulp divergence whenever the product is inexact.  Only exact products
    are immune, so a float32 mul may sit directly under add/sub solely when
    one factor is a power-of-two literal (a mantissa-preserving scale).
    The TPU rounds such a product separately, as numpy does; the rule holds
    for the CPU interpreter.  Integer arithmetic is exact."""
    if op not in ("add", "sub"):
        return True
    for t in (a, b):
        if t[0] != "mul":
            continue
        if not any(
            s[0] == "lit" and _is_pow2_f32(s[1]) for s in (t[1], t[2])
        ):
            return False
    return True


_F32_MIN_NORMAL = 2.0**-126


def _f32_lit(v):
    """A float32 arithmetic literal, or None.  Weak python scalars (and
    <=32-bit float scalars) keep float32 arithmetic under numpy promotion;
    the kernels' exact envelope also needs the literal finite and zero or
    normal as a float32 (a subnormal would be flushed on the device)."""
    if not (isinstance(v, (int, float)) or (isinstance(v, np.floating) and v.dtype.itemsize <= 4)):
        return None
    try:
        with np.errstate(over="ignore"):
            v32 = float(np.float32(v))
    except OverflowError:
        return None
    if not math.isfinite(v32) or 0.0 < abs(v32) < _F32_MIN_NORMAL:
        return None
    return float(v)


def _is_pow2_f32(v) -> bool:
    v32 = float(np.float32(v))
    return v32 != 0.0 and math.isfinite(v32) and abs(math.frexp(v32)[0]) == 0.5


def _arith_descr(e, batch: RecordBatch, group: str, col_idx: dict):
    """Lower an Expr subtree to a kernel descriptor, interning column
    indices into ``col_idx``.  Returns None when any node falls outside the
    kernel envelope for ``group`` ("float32" | "int32")."""
    if not isinstance(e, Expr):
        return None
    if e.op == "col":
        name = e.args[0]
        if name not in batch.schema:
            return None
        f = batch.schema.field(name)
        if f.dtype.name != group or batch.column(name).validity is not None:
            return None
        if name not in col_idx:
            col_idx[name] = len(col_idx)
        return ("col", col_idx[name])
    if e.op == "lit":
        v = e.args[0]
        if isinstance(v, (bool, np.bool_)):
            return None
        if group == "float32":
            v = _f32_lit(v)
            return None if v is None else ("lit", v)
        if isinstance(v, (int, np.integer)) and not isinstance(v, np.uint64):
            vi = int(v)
            if isinstance(v, np.int64) or not (-(2**31) <= vi <= 2**31 - 1):
                return None  # would promote to int64 (or raise) in numpy
            return ("lit", vi)
        return None
    if e.op not in _ARITH or len(e.args) != 2:
        return None
    a = _arith_descr(e.args[0], batch, group, col_idx)
    if a is None:
        return None
    b = _arith_descr(e.args[1], batch, group, col_idx)
    if b is None:
        return None
    if group == "float32" and not _contraction_safe(e.op, a, b):
        return None
    return (e.op, a, b)


@register_kernel("pallas", "project")
def _pl_project(bk: PallasBackend, batch: RecordBatch, exprs: dict, out_schema):
    from repro.core.operators import project_morsel

    if batch.num_rows == 0:
        return project_morsel(batch, exprs, out_schema)
    # plan each expression independently (per-column eligibility)
    groups: dict = {}  # group dtype -> (col_idx, [(out name, descr)])
    for name, e in exprs.items():
        f = out_schema.field(name)
        if f.dtype.name not in ("float32", "int32"):
            continue
        group = f.dtype.name
        col_idx = groups.setdefault(group, ({}, []))[0]
        snapshot = dict(col_idx)
        descr = _arith_descr(e, batch, group, col_idx)
        if descr is None or descr[0] in ("col", "lit"):
            col_idx.clear()
            col_idx.update(snapshot)  # drop columns interned by the failed plan
            continue
        groups[group][1].append((name, descr))
    planned = {name: None for g in groups.values() for name, _ in g[1]}
    if not planned:
        return project_morsel(batch, exprs, out_schema)
    n = batch.num_rows
    tile = bk.tile
    n_pad = -(-n // tile) * tile
    kernel_ops = bk._ops()
    for group, (col_idx, outs) in groups.items():
        if not outs:
            continue
        np_dt = np.dtype(group)
        table = np.zeros((n_pad, max(1, len(col_idx))), np_dt)
        for cname, j in col_idx.items():
            table[:n, j] = batch.column(cname).values
        res = np.asarray(kernel_ops.project_tiles(table, tuple(d for _, d in outs), tile=tile))
        bk.kernel_calls += 1
        if res[:n, -1].any():  # float32 arithmetic left the exact envelope
            bk.envelope_rejects += 1
            return project_morsel(batch, exprs, out_schema)
        for j, (name, _d) in enumerate(outs):
            planned[name] = np.ascontiguousarray(res[:n, j])
    # assemble exactly like the reference evaluator: kernel outputs for the
    # planned exprs, numpy evaluation (+dtype coercion) for the rest
    new_cols = {}
    for name, e in exprs.items():
        f = out_schema.field(name)
        vals = planned.get(name)
        if vals is None:
            vals = np.asarray(e.evaluate(batch))
            if vals.ndim == 0:
                vals = np.full(batch.num_rows, vals[()])
            if not f.dtype.is_varwidth and vals.dtype != f.dtype.np_dtype:
                vals = vals.astype(f.dtype.np_dtype)
        new_cols[name] = Column.from_values(f.dtype, vals)
    cols = [new_cols[f.name] if f.name in new_cols else batch.column(f.name) for f in out_schema]
    return RecordBatch(out_schema, cols)


# -- segment reductions (partial aggregation) -------------------------------
_SEG_GROUP_CAP = 256
_SUM_LIMBS = 8  # 8-bit limbs, int64 coverage


def _sum_limbs(values: np.ndarray) -> list:
    v = values.astype(np.int64)
    limbs = [((v >> (8 * k)) & np.int64(0xFF)).astype(np.int32) for k in range(_SUM_LIMBS - 1)]
    limbs.append((v >> (8 * (_SUM_LIMBS - 1))).astype(np.int32))  # signed top limb
    return limbs


def _limbs_to_int64(sums: np.ndarray) -> np.ndarray:
    """(G, 8) int32 limb sums -> (G,) int64 (wraparound-identical to numpy)."""
    with np.errstate(over="ignore"):
        total = np.zeros(sums.shape[0], np.int64)
        for k in range(_SUM_LIMBS):
            total += sums[:, k].astype(np.int64) << np.int64(8 * k)
    return total


def _f32_mm_ok(values: np.ndarray) -> bool:
    """float32 min/max runs on the kernel's order keys: exact for finite
    values, but NaN propagation and the ``-0.0``/``+0.0`` tie (numpy keeps
    whichever came later) are not order-key semantics."""
    return bool(np.isfinite(values).all()) and not ((values == 0.0) & np.signbit(values)).any()


def _mm_eligible(values: np.ndarray, kind: str):
    """Kernel-ready min/max column or None.  float32 must pass
    :func:`_f32_mm_ok`; integers must fit int32."""
    dt = values.dtype
    if dt == np.float32:
        return values if _f32_mm_ok(values) else None
    if dt.kind == "b" or (dt.kind == "i" and dt.itemsize <= 4) or (dt.kind == "u" and dt.itemsize <= 2):
        return values.astype(np.int32)
    return None


_I64_MAX = np.int64(2**63 - 1)
_I64_MIN = np.int64(-(2**63))
_U64_TOP = np.uint64(1 << 63)
_F64_LOW63 = np.int64(0x7FFFFFFFFFFFFFFF)


def _decode_i64(arr: np.ndarray, fn: str) -> np.ndarray:
    return arr  # empty-group sentinels (int64 extremes) ARE the identities


def _decode_u64(arr: np.ndarray, fn: str) -> np.ndarray:
    # inverse of the top-bit flip; the min sentinel int64-max decodes to
    # uint64-max and the max sentinel int64-min to 0 — the uint64 identities
    return arr.view(np.uint64) ^ _U64_TOP


def _decode_f64(arr: np.ndarray, fn: str) -> np.ndarray:
    # empty-group sentinels are unreachable from (non-NaN) float bits —
    # substitute the float identities before inverting the order map
    arr = arr.copy()
    if fn == "min":
        sent = arr == _I64_MAX
        inf = np.float64(np.inf)
    else:
        sent = arr == _I64_MIN
        inf = np.float64(-np.inf)
    bits = np.where(arr >= 0, arr, arr ^ _F64_LOW63)
    out = bits.view(np.float64).copy()
    out[sent] = inf
    return out


def _mm_wide_eligible(values: np.ndarray):
    """``(int64 order keys, decoder)`` for the two-word min/max path, or
    None.  The keys are an order-preserving int64 image of the column, fed
    through two ``segment_minmax_tiles`` passes (signed hi words, then
    sign-flipped lo words); the decoder maps group extremes (and the
    empty-group sentinels) back to the column dtype:

      * int64   — identity (sentinels are already the int64 identities)
      * uint32  — widens exactly into int64
      * uint64  — top-bit flip: ``u ^ 2^63`` viewed signed orders as uint64
      * float64 — sign-magnitude fold: non-negative bit patterns order as
        floats already; negative ones have all low 63 bits flipped.  NaN is
        ineligible (total order ≠ numpy's NaN propagation) and so is -0.0
        (bitwise total order would distinguish it from +0.0 where numpy's
        min/max result depends on operand order); ±Inf are fine.
    """
    dt = values.dtype
    if dt.kind == "i" and dt.itemsize == 8:
        return values, _decode_i64
    if dt.kind == "u" and dt.itemsize == 4:
        return values.astype(np.int64), _decode_i64
    if dt.kind == "u" and dt.itemsize == 8:
        return (values ^ _U64_TOP).view(np.int64), _decode_u64
    if dt == np.float64:
        if np.isnan(values).any() or ((values == 0.0) & np.signbit(values)).any():
            return None
        b = values.view(np.int64)
        return np.where(b >= 0, b, b ^ _F64_LOW63), _decode_f64
    return None


_LO_SIGN = np.uint32(0x80000000)


def _wide_words(v64: np.ndarray):
    """(hi, lo') int32 words of an int64 column whose lexicographic
    (signed hi, signed lo') order equals the int64 order: hi is the signed
    top word, lo' the sign-flipped low word."""
    hi = (v64 >> np.int64(32)).astype(np.int32)
    lo = ((v64 & np.int64(0xFFFFFFFF)).astype(np.uint32) ^ _LO_SIGN).view(np.int32)
    return hi, lo


def _wide_decode(hi: np.ndarray, lo_s: np.ndarray) -> np.ndarray:
    lo_u = (lo_s.view(np.uint32) ^ _LO_SIGN).astype(np.int64)
    return (hi.astype(np.int64) << np.int64(32)) | lo_u


@register_kernel("pallas", "segment_reduce")
def _pl_segment_reduce(bk: PallasBackend, gidx, ngroups, specs, n_rows) -> dict:
    kernel_ops = bk._ops()
    if ngroups == 0 or n_rows > kernel_ops.SUM_ROW_CAP or n_rows == 0:
        return {}
    sums: list = []  # (state name, values)
    fsums: list = []  # (state name, f64 values) — host f64 reference path
    mms: dict = {"f32": [], "i32": []}  # kind -> [(state name, fn, col)]
    wides: list = []  # (state name, fn, int64 keys, decoder) — two-word min/max
    count_names: list = []
    for name, fn, values in specs:
        if fn == "count":
            count_names.append(name)
        elif fn == "fsum":
            fsums.append((name, values))
        elif fn == "sum":
            if values is not None and values.dtype.kind in "iub":
                sums.append((name, values))
        elif fn in ("min", "max") and values is not None:
            col = _mm_eligible(values, fn)
            if col is not None:
                mms["f32" if col.dtype == np.float32 else "i32"].append((name, fn, col))
            else:
                wide = _mm_wide_eligible(values)
                if wide is not None:
                    wides.append((name, fn, wide[0], wide[1]))
    if not (sums or count_names or mms["f32"] or mms["i32"] or wides or fsums):
        return {}
    if ngroups > _SEG_GROUP_CAP:
        out = _windowed_segment_reduce(bk, kernel_ops, np.asarray(gidx, np.int64)[:n_rows], ngroups, sums, count_names, mms)
        if fsums:
            out.update(_f64_fold(bk, gidx, ngroups, fsums))
        return out
    tile = bk.tile
    n_pad = -(-n_rows // tile) * tile
    g_pad = -(-ngroups // 8) * 8
    g32 = np.zeros(n_pad, np.int32)
    g32[:n_rows] = np.asarray(gidx, np.int64)[:n_rows]
    out: dict = {}
    kernel_used = False
    if sums or count_names:
        limb_tbl = np.zeros((n_pad, max(1, _SUM_LIMBS * len(sums))), np.int32)
        for i, (_name, values) in enumerate(sums):
            for k, limb in enumerate(_sum_limbs(values)):
                limb_tbl[:n_rows, _SUM_LIMBS * i + k] = limb
        s_res, c_res = kernel_ops.segment_sum_tiles(g32, limb_tbl, n_rows, g_pad, tile=tile)
        s_res, c_res = np.asarray(s_res), np.asarray(c_res)
        for i, (name, _values) in enumerate(sums):
            out[name] = _limbs_to_int64(s_res[:ngroups, _SUM_LIMBS * i : _SUM_LIMBS * (i + 1)])
        for name in count_names:
            out[name] = c_res[:ngroups].astype(np.int64)
        kernel_used = True
    for kind, entries in mms.items():
        if not entries:
            continue
        np_dt = np.float32 if kind == "f32" else np.int32
        tbl = np.zeros((n_pad, len(entries)), np_dt)
        for j, (_name, _fn, col) in enumerate(entries):
            tbl[:n_rows, j] = col
        fns = tuple(fn for _n, fn, _c in entries)
        res = np.asarray(kernel_ops.segment_minmax_tiles(g32, tbl, n_rows, g_pad, fns, tile=tile))
        for j, (name, _fn, _c) in enumerate(entries):
            out[name] = np.ascontiguousarray(res[:ngroups, j])
        kernel_used = True
    if wides:
        # two-word compare: pass 1 reduces the signed hi words; pass 2
        # reduces the sign-flipped lo words among only the rows whose hi
        # word equals their group's extreme (others masked to the
        # identity sentinel).  Lexicographic (hi, lo') == int64 order on
        # the order-preserving keys; each column's decoder maps the
        # extremes (and the empty-group sentinels) back to the source
        # dtype — int64/uint32 directly, uint64/float64 by inverting
        # their monotone int64 image (see ``_mm_wide_eligible``).
        fns = tuple(fn for _n, fn, _c, _d in wides)
        hi_tbl = np.zeros((n_pad, len(wides)), np.int32)
        lo_cols = []
        for j, (_name, _fn, col, _dec) in enumerate(wides):
            hi, lo = _wide_words(col)
            hi_tbl[:n_rows, j] = hi
            lo_cols.append((hi, lo))
        h_res = np.asarray(kernel_ops.segment_minmax_tiles(g32, hi_tbl, n_rows, g_pad, fns, tile=tile))
        lo_tbl = np.empty((n_pad, len(wides)), np.int32)
        for j, (_name, fn, _col, _dec) in enumerate(wides):
            sent = np.int32(2**31 - 1) if fn == "min" else np.int32(-(2**31))
            lo_tbl[:, j] = sent
            hi, lo = lo_cols[j]
            at_extreme = hi == h_res[:, j][g32[:n_rows]]
            lo_tbl[:n_rows, j] = np.where(at_extreme, lo, sent)
        l_res = np.asarray(kernel_ops.segment_minmax_tiles(g32, lo_tbl, n_rows, g_pad, fns, tile=tile))
        for j, (name, fn, _col, decode) in enumerate(wides):
            keys64 = _wide_decode(h_res[:ngroups, j], np.ascontiguousarray(l_res[:ngroups, j]))
            out[name] = decode(keys64, fn)
        kernel_used = True
    if kernel_used:
        bk.kernel_calls += 1
    out.update(_f64_fold(bk, gidx, ngroups, fsums))
    return out


def _f64_fold(bk: PallasBackend, gidx, ngroups: int, fsums: list) -> dict:
    """The f64-accumulating reference path of float sums: bit-identical to
    the numpy scatter because a fresh state's accumulators start at +0.0
    and np.add.at adds this morsel's values in the same row order."""
    out = {}
    for name, values in fsums:
        acc = np.zeros(ngroups, np.float64)
        np.add.at(acc, np.asarray(gidx, np.int64), np.asarray(values, np.float64))
        out[name] = acc
    if fsums:
        bk.f64_folds += len(fsums)
    return out


def _windowed_segment_reduce(bk: PallasBackend, kernel_ops, gidx, ngroups: int, sums, count_names, mms) -> dict:
    """Counts, integer sums and int32 / float32 min/max of more than
    ``_SEG_GROUP_CAP`` groups: the rows sorted by group, through the fused
    kernel's windowed fold with no filter (wide min/max stay with numpy).
    Groups without rows here get the identities (0, sentinels)."""
    if not (sums or count_names or mms["f32"] or mms["i32"]):
        return {}
    tile = bk.tile
    n = gidx.size
    n_pad = -(-n // tile) * tile
    present, local = np.unique(gidx, return_inverse=True)
    order = np.argsort(local, kind="stable")
    g32 = np.zeros(n_pad, np.int32)
    g32[:n] = local[order]
    steps, live = _window_steps(g32[:n], n_pad, tile)
    limb = np.zeros((n_pad, max(1, _SUM_LIMBS * len(sums))), np.int32)
    for i, (_name, values) in enumerate(sums):
        for k, plane in enumerate(_sum_limbs(values[order])):
            limb[:n, _SUM_LIMBS * i + k] = plane
    tables = {}
    for kind, dt in (("f32", np.float32), ("i32", np.int32)):
        tables[kind] = np.zeros((n_pad, max(1, len(mms[kind]))), dt)
        for j, (_name, _fn, col) in enumerate(mms[kind]):
            tables[kind][:n, j] = col[order]
    fns = {kind: tuple(fn for _n, fn, _c in mms[kind]) or ("min",) for kind in tables}
    dummy = np.zeros((n_pad, 1), np.int32)
    res = kernel_ops.fused_chain_tiles(
        np.asarray([n, 0, 0, live], np.int32),
        dummy,
        g32,
        dummy,
        limb,
        tables["f32"],
        tables["i32"],
        dummy.astype(np.float32),
        dummy,
        steps,
        op="gt",
        kind="none",
        descrs_f=(),
        descrs_i=(),
        csums=(),
        fns_f=fns["f32"],
        fns_i=fns["i32"],
        with_gidx=False,
        segmented=True,
        ngroups=tile,
        tile=tile,
    )
    bk.kernel_calls += 1
    _ctab, _counts, gsum, gcnt, gmmf, gmmi, _gfirst = [np.asarray(r)[: present.size] for r in res]
    out: dict = {}
    for i, (name, _values) in enumerate(sums):
        out[name] = np.zeros(ngroups, np.int64)
        out[name][present] = _limbs_to_int64(gsum[:, _SUM_LIMBS * i : _SUM_LIMBS * (i + 1)])
    for name in count_names:
        out[name] = np.zeros(ngroups, np.int64)
        out[name][present] = gcnt
    for kind, got in (("f32", gmmf), ("i32", gmmi)):
        for j, (name, fn, _col) in enumerate(mms[kind]):
            if kind == "f32":
                ident = np.float32(np.inf if fn == "min" else -np.inf)
            else:
                ident = np.int32(2**31 - 1 if fn == "min" else -(2**31))
            out[name] = np.full(ngroups, ident, got.dtype)
            out[name][present] = got[:, j]
    return out


# ---------------------------------------------------------------------------
# whole-chain fused pipelines: one launch per morsel
# ---------------------------------------------------------------------------
# Sentinel returned by FusedChainPlan.run/.fold when THIS morsel falls
# outside the compiled envelope (validity mask appeared, row/group caps
# exceeded, non-finite min/max input); the caller falls back to the per-op
# path for that morsel only.
FUSED_INELIGIBLE = object()

# float keys the fused fold does not take: its NaN / -0.0 check per morsel
# (see ``FusedChainPlan.fold``) is written for float32
_WIDE_FLOAT_KEYS = {"float16", "float64"}


def _lit_value(v, group: str):
    """Literal eligibility for fused arithmetic — same envelope as
    ``_arith_descr`` (numpy promotion parity for the given group dtype)."""
    if isinstance(v, (bool, np.bool_)):
        return None
    if group == "float32":
        return _f32_lit(v)
    if isinstance(v, (int, np.integer)) and not isinstance(v, np.uint64):
        vi = int(v)
        if isinstance(v, np.int64) or not (-(2**31) <= vi <= 2**31 - 1):
            return None
        return vi
    return None


def _lower_pred(pred, mapping: dict, src_schema):
    """Lower a filter predicate against SOURCE column names.  Returns
    ``(op, kind, t_hi_bits, t_lo, src_name)`` or None."""
    if not (
        isinstance(pred, Expr)
        and pred.op in _CMP_OPS
        and isinstance(pred.args[0], Expr)
        and pred.args[0].op == "col"
        and isinstance(pred.args[1], Expr)
        and pred.args[1].op == "lit"
    ):
        return None
    m = mapping.get(pred.args[0].args[0])
    if m is None or m[0] != "src":
        return None
    sname = m[1]
    dtn = src_schema.field(sname).dtype.name
    if dtn not in _PRED_KINDS:
        return None
    norm = _normalize_threshold(pred.args[1].args[0], dtn, pred.op)
    if norm is None:
        return None
    kind, op, t_hi, t_lo = norm
    t_hi_bits = int(np.array([t_hi], np.float32).view(np.int32)[0]) if kind == "f32" else int(t_hi)
    return op, kind, t_hi_bits, int(t_lo), sname


def _lower_arith_named(e, mapping: dict, src_schema, group: str):
    """Lower an Expr to a descriptor tree over SOURCE column names.
    Computed-of-computed inlines the earlier tree when the group matches:
    the stored f32/i32 column value IS the in-kernel subtree value (each op
    rounds in the group dtype either way), so inlining is exact."""
    if not isinstance(e, Expr):
        return None
    if e.op == "col":
        m = mapping.get(e.args[0])
        if m is None:
            return None
        if m[0] == "src":
            if src_schema.field(m[1]).dtype.name != group:
                return None
            return ("col", m[1])
        return m[2] if m[1] == group else None
    if e.op == "lit":
        v = _lit_value(e.args[0], group)
        return None if v is None else ("lit", v)
    if e.op not in _ARITH or len(e.args) != 2:
        return None
    a = _lower_arith_named(e.args[0], mapping, src_schema, group)
    if a is None:
        return None
    b = _lower_arith_named(e.args[1], mapping, src_schema, group)
    if b is None:
        return None
    if group == "float32" and not _contraction_safe(e.op, a, b):
        return None
    return (e.op, a, b)


def _intern_tree(tree, idx: dict):
    """Replace source column names in a descriptor tree with table indices."""
    if tree[0] == "col":
        name = tree[1]
        if name not in idx:
            idx[name] = len(idx)
        return ("col", idx[name])
    if tree[0] == "lit":
        return tree
    return (tree[0], _intern_tree(tree[1], idx), _intern_tree(tree[2], idx))


def plan_fused_chain(specs: list, in_schema, agg=None, backend=None):
    """Compile a pipeline's op-spec chain into a :class:`FusedChainPlan`
    (one ``fused_chain_tiles`` launch per morsel), or None when any link
    falls outside the kernel envelope (→ the per-op path runs unchanged).

    ``specs`` is the executor's ``[(kind, args), ...]`` chain.  Eligible
    chains are any combination of at most one ``filter`` (predicate
    ``col <cmp> lit`` on a float32/int32/int64 source column), ``select``,
    and ``project`` (f32/i32 arithmetic or cast-free renames) — evaluated
    symbolically against SOURCE columns, so the kernel reads the original
    morsel regardless of where the filter sits in the chain.  With ``agg``
    (``(keys, aggs, mode, in_schema)``) the plan also folds the per-morsel
    partial aggregate in the same launch: counts, integer sums (8-bit-limb
    passthrough / 4-limb in-kernel for computed int32), f32 + narrow-int
    min/max, and float sums via compacted planes + the host's f64 fold.
    float32 keys are eligible: a morsel whose float key column holds a NaN
    or a ``-0.0`` (where the pre-filter factorization could pick another
    representative than the reference's post-filter one) runs the per-op
    path instead, counted in ``PallasBackend.key_rejects``.  float64 keys,
    wide min/max and var-width outputs are ineligible.
    """
    if backend is None or getattr(backend, "name", None) != "pallas":
        return None
    kernel_ops = backend._ops()
    mapping = {f.name: ("src", f.name) for f in in_schema}
    cur = in_schema
    filt = None
    for kind_, args in specs:
        if kind_ == "filter":
            if filt is not None:
                return None
            filt = _lower_pred(args[0], mapping, in_schema)
            if filt is None:
                return None
        elif kind_ == "select":
            cols = list(args[0])
            if any(c not in mapping for c in cols):
                return None
            mapping = {c: mapping[c] for c in cols}
            cur = cur.select(cols)
        elif kind_ == "project":
            exprs, out_schema = args
            new_map = {}
            for f in out_schema:
                e = exprs.get(f.name)
                if e is None:
                    m = mapping.get(f.name)
                    if m is None:
                        return None
                    new_map[f.name] = m
                    continue
                if isinstance(e, Expr) and e.op == "col":
                    m = mapping.get(e.args[0])
                    if m is None:
                        return None
                    src_dt = in_schema.field(m[1]).dtype.name if m[0] == "src" else m[1]
                    if src_dt != f.dtype.name:
                        return None  # dtype-coercing rename: outside the kernel
                    new_map[f.name] = m
                    continue
                if f.dtype.name not in ("float32", "int32"):
                    return None
                tree = _lower_arith_named(e, mapping, in_schema, f.dtype.name)
                if tree is None or tree[0] in ("col", "lit"):
                    return None
                new_map[f.name] = ("arith", f.dtype.name, tree)
            mapping = new_map
            cur = out_schema
        else:
            return None  # map / probe break the fusable chain
    if filt is None and agg is None:
        return None
    if not cur.fields:
        return None

    # -- assemble the kernel input/output layout --------------------------
    f_trees: dict = {}  # name-tree -> index among f32 computed columns
    i_trees: dict = {}
    pass_fields: list = []  # (src name, dtype, plane start, plane count)
    pass_pos = 0

    def _computed(m):
        _tag, group, tree = m
        trees = f_trees if group == "float32" else i_trees
        if tree not in trees:
            trees[tree] = len(trees)
        return ("f32" if group == "float32" else "i32", trees[tree])

    def _pass_ref(sname, dtype):
        nonlocal pass_pos
        for s, dt, start, k in pass_fields:
            if s == sname:
                return ("pass", start, k, dt)
        k = _plane_count(dtype.name)
        pass_fields.append((sname, dtype, pass_pos, k))
        ref = ("pass", pass_pos, k, dtype)
        pass_pos += k
        return ref

    out_decode = None
    key_srcs: list = []
    gcnt_states: list = []
    limb_srcs: list = []
    csum_states: list = []
    mmf: list = []
    mmi: list = []
    fsums: list = []
    if agg is None:
        out_decode = []
        for f in cur:
            m = mapping[f.name]
            if m[0] == "src":
                if f.dtype.is_varwidth:
                    return None
                out_decode.append((f, _pass_ref(m[1], f.dtype)))
            else:
                out_decode.append((f, _computed(m)))
    else:
        keys, aggs, mode, agg_schema = agg
        for k in keys:
            m = mapping.get(k)
            if m is None or m[0] != "src":
                return None
            if in_schema.field(m[1]).dtype.name in _WIDE_FLOAT_KEYS:
                return None
            key_srcs.append((k, m[1]))

        def _fsum_ref(m):
            if m[0] == "src":
                dt = in_schema.field(m[1]).dtype
                return None if dt.is_varwidth else _pass_ref(m[1], dt)
            return _computed(m)

        for out, spec in aggs.items():
            fn = spec["fn"]
            if fn == "count":
                if mode == "final":
                    m = mapping.get(out)
                    if m is None or m[0] != "src":
                        return None
                    limb_srcs.append((out, m[1]))
                else:
                    gcnt_states.append(out)
            elif fn == "mean":
                psrc = f"{out}__psum" if mode == "final" else spec.get("column")
                m = mapping.get(psrc)
                if m is None:
                    return None
                r = _fsum_ref(m)
                if r is None:
                    return None
                fsums.append((f"{out}__psum", r))
                if mode == "final":
                    m2 = mapping.get(f"{out}__pcnt")
                    if m2 is None or m2[0] != "src":
                        return None
                    limb_srcs.append((f"{out}__pcnt", m2[1]))
                else:
                    gcnt_states.append(f"{out}__pcnt")
            elif fn == "sum":
                src = out if mode == "final" else spec.get("column")
                m = mapping.get(src)
                if m is None:
                    return None
                if m[0] == "src":
                    dt = in_schema.field(m[1]).dtype.np_dtype
                    if dt.kind in "iub":
                        limb_srcs.append((out, m[1]))
                    elif dt.kind == "f":
                        fsums.append((out, _pass_ref(m[1], in_schema.field(m[1]).dtype)))
                    else:
                        return None
                elif m[1] == "int32":
                    csum_states.append((out, _computed(m)[1]))
                else:
                    fsums.append((out, _computed(m)))
            elif fn in ("min", "max"):
                src = out if mode == "final" else spec.get("column")
                m = mapping.get(src)
                if m is None or m[0] != "src":
                    return None
                dt = in_schema.field(m[1]).dtype.np_dtype
                if dt == np.float32:
                    mmf.append((out, fn, m[1]))
                elif dt.kind == "b" or (dt.kind == "i" and dt.itemsize <= 4) or (dt.kind == "u" and dt.itemsize <= 2):
                    mmi.append((out, fn, m[1]))
                else:
                    return None
            else:
                return None

    af_idx: dict = {}
    ai_idx: dict = {}
    descrs_f = tuple(_intern_tree(t, af_idx) for t, _j in sorted(f_trees.items(), key=lambda kv: kv[1]))
    descrs_i = tuple(_intern_tree(t, ai_idx) for t, _j in sorted(i_trees.items(), key=lambda kv: kv[1]))
    af_cols = [s for s, _ in sorted(af_idx.items(), key=lambda kv: kv[1])]
    ai_cols = [s for s, _ in sorted(ai_idx.items(), key=lambda kv: kv[1])]
    checked = {s for s, _dt, _p, _k in pass_fields} | set(af_cols) | set(ai_cols)
    checked |= {s for _st, s in limb_srcs} | {s for _st, _fn, s in mmf} | {s for _st, _fn, s in mmi}
    if filt is not None:
        checked.add(filt[4])
    return FusedChainPlan(
        backend,
        kernel_ops,
        filt=filt,
        out_schema=cur if agg is None else None,
        out_decode=out_decode,
        agg=None if agg is None else (list(agg[0]), dict(agg[1]), agg[2], agg[3]),
        key_srcs=key_srcs,
        gcnt_states=gcnt_states,
        limb_srcs=limb_srcs,
        csum_states=csum_states,
        mmf=mmf,
        mmi=mmi,
        fsums=fsums,
        pass_fields=pass_fields,
        pass_width=pass_pos,
        descrs_f=descrs_f,
        descrs_i=descrs_i,
        af_cols=af_cols,
        ai_cols=ai_cols,
        checked_cols=sorted(checked),
    )


class FusedChainPlan:
    """Runtime for a compiled device-resident pipeline (see
    :func:`plan_fused_chain`).  ``run`` streams one morsel through the
    filter/project chain; ``fold`` additionally produces the per-morsel
    partial ``GroupState`` — byte-identical to the reference per-op fold.
    ``stage`` pre-uploads a morsel's kernel inputs (double buffering: the
    H2D transfer of morsel *i+1* overlaps the compute of morsel *i*);
    staged buffers are torn down by ``clear_staged`` on pipeline exit or
    cancel.  Per-morsel envelope violations return ``FUSED_INELIGIBLE``.
    Spans split a morsel's host work: ``dacp.morsel.factorize`` (keys),
    ``.encode`` (planes and upload), ``.launch`` (dispatch), ``.sync``
    (waiting for the device's outputs), ``.fold`` (decode, float64 folds)."""

    def __init__(
        self,
        backend,
        kernel_ops,
        *,
        filt,
        out_schema,
        out_decode,
        agg,
        key_srcs,
        gcnt_states,
        limb_srcs,
        csum_states,
        mmf,
        mmi,
        fsums,
        pass_fields,
        pass_width,
        descrs_f,
        descrs_i,
        af_cols,
        ai_cols,
        checked_cols,
    ):
        self._bk = backend
        self._kernel_ops = kernel_ops
        self._tile = backend.tile
        if filt is None:
            self._op, self._kind, self._t_hi, self._t_lo, self._pred_src = "gt", "none", 0, 0, None
        else:
            self._op, self._kind, self._t_hi, self._t_lo, self._pred_src = filt
        self._out_schema = out_schema
        self._out_decode = out_decode
        if agg is None:
            self._agg_keys = self._aggs = self._mode = self._agg_schema = None
        else:
            self._agg_keys, self._aggs, self._mode, self._agg_schema = agg
        self._key_srcs = key_srcs
        self._gcnt_states = gcnt_states
        self._limb_srcs = limb_srcs
        self._csum_states = csum_states
        self._mmf = mmf
        self._mmi = mmi
        self._fsums = fsums
        self._pass_fields = pass_fields
        self._dp = max(1, pass_width)
        self._limb_base = max(1, _SUM_LIMBS * len(limb_srcs))
        self._descrs_f = descrs_f
        self._descrs_i = descrs_i
        self._nf = len(descrs_f)
        self._csums = tuple(idx for _state, idx in csum_states)
        self._fns_f = tuple(fn for _s, fn, _c in mmf) or ("min",)
        self._fns_i = tuple(fn for _s, fn, _c in mmi) or ("min",)
        self._af_cols = af_cols
        self._ai_cols = ai_cols
        self._with_gidx = bool(fsums)
        # ctab columns: [pass | computed f32 | computed i32 | flag? | gidx?]
        computed_end = self._dp + len(descrs_f) + len(descrs_i)
        self._flag_off = computed_end if descrs_f else None
        self._gidx_off = computed_end + (1 if descrs_f else 0)
        self._checked_cols = checked_cols
        self._sizer = None
        self._dev = None
        self._staged: dict = {}
        self._stage_lock = threading.Lock()
        self._stage_closed = False

    # -- executor wiring ----------------------------------------------------
    def bind(self, sizer, device=None) -> None:
        """Attach the pipeline's stat sink and (optional) jax device pin
        (see :func:`resolve_device`)."""
        self._sizer = sizer
        self._dev = device

    def _count_launch(self, out, staged: bool) -> None:
        if self._sizer is None:
            return
        self._sizer.bump("fused_launches")
        if staged:
            self._sizer.bump("transfers_overlapped")
        (dev,) = out[1].devices()  # where the launch really ran
        self._sizer.bump_device(dev.id)

    # -- per-morsel envelope ------------------------------------------------
    def _pad(self, n: int) -> int:
        return -(-n // self._tile) * self._tile

    def _morsel_ok(self, batch: RecordBatch) -> bool:
        n = batch.num_rows
        if n == 0 or n > self._kernel_ops.SUM_ROW_CAP:
            return False
        for name in self._checked_cols:
            if batch.column(name).validity is not None:
                return False
        return True

    # -- double-buffered uploads ---------------------------------------------
    def stage(self, batch: RecordBatch) -> None:
        """Begin the async H2D upload of ``batch``'s kernel inputs (jax
        device transfers are async: they overlap the previous morsel's
        compute).  run/fold pops the staged buffers by batch identity."""
        if self._stage_closed or not self._morsel_ok(batch):
            return
        import jax

        with span("dacp.morsel.encode", rows=batch.num_rows, staged=True):
            put = jax.device_put(self._encode(batch), self._dev)
        with self._stage_lock:
            if self._stage_closed:  # raced a CANCEL teardown: drop, don't leak
                return
            self._staged[id(batch)] = (batch.num_rows, put)

    def _take_staged(self, batch: RecordBatch):
        with self._stage_lock:
            entry = self._staged.pop(id(batch), None)
        if entry is None or entry[0] != batch.num_rows:
            return None
        return entry[1]

    def clear_staged(self) -> None:
        """Drop every in-flight staged buffer and refuse new ones (pipeline
        exit / CANCEL): a worker racing the teardown inside the source lock
        must not re-stage after the sweep."""
        with self._stage_lock:
            self._stage_closed = True
            self._staged.clear()

    @property
    def staged_count(self) -> int:
        with self._stage_lock:
            return len(self._staged)

    # -- host-side encode / decode -------------------------------------------
    def _encode_inline(self, batch: RecordBatch, order: np.ndarray | None = None) -> dict:
        """Encode an unstaged morsel on the worker that runs it."""
        with span("dacp.morsel.encode", rows=batch.num_rows, staged=False):
            return self._encode(batch, order)

    def _encode(self, batch: RecordBatch, order: np.ndarray | None = None) -> dict:
        """The morsel's kernel input tables; ``order`` lays the rows out in
        that order (a windowed fold's rows sorted by group id)."""
        n = batch.num_rows
        n_pad = self._pad(n)
        sch = batch.schema

        def values(name):
            v = np.asarray(batch.column(name).values)
            return v if order is None else v[order]

        if self._kind == "none":
            pred = np.zeros((n_pad, 1), np.int32)
        else:
            planes = _col_planes(values(self._pred_src), sch.field(self._pred_src).dtype.name)
            pred = np.zeros((n_pad, len(planes)), np.int32)
            for j, p in enumerate(planes):
                pred[:n, j] = p
        pass_tbl = np.zeros((n_pad, self._dp), np.int32)
        for s, dtype, start, _k in self._pass_fields:
            for j, p in enumerate(_col_planes(values(s), dtype.name)):
                pass_tbl[:n, start + j] = p
        limb = np.zeros((n_pad, self._limb_base), np.int32)
        for i, (_state, s) in enumerate(self._limb_srcs):
            for k, plane in enumerate(_sum_limbs(values(s))):
                limb[:n, _SUM_LIMBS * i + k] = plane
        mmf = np.zeros((n_pad, max(1, len(self._mmf))), np.float32)
        for j, (_state, _fn, s) in enumerate(self._mmf):
            mmf[:n, j] = values(s)
        mmi = np.zeros((n_pad, max(1, len(self._mmi))), np.int32)
        for j, (_state, _fn, s) in enumerate(self._mmi):
            mmi[:n, j] = values(s).astype(np.int32)
        af = np.zeros((n_pad, max(1, len(self._af_cols))), np.float32)
        for j, s in enumerate(self._af_cols):
            af[:n, j] = values(s)
        ai = np.zeros((n_pad, max(1, len(self._ai_cols))), np.int32)
        for j, s in enumerate(self._ai_cols):
            ai[:n, j] = values(s)
        return {"pred": pred, "pass": pass_tbl, "limb": limb, "mmf": mmf, "mmi": mmi, "af": af, "ai": ai}

    def _compact(self, ctab: np.ndarray, counts: np.ndarray) -> np.ndarray:
        t = self._tile
        parts = [ctab[i * t : i * t + int(c)] for i, c in enumerate(counts) if c]
        return np.concatenate(parts) if parts else ctab[:0]

    def _left_envelope(self, compact: np.ndarray) -> bool:
        """Whether a surviving row's float32 arithmetic left the kernels'
        exact envelope; the morsel then runs the per-op path, where the
        projection falls to the numpy reference."""
        if self._flag_off is None or not compact[:, self._flag_off].any():
            return False
        self._bk.envelope_rejects += 1
        return True

    def _decode_ref(self, compact: np.ndarray, ref):
        tag = ref[0]
        if tag == "pass":
            _t, start, k, dtype = ref
            return _planes_to_values(compact[:, start : start + k], dtype)
        off = self._dp + ref[1] if tag == "f32" else self._dp + self._nf + ref[1]
        col = np.ascontiguousarray(compact[:, off])
        return col.view(np.float32) if tag == "f32" else col

    def _launch(self, arrs: dict, gidx: np.ndarray, n: int, segmented: bool, ngroups: int, steps=None, live: int = 0):
        scalars = np.asarray([n, self._t_hi, self._t_lo, live], np.int32)
        if self._dev is not None:  # unstaged morsels too run on the pinned chip
            import jax

            scalars, arrs, gidx, steps = jax.device_put((scalars, arrs, gidx, steps), self._dev)
        return self._kernel_ops.fused_chain_tiles(
            scalars,
            arrs["pred"],
            gidx,
            arrs["pass"],
            arrs["limb"],
            arrs["mmf"],
            arrs["mmi"],
            arrs["af"],
            arrs["ai"],
            steps,
            op=self._op,
            kind=self._kind,
            descrs_f=self._descrs_f,
            descrs_i=self._descrs_i,
            csums=self._csums,
            fns_f=self._fns_f,
            fns_i=self._fns_i,
            with_gidx=self._with_gidx,
            segmented=segmented,
            ngroups=ngroups,
            tile=self._tile,
        )

    # -- streaming chain ------------------------------------------------------
    def run(self, batch: RecordBatch):
        """filter → project → select in one launch.  Returns the output
        morsel, None (fully filtered), or ``FUSED_INELIGIBLE``."""
        staged = self._take_staged(batch)
        if not self._morsel_ok(batch):
            return FUSED_INELIGIBLE
        n = batch.num_rows
        arrs = staged if staged is not None else self._encode_inline(batch)
        gidx = np.zeros(self._pad(n), np.int32)
        with span("dacp.morsel.launch", rows=n):
            out = self._launch(arrs, gidx, n, segmented=False, ngroups=8)
        with span("dacp.morsel.sync"):
            ctab, counts = np.asarray(out[0]), np.asarray(out[1])
        with span("dacp.morsel.fold"):
            self._count_launch(out, staged is not None)
            if int(counts.sum()) == 0:
                return None
            compact = self._compact(ctab, counts)
            if self._left_envelope(compact):
                return FUSED_INELIGIBLE
            cols = []
            for f, ref in self._out_decode:
                vals = self._decode_ref(compact, ref)
                cols.append(Column(f.dtype, values=vals) if ref[0] == "pass" else Column.from_values(f.dtype, vals))
            return RecordBatch(self._out_schema, cols)

    # -- aggregate fold --------------------------------------------------------
    def fold(self, batch: RecordBatch):
        """Per-morsel partial aggregate in one launch.  Returns a
        ``GroupState`` byte-identical to the reference per-op fold over the
        filtered morsel, None (no surviving rows), or ``FUSED_INELIGIBLE``.
        Group ids come from factorizing the PRE-filter morsel; the kernel's
        per-group minimum surviving row index reorders the survivors into
        first-seen-filtered order, matching the reference interning.  Up to
        ``_SEG_GROUP_CAP`` groups the one-hot spans every group; above it
        the fold is windowed (:func:`_window_steps`), its rows laid out in
        group-id order."""
        staged = self._take_staged(batch)
        if not self._morsel_ok(batch):
            return FUSED_INELIGIBLE
        for _state, _fn, s in self._mmf:
            if not _f32_mm_ok(batch.column(s).values):
                return FUSED_INELIGIBLE
        from repro.core.operators import GroupState, _odd_float
        from repro.core.schema import Field, Schema

        keys = [k for k, _s in self._key_srcs]
        n = batch.num_rows
        with span("dacp.morsel.factorize", rows=n):
            if any(_odd_float(batch.column(s).values) for _k, s in self._key_srcs):
                self._bk.count("key_rejects")
                return FUSED_INELIGIBLE
            if all(k == s for k, s in self._key_srcs):
                kb = batch
            else:
                fields = [Field(k, batch.schema.field(s).dtype) for k, s in self._key_srcs]
                kb = RecordBatch(Schema(fields), [batch.column(s) for _k, s in self._key_srcs])
            tmp = GroupState(keys, {}, self._mode, kb.schema, vectorized=True)
            gidx_full = tmp._factorize(kb)
        ng = tmp.ngroups
        if ng == 0:
            return FUSED_INELIGIBLE
        order = steps = None
        live = 0
        g32 = np.zeros(self._pad(n), np.int32)
        if ng <= _SEG_GROUP_CAP:
            g_pad = max(8, -(-ng // 8) * 8)
            g32[:n] = gidx_full
        else:
            g_pad = self._tile
            if (np.diff(gidx_full) < 0).any():
                order = np.argsort(gidx_full, kind="stable")
                g32[:n] = gidx_full[order]
            else:
                g32[:n] = gidx_full
            steps, live = _window_steps(g32[:n], self._pad(n), self._tile)
        if staged is None or order is not None:
            arrs = self._encode_inline(batch, order)
        else:
            arrs = staged
        with span("dacp.morsel.launch", rows=n):
            out = self._launch(arrs, g32, n, segmented=True, ngroups=g_pad, steps=steps, live=live)
        with span("dacp.morsel.sync"):
            ctab, counts, gsum, gcnt, gmmf, gmmi, gfirst = [np.asarray(o) for o in out]
        with span("dacp.morsel.fold"):
            self._count_launch(out, staged is not None and order is None)
            compact = self._compact(ctab, counts) if self._fsums or self._flag_off is not None else None
            if compact is not None and self._left_envelope(compact):
                return FUSED_INELIGIBLE
            gcnt_v = gcnt[:ng]
            alive = np.flatnonzero(gcnt_v > 0)
            if alive.size == 0:
                return None
            first = gfirst[:ng][alive]
            if order is not None:
                first = order[first]  # rows of the sorted layout back to the morsel's rows
            perm = alive[np.argsort(first, kind="stable")]
            st = GroupState(
                self._agg_keys, self._aggs, self._mode, self._agg_schema, vectorized=True, backend=self._bk
            )
            st.set_keys(tmp, perm)
            st.map_s = tmp.map_s
            acc: dict = {}
            for state in self._gcnt_states:
                acc[state] = gcnt_v[perm].astype(np.int64)
            for i, (state, _s) in enumerate(self._limb_srcs):
                acc[state] = _limbs_to_int64(gsum[perm, _SUM_LIMBS * i : _SUM_LIMBS * (i + 1)])
            base = self._limb_base
            for j, (state, _idx) in enumerate(self._csum_states):
                s4 = gsum[perm, base + 4 * j : base + 4 * (j + 1)].astype(np.int64)
                acc[state] = s4[:, 0] + (s4[:, 1] << 8) + (s4[:, 2] << 16) + (s4[:, 3] << 24)
            for j, (state, _fn, _s) in enumerate(self._mmf):
                acc[state] = gmmf[perm, j].astype(np.float64)
            for j, (state, _fn, _s) in enumerate(self._mmi):
                acc[state] = gmmi[perm, j].astype(np.int64)
            if self._fsums:
                # row order within each group, from +0.0: np.add.at's sums, bit for bit
                g_sel = compact[:, self._gidx_off]
                for state, ref in self._fsums:
                    vals = np.asarray(self._decode_ref(compact, ref), np.float64)
                    acc[state] = np.bincount(g_sel, weights=vals, minlength=ng)[perm]
            for name, (_init, dt) in st._state_specs().items():
                st.acc[name] = np.ascontiguousarray(np.asarray(acc[name], dt))
            return st


def _window_steps(gidx: np.ndarray, n_pad: int, tile: int) -> tuple:
    """The step table of a windowed fold over rows sorted by group id:
    ``(steps, live)``, ``steps`` the row tile then the group window (of
    ``tile`` groups) of each of ``2 * n_pad / tile`` steps, ordered by
    window and then tile, ``live`` how many are real (the rest repeat the
    last).  Every group id below the largest has a row, so the rows of one
    tile span at most ``tile`` consecutive ids: two windows at most, and a
    tile in two windows is folded once into each, at consecutive steps."""
    n = gidx.size
    nt = n_pad // tile
    lo = gidx[0:n:tile] // tile
    hi = gidx[np.minimum(np.arange(1, nt + 1) * tile, n) - 1] // tile
    two = np.flatnonzero(hi > lo)
    t = np.concatenate([np.arange(nt), two])
    w = np.concatenate([lo, hi[two]])
    o = np.lexsort((t, w))
    live = o.size
    steps = np.empty(4 * nt, np.int32)
    steps[:live] = t[o]
    steps[live : 2 * nt] = t[o[-1]]
    steps[2 * nt : 2 * nt + live] = w[o]
    steps[2 * nt + live :] = w[o[-1]]
    return steps, live


def resolve_device(index: int):
    """The jax device a ``DACP_DEVICES`` / ``ExecutorConfig.devices`` index
    names; an index past this host's devices raises."""
    import jax

    devs = jax.devices()
    if not 0 <= index < len(devs):
        raise ValueError(f"device index {index} out of range: jax has {len(devs)} device(s)")
    return devs[index]


# ---------------------------------------------------------------------------
# backend selection
# ---------------------------------------------------------------------------
BACKENDS = {"numpy": NumpyBackend, "pallas": PallasBackend}
_instances: dict = {}
_instances_lock = threading.Lock()


def _jax_tpu() -> bool:
    """Whether jax runs on a TPU.  Only a missing jax reads as "no": a
    chip that fails to initialise (e.g. held by another process) raises."""
    if importlib.util.find_spec("jax") is None:
        return False
    import jax

    return jax.default_backend() == "tpu"


def available_backends() -> list:
    return ["numpy", "pallas"] if importlib.util.find_spec("jax") is not None else ["numpy"]


def get_backend(name: str | None = None) -> ComputeBackend:
    """Resolve a backend by name.  ``auto`` (default, or env
    ``DACP_BACKEND``) picks pallas only on a real TPU; ``pallas`` without
    jax installed raises."""
    name = name or env_str("DACP_BACKEND")
    if name == "auto":
        name = "pallas" if _jax_tpu() else "numpy"
    if name not in BACKENDS:
        raise KeyError(f"unknown compute backend {name!r}; known: {sorted(BACKENDS)}")
    if name not in available_backends():
        raise RuntimeError(f"compute backend {name!r} needs jax, which is not installed")
    with _instances_lock:
        inst = _instances.get(name)
        if inst is None:
            inst = _instances[name] = BACKENDS[name]()
        return inst
