"""Compute backends for the morsel executor (paper §III-D).

A backend names where a pipeline's morsels run and carries the counters
that say how they ran.  Two ship in-tree:

  * ``numpy``  — the reference: every morsel runs the numpy operators of
    ``repro.core.operators``.
  * ``pallas`` — one device path.  :func:`plan_fused_chain` compiles a
    pipeline's filter / select / project chain, and an aggregate's
    per-morsel fold, into a :class:`FusedChainPlan` that runs each morsel
    as ONE ``fused_chain_tiles`` launch (``repro.kernels.fused_pipeline``).
    Columns cross into the kernel as **int32 bit-planes** (one plane per 4
    bytes of width), so compaction moves bit patterns exactly: ``-0.0``,
    NaN payloads, Inf and full-range int64 come back verbatim.

The fused envelope, decided per chain at plan time:

    filter     at most one ``col <cmp> lit``, ``<cmp>`` in {<, <=, >, >=,
               ==, !=}, on a float32 / int32 / int64 column, with a literal
               whose numpy promotion the kernel reproduces
    project    float32 / int32 ``+ - *`` over columns and python-scalar
               literals (float32 ``mul`` under ``add``/``sub`` only by a
               power of two), and dtype-preserving renames
    select     any fixed-width columns (var-width outputs decline)
    aggregate  keys of any dtype but float16 / float64; counts, integer
               sums (8-bit limbs, wraparound-identical), float sums and
               mean partial sums (float64 on the host, in row order),
               float32 and narrow-integer min/max.  Up to 256 groups a
               morsel the one-hot fold spans every group; above that the
               rows are sorted by group and folded window by window (256
               groups a window)

and per morsel at run time: a validity mask on a column the kernel reads,
float32 arithmetic that leaves the exact envelope (inf, NaN, subnormal,
flushed underflow: ``envelope_rejects``), a float32 min/max input holding
a NaN or ``-0.0``, or a float32 key holding one (``key_rejects``) returns
:data:`FUSED_INELIGIBLE`.  A chain the plan declines, and a morsel it
gives back, run the numpy operators — the same answer, byte for byte.  A
kernel that fails to compile or run fails the request: no error turns into
a silent numpy answer, and resolving ``pallas`` without jax installed
raises.

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

import numpy as np

from repro.core.batch import CoalescedBatch, Column, RecordBatch
from repro.core.env import env_str
from repro.core.expr import Expr
from repro.core.trace import span

__all__ = [
    "ComputeBackend",
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


class ComputeBackend:
    """A backend's name and counters.  Instances are thread-safe; their only
    state is counters.

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
        # morsels whose float32 arithmetic left the kernel's exact envelope
        # (inf, NaN, subnormal, flushed underflow) and were recomputed by
        # the numpy operators
        self.envelope_rejects = 0
        # grouped morsels whose float key held a NaN or -0.0, folded on the
        # numpy path instead of the fused one
        self.key_rejects = 0
        # never incremented; kept because the chip benchmark's harness
        # reads them
        self.kernel_calls = 0
        self.f64_folds = 0

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


# -- filter predicates -------------------------------------------------------
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


# -- project arithmetic ------------------------------------------------------
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


# -- aggregate folds ---------------------------------------------------------
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


# ---------------------------------------------------------------------------
# whole-chain fused pipelines: one launch per morsel
# ---------------------------------------------------------------------------
# Sentinel returned by FusedChainPlan.run/.fold when THIS morsel falls
# outside the compiled envelope (validity mask appeared, row cap exceeded,
# non-finite min/max input); the caller runs the numpy operators on that
# morsel only.
FUSED_INELIGIBLE = object()

# float keys the fused fold does not take: its NaN / -0.0 check per morsel
# (see ``FusedChainPlan.fold``) is written for float32
_WIDE_FLOAT_KEYS = {"float16", "float64"}


def _lit_value(v, group: str):
    """A fused arithmetic literal for the given group dtype, or None where
    the kernel could not keep numpy's promotion."""
    if isinstance(v, (bool, np.bool_)):
        return None
    if group == "float32":
        return _f32_lit(v)
    if isinstance(v, (int, np.integer)) and not isinstance(v, np.uint64):
        vi = int(v)
        if isinstance(v, np.int64) or not (-(2**31) <= vi <= 2**31 - 1):
            return None  # would promote to int64 (or raise) in numpy
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
    falls outside the kernel envelope (→ the numpy operators run it).

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
    representative than the reference's post-filter one) runs the numpy
    operators instead, counted in ``PallasBackend.key_rejects``.  float64
    keys, wide min/max and var-width outputs are ineligible.
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
    partial ``GroupState`` — byte-identical to the numpy fold.
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
    def _rows(self, batch: RecordBatch) -> int:
        """The morsel's kernel row count: its rows rounded up to a tile,
        or, for a :class:`CoalescedBatch`, its ``kernel_rows`` (at most
        ``SUM_ROW_CAP``), one shape for every coalesced morsel.  The kernel
        masks the rows at or past ``n``."""
        n = batch.num_rows
        if isinstance(batch, CoalescedBatch):
            n = max(n, min(batch.kernel_rows, self._kernel_ops.SUM_ROW_CAP))
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
        n_pad = self._rows(batch)
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
        exact envelope; the morsel then runs the numpy operators."""
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
        gidx = np.zeros(self._rows(batch), np.int32)
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
        ``GroupState`` byte-identical to the numpy fold over the filtered
        morsel, None (no surviving rows), or ``FUSED_INELIGIBLE``.
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
        n_pad = self._rows(batch)
        g32 = np.zeros(n_pad, np.int32)
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
            steps, live = _window_steps(g32[:n], n_pad, self._tile)
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
            st = GroupState(self._agg_keys, self._aggs, self._mode, self._agg_schema, vectorized=True)
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
    last).  Only the tiles that hold rows are named.  Every group id below
    the largest has a row, so the rows of one tile span at most ``tile``
    consecutive ids: two windows at most, and a tile in two windows is
    folded once into each, at consecutive steps."""
    n = gidx.size
    nt = -(-n // tile)
    half = 2 * (n_pad // tile)
    lo = gidx[0:n:tile] // tile
    hi = gidx[np.minimum(np.arange(1, nt + 1) * tile, n) - 1] // tile
    two = np.flatnonzero(hi > lo)
    t = np.concatenate([np.arange(nt), two])
    w = np.concatenate([lo, hi[two]])
    o = np.lexsort((t, w))
    live = o.size
    steps = np.empty(2 * half, np.int32)
    steps[:live] = t[o]
    steps[live:half] = t[o[-1]]
    steps[half : half + live] = w[o]
    steps[half + live :] = w[o[-1]]
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
