"""Fused columnar Filter+Select — the paper's §IV-B operator library made
TPU-native (DESIGN.md §3.2).

The DACP read-amplification argument restated for the on-chip hierarchy:
HBM→VMEM is "the network", and this kernel guarantees the bytes written
back are ``selected_rows × selected_columns`` only.  Per row-tile:

  1. DMA one (TILE, D) block of the columnar table into VMEM,
  2. evaluate the predicate on the predicate column (VPU),
  3. **compaction as a matmul**: ``out = Pᵀ @ block`` where
     P[i, j] = (prefix(mask)_i - 1 == j) ∧ mask_i (MXU) — selected rows land
     at the front of the tile, a per-tile count goes to a second output.

Scatter-free compaction through the systolic array is the hardware
adaptation: TPUs have no efficient in-kernel scatter, but a (TILE, TILE)
one-hot matmul at TILE=256 is ~2% of the per-row cost and keeps the whole
operator on the MXU.  A cheap host epilogue in ``repro.core.backend``
concatenates tile fronts into the final compacted table.

``filter_select_planes`` is the production form used by the compute
backend.  Columns arrive as **int32 bit-planes** (one plane per 4 bytes of
column width; ``repro.core.backend`` encodes/decodes).  The lowering keeps
every step exact on the MXU and VPU of a TPU:

  * **byte-plane compaction** — the MXU has no int32×int32 product, so each
    int32 plane is split into four byte planes (values 0..255, exact in
    bf16) and compacted by a bf16 one-hot matmul accumulating in f32.  Each
    output element sums at most one nonzero product, so the bytes (and the
    recombined bit patterns) are verbatim: bit-exact for every fixed-width
    dtype including ``-0.0``, NaN payloads, Inf, and full-range int64.
  * **matmul prefix sum** — a row's compacted position is its inclusive
    prefix count, computed as a 0/1 upper-triangular bf16 matmul (counts ≤
    TILE are exact in f32); Mosaic has no ``cumsum``.
  * **row-major predicate** — the predicate planes arrive transposed, one
    (1, TILE) row per plane, so the mask broadcasts along sublanes into the
    one-hot matrix without an in-kernel transpose.
  * **integer float compare** — float32 predicates compare an
    order-preserving int32 image of the bit patterns (``-0.0 == +0.0``,
    NaN unordered), so subnormals compare exactly on hardware that flushes
    them in float arithmetic.
  * **SMEM scalars** — ``[n_rows, t_hi, t_lo]`` and the per-tile survivor
    counts live in scalar memory.

int32 predicates compare directly, int64 as a two-word hi/lo compare
(sign-flipped unsigned low word) — no 64-bit lanes needed.  All six
comparisons (``lt le gt ge eq ne``) are supported, and a row validity bound
masks the ragged tail tile, so ``eq``-style predicates never match padding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["filter_select_planes"]

_INT32_SIGN = -(2**31)  # xor flips the sign bit: signed cmp == unsigned cmp
_ABS = 0x7FFFFFFF
_INF_BITS = 0x7F800000

_CMP = {
    "lt": lambda a, b: a < b,
    "le": lambda a, b: a <= b,
    "gt": lambda a, b: a > b,
    "ge": lambda a, b: a >= b,
    "eq": lambda a, b: a == b,
    "ne": lambda a, b: a != b,
}


def f32_order_key(bits):
    """Order-preserving int32 image of float32 bit patterns: non-negative
    patterns already order as their floats, negative ones have their low 31
    bits flipped.  Total order (``-0.0 < +0.0``), self-inverse."""
    return bits ^ ((bits >> 31) & _ABS)


def _cmp64(op: str, hi, lo, t_hi, t_lo):
    """Two-word int64 comparison on int32 planes.  ``lo``/``t_lo`` carry the
    low word with the sign bit pre-flipped, so signed int32 comparison
    implements the unsigned low-word comparison."""
    if op == "eq":
        return (hi == t_hi) & (lo == t_lo)
    if op == "ne":
        return (hi != t_hi) | (lo != t_lo)
    lt = (hi < t_hi) | ((hi == t_hi) & (lo < t_lo))
    if op == "lt":
        return lt
    if op == "ge":
        return ~lt
    gt = (hi > t_hi) | ((hi == t_hi) & (lo > t_lo))
    return gt if op == "gt" else ~gt  # "le"


def _f32_cmp(op: str, x, t):
    """IEEE float32 comparison of bit patterns, in integer arithmetic."""
    unordered = ((x & _ABS) > _INF_BITS) | ((t & _ABS) > _INF_BITS)  # NaN
    zero = jnp.int32(_INT32_SIGN)  # -0.0 compares equal to +0.0
    kx = f32_order_key(jnp.where(x == zero, 0, x))
    kt = f32_order_key(jnp.where(t == zero, 0, t))
    hit = _CMP[op](kx, kt)
    return (hit | unordered) if op == "ne" else (hit & ~unordered)


def pred_mask(pred, t_hi, t_lo, *, op: str, kind: str):
    """(1, tile) bool mask from the predicate planes laid out as rows
    (P, tile) int32.  ``t_hi``/``t_lo`` are traced int32 scalars carrying
    the threshold's bit pattern (so changing the literal does not retrace
    the kernel)."""
    shape = (1, pred.shape[1])
    t_hi = jnp.full(shape, t_hi, jnp.int32)
    if kind == "f32":
        return _f32_cmp(op, pred[0:1, :], t_hi)
    if kind == "i32":
        return _CMP[op](pred[0:1, :], t_hi)
    # i64: plane 0 = high word (signed), plane 1 = low word (raw bits)
    lo = pred[1:2, :] ^ jnp.int32(_INT32_SIGN)
    return _cmp64(op, pred[0:1, :], lo, t_hi, jnp.full(shape, t_lo, jnp.int32))


def row_ids(tile: int):
    """(1, tile) global row index of each lane of this grid step."""
    return pl.program_id(0) * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)


def as_bf16(x):
    """Exact bf16 image of small integers (|x| ≤ 256) or a bool mask."""
    if x.dtype == jnp.bool_:
        x = jnp.where(x, 1.0, 0.0)
    return x.astype(jnp.float32).astype(jnp.bfloat16)


def onehot_dot(onehot, vals):
    """``onehot (M, K) @ vals (K, N)`` for a 0/1 matrix and int32 ``vals``
    in [-256, 256]: bf16 operands, f32 accumulation, exact while every
    output's sum stays below 2^24 (K ≤ 256 rows of bytes: ≤ 65,280)."""
    return jnp.dot(as_bf16(onehot), as_bf16(vals), preferred_element_type=jnp.float32).astype(jnp.int32)


def compact(mask, table):
    """Move ``table``'s (tile, D) int32 rows where the (1, tile) ``mask``
    holds to the front of the tile, bit-exact.  Returns the compacted
    (tile, D) block (rows past the survivor count are zero)."""
    tile = table.shape[0]
    k = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    # inclusive prefix count as a 0/1 upper-triangular matmul (no cumsum)
    m8 = jnp.broadcast_to(jnp.where(mask, 1, 0), (8, tile))
    pos = onehot_dot(m8, jnp.where(k <= i, 1, 0))[0:1, :] - 1
    pt = (pos == k) & mask  # pt[j, i]: row i lands at position j
    out = None
    for b in range(4):
        byte = onehot_dot(pt, (table >> (8 * b)) & 0xFF) << (8 * b)
        out = byte if out is None else out | byte
    return out


def survivors(mask):
    """Number of set lanes of a (1, tile) mask (an int32 scalar)."""
    return jnp.sum(jnp.where(mask, 1, 0))


def _planes_kernel(sc_ref, pred_ref, tbl_ref, out_ref, cnt_ref, *, op, kind, tile):
    mask = pred_mask(pred_ref[...], sc_ref[1], sc_ref[2], op=op, kind=kind)
    mask = mask & (row_ids(tile) < sc_ref[0])  # padding never matches (eq-safe)
    out_ref[...] = compact(mask, tbl_ref[...])
    cnt_ref[pl.program_id(0)] = survivors(mask)


def filter_select_planes(
    pred_planes,
    table,
    scalars,
    op: str = "gt",
    kind: str = "f32",
    tile: int = 256,
    interpret: bool = False,
):
    """pred_planes: (N, P) int32; table: (N, D) int32 bit-planes of the
    output columns; scalars: (3,) int32 ``[n_rows, t_hi bits, t_lo bits]``
    (rows >= n_rows are padding; thresholds travel as traced data, so a new
    literal reuses the compiled kernel).  Returns (per-tile-compacted
    (N, D) int32 planes, counts (N//tile,) int32)."""
    n, d = table.shape
    assert n % tile == 0, (n, tile)
    p = pred_planes.shape[1]
    kernel = functools.partial(_planes_kernel, op=op, kind=kind, tile=tile)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(n // tile,),
        in_specs=[
            smem,
            pl.BlockSpec((p, tile), lambda i: (0, i)),
            pl.BlockSpec((tile, d), lambda i: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((tile, d), lambda i: (i, 0)), smem],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), jnp.int32),
            jax.ShapeDtypeStruct((n // tile,), jnp.int32),
        ],
        interpret=interpret,
    )(jnp.asarray(scalars, jnp.int32), jnp.asarray(pred_planes).T, table)
