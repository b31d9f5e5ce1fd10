"""Whole-chain fused pipeline kernel: filter → project → segment-reduce in
ONE Pallas launch per morsel (DESIGN.md §3.2, the device-resident executor),
the compute backend's only device kernel.

The morsel's bit-plane columns stay device-resident across all three
stages — per row-tile, in a single grid step:

  1. predicate mask on the filter column's int32 plane(s) (f32 bitcast /
     i32 / two-word i64 compare, :func:`pred_mask`),
  2. projection arithmetic on the *pre-filter* rows (element-wise, so the
     surviving rows carry exactly the values the reference computes after
     filtering), descriptor trees evaluated by :func:`eval_descr` /
     :func:`eval_checked`,
  3. one-hot compaction matmul of the passthrough planes + the bitcast
     computed columns (+ the group-id column when a float sum needs the
     host's f64 fold), :func:`compact`,
  4. masked one-hot **segment fold** for the aggregate tail: 8-bit-limb
     sums (passthrough columns arrive as host-built limb planes; computed
     int32 columns are limb-decomposed in-kernel), group counts, f32/i32
     masked min/max, and each group's minimum surviving row index — the
     host reorders groups into first-seen-filtered order from it, which
     makes the fused partial ``GroupState`` byte-identical to the
     reference fold over the filtered batch.

Everything stays int32/float32 in-kernel and every step is exact on the
MXU and VPU of a TPU:

  * **byte-plane compaction** — the MXU has no int32×int32 product, so each
    int32 plane is split into four byte planes (values 0..255, exact in
    bf16) and compacted by a bf16 one-hot matmul accumulating in f32.  Each
    output element sums at most one nonzero product, so the bytes (and the
    recombined bit patterns) are verbatim: bit-exact for every fixed-width
    dtype including ``-0.0``, NaN payloads, Inf, and full-range int64.
  * **matmul prefix sum** — a row's compacted position is its inclusive
    prefix count, computed as a 0/1 upper-triangular bf16 matmul (counts ≤
    TILE are exact in f32); Mosaic has no ``cumsum``.
  * **integer float compare** — float32 predicates compare an
    order-preserving int32 image of the bit patterns (``-0.0 == +0.0``,
    NaN unordered), so subnormals compare exactly on hardware that flushes
    them in float arithmetic; int64 compares as two words (sign-flipped
    unsigned low word), with no 64-bit lanes.
  * **limb sums** — a tile's one-hot matmul over 8-bit limbs is at most
    256 × 255 = 65,280 < 2^24, exact in f32; over a morsel of
    ``SUM_ROW_CAP`` rows each limb sum stays below 2^26, exact in the int32
    accumulator, and the host recombines ``Σ limb_sum_k << 8k`` into int64
    — wraparound-identical to numpy.
  * **min/max on order keys** — float32 min/max reduce the order-preserving
    int32 image, so the extremes are bit patterns of inputs (the host keeps
    NaN and ``-0.0`` off this path); empty groups hold the sentinels.
  * **float32 exact envelope** — hardware may flush subnormals to zero,
    encode NaN results differently, or contract a multiply into an add, so
    every float32 operand, intermediate and result is checked in-kernel: a
    row is flagged when any is ±inf, NaN, subnormal, or a zero that only a
    flushed underflow explains.  The host recomputes a morsel with a
    flagged survivor with the numpy reference.

Scalars and per-tile counts live in SMEM, and the predicate planes, group
ids and min/max columns are laid out as rows so they broadcast against the
group axis without an in-kernel transpose.  Float sums are NOT folded
in-kernel (f64 accumulation order matters); their source planes ride
through the compaction output and the host folds them in row order —
bit-identical to the reference's ``np.add.at``.

Static plan parameters (the jit signature):

    op, kind       predicate comparison + column kind ("none" = no filter)
    descrs_f/_i    descriptor trees over the f32 / i32 arithmetic tables:
                   ("col", j), ("lit", v) or (op, a, b), op in add/sub/mul
    csums          indices into ``descrs_i`` whose outputs are summed
                   (4-limb in-kernel decomposition)
    fns_f/_i       "min"/"max" per column of the f32 / i32 min/max tables
    with_gidx      append the group-id column to the compaction table
    segmented      run the segment fold (False = streaming chain: the group
                   outputs are zero-filled dummies)
    ngroups        padded group count (multiple of 8); with a step table,
                   the height of one group window

A step table (the ``steps`` argument, traced data) makes the fold
windowed, for morsels with more groups than a one-hot over all of them
should span: see :func:`fused_chain_tiles`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_chain_tiles", "SUM_ROW_CAP"]

# 8-bit limbs: |limb| <= 255 (top limb signed, in [-128, 127]), so a sum over
# SUM_ROW_CAP rows is < 2^26 — comfortably exact in the int32 accumulator.
SUM_ROW_CAP = 262144

I32_MAX = 2**31 - 1
I32_MIN = -(2**31)
_INT32_SIGN = -(2**31)  # xor flips the sign bit: signed cmp == unsigned cmp
_ABS = 0x7FFFFFFF
_EXP = 0x7F800000  # also the bits of +inf
_MANT = 0x007FFFFF

_CMP = {
    "lt": lambda a, b: a < b,
    "le": lambda a, b: a <= b,
    "gt": lambda a, b: a > b,
    "ge": lambda a, b: a >= b,
    "eq": lambda a, b: a == b,
    "ne": lambda a, b: a != b,
}
_ARITH = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b,
}


# -- predicate ---------------------------------------------------------------
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
    unordered = ((x & _ABS) > _EXP) | ((t & _ABS) > _EXP)  # NaN
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


# -- projection arithmetic ---------------------------------------------------
def eval_descr(d, block):
    """Value of a descriptor tree over a (tile, D) block, as (tile, 1)."""
    kind = d[0]
    if kind == "col":
        return block[:, d[1] : d[1] + 1]  # columns stay 2-D
    if kind == "lit":
        return d[1]  # python scalar: weak promotion, same as the numpy ref
    return _ARITH[kind](eval_descr(d[1], block), eval_descr(d[2], block))


def _bits(v):
    return jax.lax.bitcast_convert_type(v, jnp.int32)


def _off_envelope(v):
    """±inf, NaN or subnormal — read from the bit pattern, since hardware
    that treats subnormals as zero would compare them equal to 0."""
    b = _bits(v)
    e = b & _EXP
    return (e == _EXP) | ((e == 0) & ((b & _MANT) != 0))


def _nonzero(v):
    return (v != 0.0) if isinstance(v, float) else (_bits(v) & _ABS) != 0


def eval_checked(d, block):
    """``(value, flag)`` of a float32 descriptor tree over a (tile, D)
    block: ``flag`` (tile, 1) bool marks rows that left the exact envelope
    (see the module docstring).  Literals are kept finite and normal (or
    zero) at plan time, so only columns and results are checked."""
    kind = d[0]
    if kind == "col":
        v = block[:, d[1] : d[1] + 1]
        return v, _off_envelope(v)
    if kind == "lit":
        return d[1], None
    a, fa = eval_checked(d[1], block)
    b, fb = eval_checked(d[2], block)
    r = _ARITH[kind](a, b)
    if kind == "add":
        flushed = a != -b
    elif kind == "sub":
        flushed = a != b
    else:  # mul
        flushed = _nonzero(a) & _nonzero(b)
    flag = _off_envelope(r) | (~_nonzero(r) & flushed)
    for f in (fa, fb):
        if f is not None:
            flag = flag | f
    return r, flag


# -- compaction --------------------------------------------------------------
def _as_bf16(x):
    """Exact bf16 image of small integers (|x| ≤ 256) or a bool mask."""
    if x.dtype == jnp.bool_:
        x = jnp.where(x, 1.0, 0.0)
    return x.astype(jnp.float32).astype(jnp.bfloat16)


def onehot_dot(onehot, vals):
    """``onehot (M, K) @ vals (K, N)`` for a 0/1 matrix and int32 ``vals``
    in [-256, 256]: bf16 operands, f32 accumulation, exact while every
    output's sum stays below 2^24 (K ≤ 256 rows of bytes: ≤ 65,280)."""
    return jnp.dot(_as_bf16(onehot), _as_bf16(vals), preferred_element_type=jnp.float32).astype(jnp.int32)


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


# -- segment fold ------------------------------------------------------------
def onehot(gidx_row, mask_row, ngroups: int):
    """(G, tile) bool: row i belongs to group g and survives ``mask_row``."""
    giota = jax.lax.broadcasted_iota(jnp.int32, (ngroups, gidx_row.shape[1]), 0)
    return (gidx_row == giota) & mask_row


def mm_sentinels(fns) -> tuple:
    """Empty-group identities of the int32 min/max reduction."""
    return tuple(I32_MAX if fn == "min" else I32_MIN for fn in fns)


def mm_fold(cur, rows, oh, fns, sentinels):
    """Masked per-group min/max of the value rows ``rows`` (M, tile)
    folded into the accumulator ``cur`` (G, M)."""
    cols = []
    for j, fn in enumerate(fns):
        masked = jnp.where(oh, rows[j : j + 1, :], sentinels[j])  # (G, tile)
        if fn == "min":
            cols.append(jnp.minimum(cur[:, j : j + 1], masked.min(axis=1, keepdims=True)))
        else:
            cols.append(jnp.maximum(cur[:, j : j + 1], masked.max(axis=1, keepdims=True)))
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)


def mm_init(ngroups: int, sentinels):
    cols = [jnp.full((ngroups, 1), s, jnp.int32) for s in sentinels]
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)


def f32_from_keys(keys, fns):
    """Decode order keys back to float32; empty groups get ±inf."""
    empty = jnp.asarray(mm_sentinels(fns), jnp.int32)[None, :]
    inf = jnp.asarray([jnp.inf if fn == "min" else -jnp.inf for fn in fns], jnp.float32)[None, :]
    vals = jax.lax.bitcast_convert_type(f32_order_key(keys), jnp.float32)
    return jnp.where(keys == empty, inf, vals)


# -- the kernel --------------------------------------------------------------
def _cat(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _kernel(*refs, windowed, **static):
    """One grid step.  A plain launch folds row tile ``i`` at step ``i``.  A
    windowed launch reads its step table first (scalar prefetch): step
    ``s`` folds row tile ``steps[s]`` into group window ``steps[S + s]``;
    steps past the table's end (``s >= scalars[3]``) repeat its last one
    and change nothing."""
    if not windowed:
        i = pl.program_id(0)
        _step(*refs, tile_no=i, win=None, first=i == 0, **static)
        return
    steps_ref, *refs = refs
    s = pl.program_id(0)
    half = steps_ref.shape[0] // 2
    win = steps_ref[half + s]
    first = (s == 0) | (win != steps_ref[half + jnp.maximum(s - 1, 0)])

    @pl.when(s < refs[0][3])
    def _():
        _step(*refs, tile_no=steps_ref[s], win=win, first=first, **static)


def _step(
    sc_ref,
    pred_ref,
    grow_ref,
    gcol_ref,
    pass_ref,
    limb_ref,
    mmf_ref,
    mmi_ref,
    af_ref,
    ai_ref,
    ctab_ref,
    cnt_ref,
    gsum_ref,
    gcnt_ref,
    gmmf_ref,
    gmmi_ref,
    gfirst_ref,
    *,
    op,
    kind,
    descrs_f,
    descrs_i,
    csums,
    fns_f,
    fns_i,
    with_gidx,
    segmented,
    ngroups,
    tile,
    tile_no,
    win,
    first,
):
    rows = tile_no * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    mask = rows < sc_ref[0]
    if kind != "none":
        mask = pred_mask(pred_ref[...], sc_ref[1], sc_ref[2], op=op, kind=kind) & mask

    # -- projection arithmetic on pre-filter rows (element-wise == the
    #    reference's post-filter values on every surviving row)
    checked = [eval_checked(d, af_ref[...]) for d in descrs_f]
    icols = [eval_descr(d, ai_ref[...]) for d in descrs_i]

    # -- one-hot compaction of passthrough planes + computed columns (+ the
    #    float envelope flag, so only surviving rows' flags reach the host)
    parts = [pass_ref[...]]
    parts += [jax.lax.bitcast_convert_type(v, jnp.int32) for v, _f in checked]
    parts += icols
    if checked:
        flag = checked[0][1]
        for _v, f in checked[1:]:
            flag = flag | f
        parts.append(jnp.where(flag, 1, 0))
    if with_gidx:
        parts.append(gcol_ref[...])
    ctab_ref[...] = compact(mask, _cat(parts))
    cnt_ref[tile_no] = survivors(mask)

    sent_f = mm_sentinels(fns_f)
    sent_i = mm_sentinels(fns_i)

    @pl.when(first)
    def _():
        gsum_ref[...] = jnp.zeros_like(gsum_ref)
        gcnt_ref[...] = jnp.zeros_like(gcnt_ref)
        gfirst_ref[...] = jnp.full_like(gfirst_ref, I32_MAX)
        gmmf_ref[...] = mm_init(ngroups, sent_f)
        gmmi_ref[...] = mm_init(ngroups, sent_i)

    if not segmented:
        return

    # -- masked segment fold (only surviving rows reach any group); a
    #    windowed step folds the rows whose group lies in its window
    gids = grow_ref[...] if win is None else grow_ref[...] - win * ngroups
    oh = onehot(gids, mask, ngroups)
    limbs = [limb_ref[...]]
    for k in csums:
        v = icols[k]
        limbs += [(v >> (8 * s)) & 0xFF for s in range(3)]
        limbs.append(v >> 24)  # signed top limb (arithmetic shift)
    gsum_ref[...] += onehot_dot(oh, _cat(limbs))
    gcnt_ref[...] += jnp.sum(jnp.where(oh, 1, 0), axis=1, keepdims=True)
    gfirst_ref[...] = jnp.minimum(gfirst_ref[...], jnp.where(oh, rows, I32_MAX).min(axis=1, keepdims=True))
    gmmf_ref[...] = mm_fold(gmmf_ref[...], mmf_ref[...], oh, fns_f, sent_f)
    gmmi_ref[...] = mm_fold(gmmi_ref[...], mmi_ref[...], oh, fns_i, sent_i)


def fused_chain_tiles(
    scalars,
    pred,
    gidx,
    pass_tbl,
    limb_tbl,
    mmf,
    mmi,
    af,
    ai,
    steps=None,
    *,
    op: str,
    kind: str,
    descrs_f: tuple,
    descrs_i: tuple,
    csums: tuple,
    fns_f: tuple,
    fns_i: tuple,
    with_gidx: bool,
    segmented: bool,
    ngroups: int,
    tile: int = 256,
    interpret: bool = False,
):
    """One launch over the whole morsel chain.

    Inputs (all row tables padded to a multiple of ``tile``; unused tables
    are width-1 zero dummies):

        scalars   (4,)      int32  [n_rows, t_hi bits, t_lo bits, live steps]
        pred      (N, P)    int32  filter-column bit-planes
        gidx      (N,)      int32  full-morsel group ids (zeros unsegmented)
        pass_tbl  (N, Dp)   int32  compaction passthrough planes
        limb_tbl  (N, L)    int32  passthrough sum-column 8-bit limb planes
        mmf       (N, Mf)   f32    min/max float32 columns (no NaN, no -0.0)
        mmi       (N, Mi)   i32    min/max int columns (widened)
        af        (N, Af)   f32    projection-arithmetic input columns
        ai        (N, Ai)   i32    projection-arithmetic input columns
        steps     (2S,)     int32  windowed fold only: row tile, then group
                                   window, of each of S grid steps

    Without ``steps`` the fold's one-hot spans all ``ngroups`` groups, so
    its cost is rows x groups.  With ``steps`` (rows sorted by group id) the
    group table is N rows tall and each step folds one row tile into one
    window of ``ngroups`` consecutive groups, the window's block resident
    while consecutive steps name it: the cost is rows x ``ngroups``
    whatever the morsel's group count.  ``scalars[3]`` is the number of
    live steps.

    Returns ``(ctab, counts, gsum, gcnt, gmmf, gmmi, gfirst)``: the
    per-tile-compacted table ``[pass | computed f32 | computed i32 |
    f32 envelope flag (with any computed f32) | gidx?]`` with per-tile
    survivor counts, and per-group limb sums
    ``[passthrough | in-kernel csums]``, counts, min/max extremes, and the
    minimum surviving row index (``2^31-1`` for groups with no survivors;
    a windowed fold leaves the groups of windows no step names undefined).
    """
    n, dp = pass_tbl.shape
    assert n % tile == 0, (n, tile)
    assert ngroups % 8 == 0 and ngroups > 0, ngroups
    windowed = steps is not None
    if windowed:
        assert n % ngroups == 0, (n, ngroups)
    p = pred.shape[1]
    length = limb_tbl.shape[1]
    mf, mi = mmf.shape[1], mmi.shape[1]
    afw, aiw = af.shape[1], ai.shape[1]
    dc = dp + len(descrs_f) + len(descrs_i) + (1 if descrs_f else 0) + (1 if with_gidx else 0)
    ls = length + 4 * len(csums)
    assert len(fns_f) == mf and len(fns_i) == mi, (fns_f, mf, fns_i, mi)
    kernel = functools.partial(
        _kernel,
        windowed=windowed,
        op=op,
        kind=kind,
        descrs_f=descrs_f,
        descrs_i=descrs_i,
        csums=csums,
        fns_f=fns_f,
        fns_i=fns_i,
        with_gidx=with_gidx,
        segmented=segmented,
        ngroups=ngroups,
        tile=tile,
    )
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    if windowed:
        half = steps.shape[0] // 2

        def tile_of(s, st):
            return st[s]

        def win_of(s, st):
            return st[half + s]

    else:

        def tile_of(i):
            return i

        def win_of(i):
            return 0

    def rows(width):  # a (width, N) row-major table, one (width, tile) block per step
        return pl.BlockSpec((width, tile), lambda *a: (0, tile_of(*a)))

    def tiles(width):  # a (N, width) column table, one (tile, width) block per step
        return pl.BlockSpec((tile, width), lambda *a: (tile_of(*a), 0))

    def groups(width):  # a per-group accumulator: the whole table, or the step's window of it
        return pl.BlockSpec((ngroups, width), lambda *a: (win_of(*a), 0))

    height = n if windowed else ngroups
    in_specs = [smem, rows(p), rows(1), tiles(1), tiles(dp), tiles(length), rows(mf), rows(mi), tiles(afw), tiles(aiw)]
    out_specs = [tiles(dc), smem, groups(ls), groups(1), groups(mf), groups(mi), groups(1)]
    if windowed:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(half,), in_specs=in_specs, out_specs=out_specs
        )
        call_kw = {"grid_spec": grid_spec}
        lead = (jnp.asarray(steps, jnp.int32),)
    else:
        call_kw = {"grid": (n // tile,), "in_specs": in_specs, "out_specs": out_specs}
        lead = ()
    gidx = jnp.asarray(gidx, jnp.int32)
    mmf_keys = f32_order_key(jax.lax.bitcast_convert_type(jnp.asarray(mmf, jnp.float32), jnp.int32))
    ctab, counts, gsum, gcnt, gmmf, gmmi, gfirst = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((n, dc), jnp.int32),
            jax.ShapeDtypeStruct((n // tile,), jnp.int32),
            jax.ShapeDtypeStruct((height, ls), jnp.int32),
            jax.ShapeDtypeStruct((height, 1), jnp.int32),
            jax.ShapeDtypeStruct((height, mf), jnp.int32),
            jax.ShapeDtypeStruct((height, mi), jnp.int32),
            jax.ShapeDtypeStruct((height, 1), jnp.int32),
        ],
        interpret=interpret,
        **call_kw,
    )(
        *lead,
        jnp.asarray(scalars, jnp.int32),
        jnp.asarray(pred).T,
        gidx.reshape(1, n),
        gidx.reshape(n, 1),
        pass_tbl,
        limb_tbl,
        mmf_keys.T,
        jnp.asarray(mmi).T,
        af,
        ai,
    )
    return ctab, counts, gsum, gcnt[:, 0], f32_from_keys(gmmf, fns_f), gmmi, gfirst[:, 0]
