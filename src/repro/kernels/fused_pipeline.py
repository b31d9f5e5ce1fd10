"""Whole-chain fused pipeline kernel: filter → project → segment-reduce in
ONE Pallas launch per morsel (DESIGN.md §3.2, the device-resident executor).

The per-op kernels (``filter_select``, ``project_arith``,
``segment_reduce``) each cross the host↔device boundary once per morsel:
mask + compaction comes back to the host, the compacted table is re-padded
and re-uploaded for projection, and the factorized fold is a third launch.
This kernel keeps the morsel's bit-plane columns device-resident across all
three stages — per row-tile, in a single grid step:

  1. predicate mask on the filter column's int32 plane(s) (f32 bitcast /
     i32 / two-word i64 compare — same ``_pred_mask`` as filter_select),
  2. projection arithmetic on the *pre-filter* rows (element-wise, so the
     surviving rows carry exactly the values the reference computes after
     filtering), descriptors compiled like ``project_arith``,
  3. integer one-hot compaction matmul of the passthrough planes + the
     bitcast computed columns (+ the group-id column when a float sum needs
     the host's f64 fold),
  4. masked one-hot **segment fold** for the aggregate tail: 8-bit-limb
     sums (passthrough columns arrive as host-built limb planes; computed
     int32 columns are limb-decomposed in-kernel), group counts, f32/i32
     masked min/max, and each group's minimum surviving row index — the
     host reorders groups into first-seen-filtered order from it, which
     makes the fused partial ``GroupState`` byte-identical to the
     reference fold over the filtered batch.

Everything stays int32/float32 in-kernel and lowers the same way as the
per-op kernels: byte-plane bf16 one-hot matmuls for compaction and limb
sums (exact, see ``filter_select`` / ``segment_reduce``), a matmul prefix
sum instead of ``cumsum``, integer order keys for float compares and
min/max, scalars and per-tile counts in SMEM, and the predicate planes,
group ids and min/max columns laid out as rows so they broadcast against
the group axis.
Float sums are NOT folded in-kernel (f64 accumulation order matters); their
source planes ride through the compaction output and the host folds them
with ``np.add.at`` in row order — bit-identical to the reference.

Static plan parameters (the lru-cached kernel signature):

    op, kind       predicate comparison + column kind ("none" = no filter)
    descrs_f/_i    project_arith descriptor trees over the f32 / i32 tables
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

from repro.kernels.filter_select import compact, f32_order_key, onehot_dot, pred_mask, survivors
from repro.kernels.project_arith import eval_checked, eval_descr
from repro.kernels.segment_reduce import I32_MAX, f32_from_keys, mm_fold, mm_init, mm_sentinels, onehot

__all__ = ["fused_chain_tiles"]


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
