"""Segment reductions for per-morsel partial aggregation (pushdown R9 on
the accelerator).

``GroupState`` factorizes a morsel's key columns into dense group ids; these
kernels then fold the morsel's value columns into per-group accumulators on
the TPU, using the same one-hot MXU pattern as ``filter_select``:

  * **segment_sum_tiles** — per-tile one-hot matmul ``onehot(G, T) @ limbs
    (T, S)`` accumulated across the grid.  Value columns arrive decomposed
    into **8-bit limbs widened to int32** (8 limbs for int64, 4 for int32;
    ``repro.core.backend`` encodes).  The matmul runs on bf16 operands
    (limbs in [-128, 255] are exact in bf16) with f32 accumulation: one
    tile's limb sum is at most 256 × 255 = 65,280 < 2^24, so it is exact,
    and it converts to int32 before the cross-tile accumulation.  Over a
    whole 262144-row morsel each limb sum stays below 2^26, so the int32
    accumulator is exact and the host recombines ``Σ limb_sum_k << 8k``
    into the int64 accumulator — the result is bit-identical to numpy's
    sequential ``np.add.at`` including int64 wraparound.  Group **counts**
    (a row-sum of the one-hot matrix) ride along in the same pass.
  * **segment_minmax_tiles** — per-group min/max via a masked broadcast
    reduce (VPU): ``where(onehot, vals, sentinel)`` reduced over the tile
    axis, accumulated across tiles with ``minimum``/``maximum``.  Everything
    reduces as int32: float32 columns compare through their order-preserving
    int32 image (``filter_select.f32_order_key``), so the extremes are bit
    patterns of inputs, whatever the chip does with subnormals.  Wide
    min/max — int64, and uint64/float64 through an order-preserving int64
    key image — run as **two passes** of this kernel (host-orchestrated in
    ``repro.core.backend``): pass 1 reduces the signed hi words, pass 2 the
    sign-flipped lo words among rows at their group's hi extreme — the
    lexicographic (hi, lo') order equals the key order, full 64-bit exact.

Group ids and value columns arrive as rows — (1, N) and (M, N) — so they
broadcast against the group axis without an in-kernel transpose; the row
bound ``n_rows`` sits in scalar memory.  Group ids ≥ the padded group count
never occur (the backend caps eligibility at ``ngroups <= G``); padding
**rows** are masked with the ``n_rows`` bound, so they contribute zero /
sentinel to every group.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.filter_select import f32_order_key, onehot_dot, row_ids

__all__ = ["segment_sum_tiles", "segment_minmax_tiles", "SUM_ROW_CAP"]

# 8-bit limbs: |limb| <= 255 (top limb signed, in [-128, 127]), so a sum over
# SUM_ROW_CAP rows is < 2^26 — comfortably exact in the int32 accumulator.
SUM_ROW_CAP = 262144

I32_MAX = 2**31 - 1
I32_MIN = -(2**31)


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


def _sum_kernel(nvalid_ref, gidx_ref, limb_ref, sum_ref, cnt_ref, *, ngroups, tile):
    oh = onehot(gidx_ref[...], row_ids(tile) < nvalid_ref[0], ngroups)

    @pl.when(pl.program_id(0) == 0)
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    sum_ref[...] += onehot_dot(oh, limb_ref[...])
    cnt_ref[...] += jnp.sum(jnp.where(oh, 1, 0), axis=1, keepdims=True)


def segment_sum_tiles(gidx, limbs, n_rows, ngroups: int, tile: int = 256, interpret: bool = False):
    """gidx: (N,) int32 in [0, ngroups); limbs: (N, S) int32 8-bit limb
    planes; rows >= n_rows are padding.  Returns (limb sums (ngroups, S)
    int32, counts (ngroups,) int32)."""
    n, s = limbs.shape
    assert n % tile == 0, (n, tile)
    kernel = functools.partial(_sum_kernel, ngroups=ngroups, tile=tile)
    sums, counts = pl.pallas_call(
        kernel,
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((tile, s), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((ngroups, s), lambda i: (0, 0)),
            pl.BlockSpec((ngroups, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((ngroups, s), jnp.int32),
            jax.ShapeDtypeStruct((ngroups, 1), jnp.int32),
        ],
        interpret=interpret,
    )(jnp.asarray(n_rows, jnp.int32).reshape(1), jnp.asarray(gidx).reshape(1, n), limbs)
    return sums, counts[:, 0]


def _minmax_kernel(nvalid_ref, gidx_ref, val_ref, out_ref, *, fns, ngroups, tile, sentinels):
    oh = onehot(gidx_ref[...], row_ids(tile) < nvalid_ref[0], ngroups)

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = mm_init(ngroups, sentinels)

    out_ref[...] = mm_fold(out_ref[...], val_ref[...], oh, fns, sentinels)


def segment_minmax_tiles(gidx, vals, n_rows, ngroups: int, fns, tile: int = 256, interpret: bool = False):
    """gidx: (N,) int32; vals: (N, M) float32 or int32; ``fns[j]`` is "min"
    or "max" for column j.  Returns per-group reductions (ngroups, M); groups
    with no rows hold the identity sentinel (+inf / -inf / int32 extremes).
    float32 columns must hold no NaN and no ``-0.0`` (the order key ranks
    ``-0.0`` below ``+0.0``; numpy's result between them depends on row
    order) — the backend checks both before dispatch."""
    n, m = vals.shape
    assert n % tile == 0, (n, tile)
    fns = tuple(fns)
    is_float = vals.dtype == jnp.float32
    rows = vals.T
    if is_float:
        rows = f32_order_key(jax.lax.bitcast_convert_type(rows, jnp.int32))
    sentinels = mm_sentinels(fns)
    kernel = functools.partial(_minmax_kernel, fns=fns, ngroups=ngroups, tile=tile, sentinels=sentinels)
    out = pl.pallas_call(
        kernel,
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((m, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((ngroups, m), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((ngroups, m), jnp.int32),
        interpret=interpret,
    )(jnp.asarray(n_rows, jnp.int32).reshape(1), jnp.asarray(gidx).reshape(1, n), rows)
    return f32_from_keys(out, fns) if is_float else out


def f32_from_keys(keys, fns):
    """Decode order keys back to float32; empty groups get ±inf."""
    empty = jnp.asarray(mm_sentinels(fns), jnp.int32)[None, :]
    inf = jnp.asarray([jnp.inf if fn == "min" else -jnp.inf for fn in fns], jnp.float32)[None, :]
    vals = jax.lax.bitcast_convert_type(f32_order_key(keys), jnp.float32)
    return jnp.where(keys == empty, inf, vals)
