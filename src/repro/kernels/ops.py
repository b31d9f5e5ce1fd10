"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to auto: True on the CPU (the kernels execute via
the Pallas interpreter for the correctness tests, ``JAX_PLATFORMS=cpu``),
False everywhere else (Mosaic compilation — a platform the kernels cannot
compile for fails loudly instead of interpreting).  Wrappers also own the
thin jnp epilogues (e.g. global compaction after per-tile filter_select).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as ref_mod
from repro.kernels.decode_attention import decode_attention as _decode_attention
from repro.kernels.filter_select import filter_select_planes as _filter_select_planes
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.fused_pipeline import fused_chain_tiles as _fused_chain_tiles
from repro.kernels.mlstm_chunk import mlstm_chunk as _mlstm_chunk
from repro.kernels.project_arith import project_tiles as _project_tiles
from repro.kernels.segment_reduce import SUM_ROW_CAP
from repro.kernels.segment_reduce import segment_minmax_tiles as _segment_minmax_tiles
from repro.kernels.segment_reduce import segment_sum_tiles as _segment_sum_tiles
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan

__all__ = [
    "auto_interpret",
    "flash_attention",
    "decode_attention",
    "ssd_scan",
    "mlstm_chunk",
    "filter_select_planes",
    "fused_chain_tiles",
    "project_tiles",
    "segment_sum_tiles",
    "segment_minmax_tiles",
    "SUM_ROW_CAP",
]


def auto_interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 512, block_k: int = 512):
    return _flash_attention(q, k, v, causal=causal, block_q=block_q, block_k=block_k, interpret=auto_interpret())


@functools.partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q, k, v, length, block_k: int = 1024):
    return _decode_attention(q, k, v, length, block_k=block_k, interpret=auto_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, B, C, chunk: int = 256):
    return _ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=auto_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def mlstm_chunk(q, k, v, log_i, log_f, chunk: int = 256):
    return _mlstm_chunk(q, k, v, log_i, log_f, chunk=chunk, interpret=auto_interpret())


@functools.partial(jax.jit, static_argnames=("op", "kind", "tile"))
def filter_select_planes(pred_planes, table, scalars, op: str, kind: str, tile: int = 256):
    # scalars = [n_rows, t_hi bits, t_lo bits] rides as traced data: a new
    # predicate literal (or morsel row count) reuses the compiled kernel
    return _filter_select_planes(
        pred_planes, table, scalars, op=op, kind=kind, tile=tile, interpret=auto_interpret()
    )


@functools.partial(jax.jit, static_argnames=("ngroups", "tile"))
def segment_sum_tiles(gidx, limbs, n_rows, ngroups: int, tile: int = 256):
    return _segment_sum_tiles(gidx, limbs, n_rows, ngroups, tile=tile, interpret=auto_interpret())


@functools.partial(jax.jit, static_argnames=("ngroups", "fns", "tile"))
def segment_minmax_tiles(gidx, vals, n_rows, ngroups: int, fns: tuple, tile: int = 256):
    return _segment_minmax_tiles(gidx, vals, n_rows, ngroups, fns, tile=tile, interpret=auto_interpret())


def project_tiles(table, descrs, tile: int = 256):
    return _project_tiles(table, descrs, tile=tile, interpret=auto_interpret())


_FUSED_STATIC = (
    "op",
    "kind",
    "descrs_f",
    "descrs_i",
    "csums",
    "fns_f",
    "fns_i",
    "with_gidx",
    "segmented",
    "ngroups",
    "tile",
)


@functools.partial(jax.jit, static_argnames=_FUSED_STATIC)
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
):
    # scalars = [n_rows, t_hi bits, t_lo bits, live steps] ride as traced
    # data: a new predicate literal / morsel row count reuses the compiled
    # chain, and so does a windowed fold's step table (its length is 2x the
    # row tiles, whatever the morsel's group count)
    return _fused_chain_tiles(
        scalars,
        pred,
        gidx,
        pass_tbl,
        limb_tbl,
        mmf,
        mmi,
        af,
        ai,
        steps,
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
        interpret=auto_interpret(),
    )


# re-export oracles next to the wrappers for test ergonomics
ref = ref_mod
