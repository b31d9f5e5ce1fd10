"""Fused project-arithmetic kernel: a COOK ``project`` node's arithmetic
Expr chains (``col op col`` / ``col op lit``) compiled into one VPU pass.

The compute backend lowers each eligible expression tree
(``repro.core.expr.Expr``) into a hashable **descriptor** —

    ("col", j)            column j of the morsel table
    ("lit", v)            python scalar (weak-typed, numpy-2 promotion)
    (op, a, b)            op in {add, sub, mul}, a/b descriptors

— and this module compiles the descriptor tuple into a Pallas kernel that
evaluates every output column of the projection over a (TILE, D) block in a
single fused pass: one HBM→VMEM read of the input columns, one write of the
projected columns, no per-expression numpy temporaries.  Kernels are cached
per descriptor signature (thresholds and column indices are static), so a
long-running pipeline compiles each projection shape once.

Arithmetic runs in the table's dtype (float32 or int32) with weak scalar
promotion.  int32 arithmetic wraps exactly like numpy's.  float32 agrees
with numpy's IEEE arithmetic only inside an **exact envelope**: hardware
may flush subnormals to zero (the CPU backend and the TPU both do), encode
NaN results differently, or contract a multiply into an add.  So every
float32 operand, intermediate and result is checked in-kernel, and a row
is flagged when any of them is ±inf, NaN, subnormal, or a zero that only a
flushed underflow explains (a product of nonzero operands, a sum of
operands that do not cancel).  Inside the envelope the rounded
results are IEEE's; the caller recomputes flagged morsels with the numpy
reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["project_tiles", "eval_descr", "eval_checked"]

_ARITH = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b,
}
_EXP = 0x7F800000
_MANT = 0x007FFFFF
_ABS = 0x7FFFFFFF


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


def _kernel(tbl_ref, out_ref, *, descrs, checked):
    block = tbl_ref[...]  # (tile, D)
    if checked:
        pairs = [eval_checked(d, block) for d in descrs]
        flag = pairs[0][1]
        for _v, f in pairs[1:]:
            flag = flag | f
        cols = [v for v, _f in pairs] + [jnp.where(flag, 1.0, 0.0)]
    else:
        cols = [eval_descr(d, block) for d in descrs] + [jnp.zeros((block.shape[0], 1), block.dtype)]
    out_ref[...] = jnp.concatenate(cols, axis=1).astype(out_ref.dtype)


@functools.lru_cache(maxsize=256)
def _compiled(descrs: tuple, d: int, dtype_name: str, tile: int, interpret: bool):
    dtype = jnp.dtype(dtype_name)
    kernel = functools.partial(_kernel, descrs=descrs, checked=dtype == jnp.float32)
    width = len(descrs) + 1

    def run(table):
        n = table.shape[0]
        return pl.pallas_call(
            kernel,
            grid=(n // tile,),
            in_specs=[pl.BlockSpec((tile, d), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((tile, width), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, width), dtype),
            interpret=interpret,
        )(table)

    return jax.jit(run)


def project_tiles(table, descrs, tile: int = 256, interpret: bool = False):
    """table: (N, D) float32|int32, N a multiple of ``tile``; ``descrs`` is a
    tuple of expression descriptors.  Returns (N, len(descrs) + 1) in the
    table dtype: one column per descriptor, then a flag column that is
    nonzero on rows whose float32 arithmetic left the exact envelope
    (always zero for int32).  Padding rows hold garbage (the caller trims
    to the morsel size)."""
    n, d = table.shape
    assert n % tile == 0, (n, tile)
    fn = _compiled(tuple(descrs), d, table.dtype.name, tile, bool(interpret))
    return fn(table)
