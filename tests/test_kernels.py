"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels import ops, ref

R = np.random.default_rng(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("b,kv,g,s,hd", [(1, 1, 1, 128, 64), (2, 2, 2, 256, 64), (1, 4, 2, 512, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, kv, g, s, hd, dtype, causal):
    q = jnp.asarray(R.normal(size=(b, kv, g, s, hd)), dtype)
    k = jnp.asarray(R.normal(size=(b, kv, s, hd)), dtype)
    v = jnp.asarray(R.normal(size=(b, kv, s, hd)), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=128)
    want = ref.flash_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), causal)
    assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("t,length,blk", [(256, 256, 128), (512, 300, 128), (1024, 17, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(t, length, blk, dtype):
    b, kv, g, hd = 2, 2, 4, 64
    q = jnp.asarray(R.normal(size=(b, kv, g, hd)), dtype)
    k = jnp.asarray(R.normal(size=(b, kv, t, hd)), dtype)
    v = jnp.asarray(R.normal(size=(b, kv, t, hd)), dtype)
    got = ops.decode_attention(q, k, v, length, block_k=blk)
    want = ref.decode_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), length)
    assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("s,chunk", [(64, 16), (128, 32), (256, 64)])
@pytest.mark.parametrize("p,n", [(32, 16), (64, 64)])
def test_ssd_scan_sweep(s, chunk, p, n):
    b, h = 2, 3
    x = jnp.asarray(R.normal(size=(b, s, h, p)).astype(np.float32))
    dt = jnp.asarray(np.abs(R.normal(size=(b, s, h))).astype(np.float32) * 0.1)
    A = jnp.asarray(-np.abs(R.normal(size=(h,))).astype(np.float32))
    B = jnp.asarray(R.normal(size=(b, s, n)).astype(np.float32))
    C = jnp.asarray(R.normal(size=(b, s, n)).astype(np.float32))
    got = ops.ssd_scan(x, dt, A, B, C, chunk=chunk)
    want, _ = ref.ssd_scan_ref(x, dt, A, B, C)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("s,chunk,d", [(64, 16, 32), (128, 64, 64)])
def test_mlstm_chunk_sweep(s, chunk, d):
    b, h = 2, 2
    q = jnp.asarray(R.normal(size=(b, s, h, d)).astype(np.float32))
    k = jnp.asarray(R.normal(size=(b, s, h, d)).astype(np.float32))
    v = jnp.asarray(R.normal(size=(b, s, h, d)).astype(np.float32))
    li = jnp.asarray(R.normal(size=(b, s, h)).astype(np.float32))
    lf = jnp.asarray(R.normal(size=(b, s, h)).astype(np.float32) - 1.0)
    got = ops.mlstm_chunk(q, k, v, li, lf, chunk=chunk)
    want = ref.mlstm_chunk_ref(q, k, v, li, lf)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=5e-4, atol=5e-4)


def _stream(pred, table, scalars, op: str, kind: str, tile: int):
    """The fused kernel in stream mode (a filter and its compaction, no
    projection, no fold): ``(per-tile-compacted table, per-tile counts)``."""
    n = table.shape[0]
    zcol = jnp.zeros((n, 1), jnp.int32)
    ctab, counts, *_ = ops.fused_chain_tiles(
        scalars,
        pred,
        jnp.zeros(n, jnp.int32),
        table,
        zcol,
        jnp.zeros((n, 1), jnp.float32),
        zcol,
        jnp.zeros((n, 1), jnp.float32),
        zcol,
        op=op,
        kind=kind,
        descrs_f=(),
        descrs_i=(),
        csums=(),
        fns_f=("min",),
        fns_i=("min",),
        with_gidx=False,
        segmented=False,
        ngroups=8,
        tile=tile,
    )
    return np.asarray(ctab), np.asarray(counts)


@pytest.mark.parametrize("n,d,tile", [(512, 4, 128), (1024, 8, 256)])
@pytest.mark.parametrize("op", ["gt", "le", "eq"])
def test_fused_stream_compaction_sweep(n, d, tile, op):
    """Bit-plane compaction of the fused kernel's stream mode == numpy
    boolean indexing, bit-exact."""
    vals = R.normal(size=(n, d)).astype(np.float32)
    vals[::37, 0] = -0.0
    thr = np.float32(0.1)
    planes = vals.view(np.int32)
    t_hi = np.array([thr], np.float32).view(np.int32)[0]
    scalars = np.array([n, t_hi, 0, 0], np.int32)
    got, counts = _stream(jnp.asarray(planes[:, :1]), jnp.asarray(planes), scalars, op, "f32", tile)
    cmp = {"gt": np.greater, "le": np.less_equal, "eq": np.equal}[op]
    mask = cmp(vals[:, 0], thr)
    front = np.concatenate([got[i * tile : i * tile + c] for i, c in enumerate(counts)])
    assert counts.sum() == mask.sum()
    np.testing.assert_array_equal(front.view(np.float32), vals[mask])


def test_fused_stream_i64_two_word():
    """int64 predicates compare as two int32 words — full-range exact."""
    n, tile = 512, 128
    v = R.integers(-(2**62), 2**62, size=n, dtype=np.int64)
    v[: tile // 2] = np.array([2**62 + 7, -(2**62) - 7], np.int64).repeat(tile // 4)
    target = np.int64(2**62 + 7)
    hi = (v >> 32).astype(np.int32)
    lo = (v & 0xFFFFFFFF).astype(np.uint64).astype(np.uint32).view(np.int32)
    pred = np.stack([hi, lo], axis=1)
    t_hi = np.int32(target >> 32)
    t_lo = np.int32(np.uint32(target & 0xFFFFFFFF).view(np.int32) ^ np.int32(-(2**31)))
    scalars = np.array([n, t_hi, t_lo, 0], np.int32)
    got, counts = _stream(jnp.asarray(pred), jnp.asarray(pred), scalars, "gt", "i64", tile)
    mask = v > target
    front = np.concatenate([got[i * tile : i * tile + c] for i, c in enumerate(counts)])
    assert counts.sum() == mask.sum()
    back = (front[:, 0].astype(np.int64) << 32) | front[:, 1].view(np.uint32).astype(np.int64)
    np.testing.assert_array_equal(back, v[mask])


def test_fused_chain_tiles_matches_numpy():
    """One-launch chain (filter → arith → compact → segment fold) == numpy."""
    from repro.kernels.fused_pipeline import fused_chain_tiles as raw_fused

    n, tile, ng = 512, 128, 8
    x = R.normal(size=n).astype(np.float32)
    iv = R.integers(-500, 500, size=n).astype(np.int32)
    g = R.integers(0, 5, size=n).astype(np.int32)
    thr = np.float32(0.0)
    scalars = np.array([n, np.array([thr], np.float32).view(np.int32)[0], 0, 0], np.int32)
    v64 = iv.astype(np.int64)
    limbs = np.stack(
        [((v64 >> (8 * k)) & 0xFF).astype(np.int32) for k in range(7)] + [(v64 >> 56).astype(np.int32)],
        axis=1,
    )
    zcol = np.zeros((n, 1), np.int32)
    out = raw_fused(
        jnp.asarray(scalars),
        jnp.asarray(x.view(np.int32).reshape(n, 1)),
        jnp.asarray(g),
        jnp.asarray(zcol),
        jnp.asarray(limbs),
        jnp.asarray(x.reshape(n, 1)),
        jnp.asarray(iv.reshape(n, 1)),
        jnp.asarray(x.reshape(n, 1)),
        jnp.asarray(zcol),
        op="gt",
        kind="f32",
        descrs_f=(("mul", ("col", 0), ("lit", 2.0)),),
        descrs_i=(),
        csums=(),
        fns_f=("max",),
        fns_i=("min",),
        with_gidx=False,
        segmented=True,
        ngroups=ng,
        tile=tile,
        interpret=True,
    )
    ctab, counts, gsum, gcnt, gmmf, gmmi, gfirst = [np.asarray(o) for o in out]
    mask = x > thr
    front = np.concatenate([ctab[i * tile : i * tile + c] for i, c in enumerate(counts)])
    np.testing.assert_array_equal(front[:, 1].view(np.float32), (x * np.float32(2.0))[mask])
    for gi in range(5):
        m = mask & (g == gi)
        assert gcnt[gi] == m.sum()
        tot = sum(int(gsum[gi, k]) << (8 * k) for k in range(7)) + (int(gsum[gi, 7]) << 56)
        assert np.int64(np.uint64(tot & (2**64 - 1))) == v64[m].sum()
        if m.any():
            assert gmmf[gi, 0] == x[m].max()
            assert gmmi[gi, 0] == iv[m].min()
            assert gfirst[gi] == np.flatnonzero(m)[0]
        else:
            assert gfirst[gi] == 2**31 - 1


def test_mlstm_kernel_matches_model_cell():
    """The Pallas chunkwise kernel and the model's recurrent scan agree."""
    from repro.models.xlstm import _mlstm_cell_scan

    b, s, h, d = 1, 64, 2, 32
    q = jnp.asarray(R.normal(size=(b, s, h, d)).astype(np.float32))
    k = jnp.asarray(R.normal(size=(b, s, h, d)).astype(np.float32))
    v = jnp.asarray(R.normal(size=(b, s, h, d)).astype(np.float32))
    li = jnp.asarray(R.normal(size=(b, s, h)).astype(np.float32))
    lf = jnp.asarray(R.normal(size=(b, s, h)).astype(np.float32) - 1.0)
    y_model, _ = _mlstm_cell_scan(q, k, v, li, lf)
    y_kernel = ops.mlstm_chunk(q, k, v, li, lf, chunk=16)
    assert_allclose(np.asarray(y_kernel), np.asarray(y_model), rtol=5e-4, atol=5e-4)


def test_ssd_kernel_matches_model_chunked():
    """Pallas SSD kernel ≡ the model's matmul-form chunked SSD."""
    from repro.models.ssm import _ssd_chunked

    b, s, h, p, n = 1, 128, 2, 32, 16
    x = jnp.asarray(R.normal(size=(b, s, h, p)).astype(np.float32))
    dt = jnp.asarray(np.abs(R.normal(size=(b, s, h))).astype(np.float32) * 0.1)
    A = jnp.asarray(-np.abs(R.normal(size=(h,))).astype(np.float32))
    B = jnp.asarray(R.normal(size=(b, s, n)).astype(np.float32))
    C = jnp.asarray(R.normal(size=(b, s, n)).astype(np.float32))
    y_model, _ = _ssd_chunked(x, dt, A, B, C, chunk=32)
    y_kernel = ops.ssd_scan(x, dt, A, B, C, chunk=32)
    assert_allclose(np.asarray(y_kernel), np.asarray(y_model), rtol=2e-4, atol=2e-4)
