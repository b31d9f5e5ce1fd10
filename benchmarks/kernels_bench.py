"""Kernel micro-benchmarks (interpret mode on CPU — timings indicative
only; the authoritative perf story for TPU is the §Roofline analysis).
Reports kernel vs pure-jnp oracle on identical shapes."""

from __future__ import annotations

import json
import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.kernels import ops, ref


def _time(fn, *args, iters=3) -> float:
    out = fn(*args)
    jnp.stack([x.ravel()[0] for x in (out if isinstance(out, tuple) else (out,))]).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        jnp.stack([x.ravel()[0] for x in (out if isinstance(out, tuple) else (out,))]).block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def _time_host(fn, iters=5) -> float:
    """Time a closure that materializes its own outputs to host numpy —
    ``np.asarray`` is the sync, exactly as the executor's decode path pays
    it (``jnp.stack``-style blocking over many mixed-dtype outputs adds
    milliseconds of dispatch that the real pipeline never sees)."""
    fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e6


def run(verbose: bool = True) -> dict:
    r = np.random.default_rng(0)
    results = {}

    b, kv, g, s, hd = 1, 2, 2, 512, 64
    q = jnp.asarray(r.normal(size=(b, kv, g, s, hd)).astype(np.float32))
    k = jnp.asarray(r.normal(size=(b, kv, s, hd)).astype(np.float32))
    v = jnp.asarray(r.normal(size=(b, kv, s, hd)).astype(np.float32))
    results["flash_attention_us"] = _time(lambda: ops.flash_attention(q, k, v, block_q=128, block_k=128))
    results["flash_attention_ref_us"] = _time(lambda: ref.flash_attention_ref(q, k, v))

    qd = jnp.asarray(r.normal(size=(b, kv, g, hd)).astype(np.float32))
    kd = jnp.asarray(r.normal(size=(b, kv, 4096, hd)).astype(np.float32))
    vd = jnp.asarray(r.normal(size=(b, kv, 4096, hd)).astype(np.float32))
    results["decode_attention_us"] = _time(lambda: ops.decode_attention(qd, kd, vd, 4000))
    results["decode_attention_ref_us"] = _time(lambda: ref.decode_attention_ref(qd, kd, vd, 4000))

    x = jnp.asarray(r.normal(size=(1, 512, 4, 64)).astype(np.float32))
    dt = jnp.asarray(np.abs(r.normal(size=(1, 512, 4))).astype(np.float32) * 0.1)
    A = jnp.asarray(-np.abs(r.normal(size=(4,))).astype(np.float32))
    B = jnp.asarray(r.normal(size=(1, 512, 32)).astype(np.float32))
    C = jnp.asarray(r.normal(size=(1, 512, 32)).astype(np.float32))
    results["ssd_scan_us"] = _time(lambda: ops.ssd_scan(x, dt, A, B, C, chunk=128))
    results["ssd_scan_ref_us"] = _time(lambda: ref.ssd_scan_ref(x, dt, A, B, C)[0])

    qm = jnp.asarray(r.normal(size=(1, 512, 2, 64)).astype(np.float32))
    li = jnp.asarray(r.normal(size=(1, 512, 2)).astype(np.float32))
    lf = jnp.asarray(r.normal(size=(1, 512, 2)).astype(np.float32) - 1)
    results["mlstm_chunk_us"] = _time(lambda: ops.mlstm_chunk(qm, qm, qm, li, lf, chunk=128))
    results["mlstm_chunk_ref_us"] = _time(lambda: ref.mlstm_chunk_ref(qm, qm, qm, li, lf))

    # multi-dtype bit-plane form (int64 predicate over hi/lo planes) —
    # the production kernel the compute backend dispatches to
    n = 4096
    planes = jnp.asarray(r.integers(-(2**31), 2**31, (n, 4)).astype(np.int32))
    pred = planes[:, :2]
    scalars = jnp.asarray([n, 0, 0], jnp.int32)  # [n_rows, t_hi bits, t_lo bits]
    results["filter_select_planes_us"] = _time(
        lambda: ops.filter_select_planes(pred, planes, scalars, "gt", "i64", tile=256)
    )

    # segment reductions (the aggregate breaker's per-morsel partial fold)
    gidx = jnp.asarray(r.integers(0, 64, n).astype(np.int32))
    limbs = jnp.asarray(r.integers(0, 255, (n, 8)).astype(np.int32))
    results["segment_sum_us"] = _time(lambda: ops.segment_sum_tiles(gidx, limbs, n, 64, tile=256))
    vals = jnp.asarray(r.normal(size=(n, 2)).astype(np.float32))
    results["segment_minmax_us"] = _time(
        lambda: ops.segment_minmax_tiles(gidx, vals, n, 64, ("min", "max"), tile=256)
    )

    # fused project arithmetic ((a*2+1, (a-b)*0.75) over one VMEM pass)
    ptbl = jnp.asarray(r.normal(size=(n, 2)).astype(np.float32))
    descrs = (
        ("add", ("mul", ("col", 0), ("lit", 2.0)), ("lit", 1.0)),
        ("mul", ("sub", ("col", 0), ("col", 1)), ("lit", 0.75)),
    )
    results["project_arith_us"] = _time(lambda: ops.project_tiles(ptbl, descrs, tile=256))

    # one-launch fused chain (filter → project → segment fold) vs the same
    # logical chain as separate kernel launches with the host round-trips
    # the per-op backend path really pays between them — the device-resident
    # execution win (speedup_fused_vs_unfused gates in CI).  Morsel-sized
    # input: per-launch overhead is exactly what fusion amortizes away
    n, ng, tile = 1024, 64, 256
    xs = r.normal(size=n).astype(np.float32)
    iv = r.integers(-500, 500, n).astype(np.int32)
    gix = r.integers(0, ng, n).astype(np.int32)
    v64 = iv.astype(np.int64)
    limbs = np.stack(
        [((v64 >> (8 * k)) & 0xFF).astype(np.int32) for k in range(7)] + [(v64 >> 56).astype(np.int32)],
        axis=1,
    )
    zcol = np.zeros((n, 1), np.int32)
    cdescr = (("add", ("mul", ("col", 0), ("lit", 2.0)), ("lit", 1.0)),)
    jxp = jnp.asarray(xs.view(np.int32).reshape(n, 1))
    jx = jnp.asarray(xs.reshape(n, 1))
    jiv = jnp.asarray(iv.reshape(n, 1))
    jg, jlimbs, jz = jnp.asarray(gix), jnp.asarray(limbs), jnp.asarray(zcol)
    fscalars = jnp.asarray([n, 0, 0, 0], jnp.int32)

    def fused_chain():
        out = ops.fused_chain_tiles(
            fscalars, jxp, jg, jz, jlimbs, jx, jiv, jx, jz,
            op="gt", kind="f32", descrs_f=cdescr, descrs_i=(), csums=(),
            fns_f=("max",), fns_i=("min",), with_gidx=False, segmented=True,
            ngroups=ng, tile=tile,
        )
        return [np.asarray(o) for o in out]  # host decode, as the plan pays it

    results["fused_chain_us"] = _time_host(fused_chain)

    ftbl = jnp.asarray(
        np.concatenate([xs.view(np.int32).reshape(n, 1), iv.reshape(n, 1), gix.reshape(n, 1)], axis=1)
    )
    fsel_scalars = jnp.asarray([n, 0, 0], jnp.int32)

    def unfused_chain():
        # launch 1: filter + compact the predicate/payload planes
        out, counts = ops.filter_select_planes(jxp, ftbl, fsel_scalars, "gt", "f32", tile=tile)
        out, counts = np.asarray(out), np.asarray(counts)  # host round-trip
        sel = np.concatenate([out[i * tile : i * tile + c] for i, c in enumerate(counts) if c])
        m = sel.shape[0]
        pad = (m + tile - 1) // tile * tile or tile
        # launch 2: project c = x*2+1 over the survivors
        ptab = np.zeros((pad, 1), np.float32)
        ptab[:m, 0] = sel[:, 0].view(np.float32)
        proj = np.asarray(ops.project_tiles(jnp.asarray(ptab), cdescr, tile=tile))  # host round-trip
        # launches 3+4: segment folds (8-limb int sum, float max) on survivors
        s64 = sel[:, 1].astype(np.int64)
        slimbs = np.zeros((pad, 8), np.int32)
        for k in range(7):
            slimbs[:m, k] = ((s64 >> (8 * k)) & 0xFF).astype(np.int32)
        slimbs[:m, 7] = (s64 >> 56).astype(np.int32)
        sg = np.zeros(pad, np.int32)
        sg[:m] = sel[:, 2]
        gs = ops.segment_sum_tiles(jnp.asarray(sg), jnp.asarray(slimbs), m, ng, tile=tile)
        vals = np.zeros((pad, 1), np.float32)
        vals[:m, 0] = proj[:m, 0]
        mm = ops.segment_minmax_tiles(jnp.asarray(sg), jnp.asarray(vals), m, ng, ("max",), tile=tile)
        flat = []
        for o in (gs, mm):
            flat.extend(o) if isinstance(o, tuple) else flat.append(o)
        return [np.asarray(o) for o in flat]  # host decode, as the plan pays it

    results["unfused_chain_us"] = _time_host(unfused_chain)
    results["speedup_fused_vs_unfused"] = results["unfused_chain_us"] / results["fused_chain_us"]

    if verbose:
        for name in ("flash_attention", "decode_attention", "ssd_scan", "mlstm_chunk"):
            emit(f"kernels.{name}", results[f"{name}_us"], f"ref={results[f'{name}_ref_us']:.0f}us,interp")
        for name in ("filter_select_planes", "segment_sum", "segment_minmax", "project_arith"):
            emit(f"kernels.{name}", results[f"{name}_us"], "interp")
        emit(
            "kernels.fused_chain",
            results["fused_chain_us"],
            f"unfused={results['unfused_chain_us']:.0f}us,{results['speedup_fused_vs_unfused']:.2f}x",
        )
    return results


if __name__ == "__main__":
    print(json.dumps(run(), indent=1))
