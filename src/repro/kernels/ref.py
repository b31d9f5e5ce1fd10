"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

Each ``*_ref`` is the simplest correct implementation of the kernel's exact
contract — no blocking, no online softmax, no chunking — so kernel tests
reduce to ``assert_allclose(kernel(x), ref(x))`` over shape/dtype sweeps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "flash_attention_ref",
    "decode_attention_ref",
    "ssd_scan_ref",
    "mlstm_chunk_ref",
]


def flash_attention_ref(q, k, v, causal: bool = True):
    """q: (B, KV, G, S, hd); k/v: (B, KV, T, hd).  fp32 softmax."""
    hd = q.shape[-1]
    s, t = q.shape[3], k.shape[2]
    scores = jnp.einsum("bngsh,bnth->bngst", q, k).astype(jnp.float32) * hd**-0.5
    if causal:
        mask = jnp.arange(s)[:, None] >= jnp.arange(t)[None, :]
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bngst,bnth->bngsh", p.astype(v.dtype), v)


def decode_attention_ref(q, k, v, length):
    """q: (B, KV, G, hd); k/v: (B, KV, T, hd); attend to positions < length."""
    hd = q.shape[-1]
    t = k.shape[2]
    scores = jnp.einsum("bngh,bnth->bngt", q, k).astype(jnp.float32) * hd**-0.5
    mask = (jnp.arange(t) < length)[None, None, None, :]
    scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bngt,bnth->bngh", p.astype(v.dtype), v)


def ssd_scan_ref(x, dt, A, B, C):
    """Sequential SSD recurrence (exact oracle).

    x: (b, s, h, p); dt: (b, s, h) fp32 (post-softplus); A: (h,) fp32 < 0;
    B/C: (b, s, n).  h_t = exp(dt A) h_{t-1} + dt B x;  y = C·h.
    """
    b, s, nh, p = x.shape
    n = B.shape[-1]

    def step(S, xs):
        xt, dtt, Bt, Ct = xs  # (b,h,p), (b,h), (b,n), (b,n)
        decay = jnp.exp(dtt * A[None, :])
        S = S * decay[..., None, None] + jnp.einsum("bhp,bn,bh->bhpn", xt, Bt, dtt)
        y = jnp.einsum("bhpn,bn->bhp", S, Ct)
        return S, y

    S0 = jnp.zeros((b, nh, p, n), jnp.float32)
    xs = (
        x.transpose(1, 0, 2, 3).astype(jnp.float32),
        dt.transpose(1, 0, 2),
        B.transpose(1, 0, 2).astype(jnp.float32),
        C.transpose(1, 0, 2).astype(jnp.float32),
    )
    S, ys = jax.lax.scan(step, S0, xs)
    return ys.transpose(1, 0, 2, 3), S


def mlstm_chunk_ref(q, k, v, log_i, log_f):
    """Sequential stabilized mLSTM recurrence (exact oracle).

    q/k/v: (b, s, h, d); log_i/log_f: (b, s, h) fp32.
    """
    b, s, nh, d = q.shape
    scale = d**-0.5

    def step(carry, xs):
        Cm, n, m = carry
        qt, kt, vt, li, lf = xs
        m_new = jnp.maximum(lf + m, li)
        i_p = jnp.exp(li - m_new)
        f_p = jnp.exp(lf + m - m_new)
        Cm = f_p[..., None, None] * Cm + i_p[..., None, None] * (kt[..., :, None] * vt[..., None, :])
        n = f_p[..., None] * n + i_p[..., None] * kt
        num = jnp.einsum("bhk,bhkv->bhv", qt, Cm) * scale
        den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", qt, n)) * scale, jnp.exp(-m_new))
        return (Cm, n, m_new), num / den[..., None]

    C0 = jnp.zeros((b, nh, d, d), jnp.float32)
    n0 = jnp.zeros((b, nh, d), jnp.float32)
    m0 = jnp.full((b, nh), -jnp.inf, jnp.float32)
    xs = tuple(a.transpose(1, 0, 2, 3).astype(jnp.float32) for a in (q, k, v)) + (
        log_i.transpose(1, 0, 2),
        log_f.transpose(1, 0, 2),
    )
    _, ys = jax.lax.scan(step, (C0, n0, m0), xs)
    return ys.transpose(1, 0, 2, 3)
