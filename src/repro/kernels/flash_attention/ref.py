"""Pure-jnp oracles for the flash-attention kernels: the plain softmax
attention (materializes scores) and the chunked online-softmax form that
never does (the model's compiled path off the TPU)."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def reference_attention(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None):
    """q [B,Sq,H,D], k/v [B,Sk,Kh,D] -> [B,Sq,H,D] (q.dtype), f32 math."""
    B, Sq, H, D = q.shape
    _, Sk, Kh, _ = k.shape
    rep = H // Kh
    kr = jnp.repeat(k, rep, axis=2).astype(jnp.float32)
    vr = jnp.repeat(v, rep, axis=2).astype(jnp.float32)
    qf = q.astype(jnp.float32) / math.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kr)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qi = jnp.arange(Sq)[:, None]
    ki = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vr)
    return o.astype(q.dtype)


def chunked_attention(q, k, v, *, causal: bool, window: Optional[int],
                      softcap: Optional[float], chunk: int):
    """Online-softmax over KV chunks; O(Sq·chunk) live memory.

    Equivalent to reference_attention (tests assert allclose); this is the
    compilable stand-in for the flash Pallas kernel.
    """
    B, Sq, H, D = q.shape
    Sk, Kh = k.shape[1], k.shape[2]
    rep = H // Kh
    chunk = min(chunk, Sk)
    n_chunks = (Sk + chunk - 1) // chunk
    pad = n_chunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, n_chunks, chunk, Kh, D)
    vc = v.reshape(B, n_chunks, chunk, Kh, D)

    qf = q.astype(jnp.float32) / math.sqrt(D)

    def body(carry, xs):
        m_prev, l_prev, acc = carry          # [B,H,Sq], [B,H,Sq], [B,Sq,H,D]
        ci, kci, vci = xs                    # kci/vci [B,chunk,Kh,D]
        kr = jnp.repeat(kci, rep, axis=2)
        vr = jnp.repeat(vci, rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kr.astype(jnp.float32))
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        # ADDITIVE 2-D mask [Sq, chunk]: a boolean `where` broadcast to
        # [B,H,Sq,chunk] gets hoisted out of the scan by XLA and
        # materialized for every chunk (512 MB-scale buffers per layer);
        # the additive form stays 2-D and fuses into the einsum epilogue.
        kidx = ci * chunk + jnp.arange(chunk)
        qidx = jnp.arange(Sq)
        neg = jnp.where(kidx[None, :] < Sk, 0.0, NEG_INF)       # pad
        if causal:
            neg = neg + jnp.where(kidx[None, :] <= qidx[:, None], 0.0,
                                  NEG_INF)
        if window is not None:
            neg = neg + jnp.where(kidx[None, :] > (qidx[:, None] - window),
                                  0.0, NEG_INF)
        neg = jnp.maximum(neg, NEG_INF)      # avoid -inf arithmetic
        s = s + neg[None, None]
        m_cur = jnp.max(s, axis=-1)                     # [B,H,Sq]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                 # rescale old
        p = jnp.exp(s - m_new[..., None])               # [B,H,Sq,K]
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + pv
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, Sq, H, D), jnp.float32)
    (m_f, l_f, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.arange(n_chunks), kc.transpose(1, 0, 2, 3, 4),
         vc.transpose(1, 0, 2, 3, 4)),
    )
    l_f = jnp.maximum(l_f, 1e-30)
    out = acc / l_f.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)
