"""Pure-jnp oracles: the sequential stabilized mLSTM recurrence, and the
chunkwise-parallel form the kernel computes (its VJP is the kernel's
backward, and it is the model's compiled path off the TPU)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def mlstm_sequential(q, k, v, logi, logf):
    """q/k/v [B,S,H,P], logi/logf [B,S,H] (k pre-scaled) -> h [B,S,H,P]."""
    B, S, H, P = q.shape
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    li = logi.astype(jnp.float32)
    lf = logf.astype(jnp.float32)

    def step(state, t):
        c, n, m = state                                     # [B,H,P,P] ...
        m_new = jnp.maximum(lf[:, t] + m, li[:, t])
        fw = jnp.exp(lf[:, t] + m - m_new)
        iw = jnp.exp(li[:, t] - m_new)
        c = c * fw[..., None, None] + iw[..., None, None] * jnp.einsum(
            "bhp,bhr->bhpr", kf[:, t], vf[:, t])
        n = n * fw[..., None] + iw[..., None] * kf[:, t]
        num = jnp.einsum("bhp,bhpr->bhr", qf[:, t], c)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhp,bhp->bh", n, qf[:, t])),
                          jnp.exp(-m_new))
        return (c, n, m_new), num / den[..., None]

    st = (jnp.zeros((B, H, P, P), jnp.float32),
          jnp.zeros((B, H, P), jnp.float32),
          jnp.full((B, H), -1e30, jnp.float32))
    _, hs = jax.lax.scan(step, st, jnp.arange(S))
    return hs.transpose(1, 0, 2, 3).astype(q.dtype)


def mlstm_chunked(q, k, v, logi, logf, *, chunk: int):
    """Chunkwise-parallel stabilized mLSTM in jnp: q/k/v [B,S,H,P],
    logi/logf [B,S,H] (k pre-scaled) -> h [B,S,H,P] (f32).

    Within a chunk the recurrence is attention-shaped matmuls under a
    log-space gate mask; across chunks the (C, n, m) memory is carried
    by a ``lax.scan``."""
    B, S, H, P = q.shape
    c = min(chunk, S)
    assert S % c == 0, "mlstm_chunk must divide seq len"
    n_chunks = S // c

    def csh(t, tail):      # chunk + move chunk axis first
        return t.reshape((B, n_chunks, c) + tail).transpose(
            (1, 0, 2) + tuple(range(3, 3 + len(tail))))

    qc, kc, vc = csh(q, (H, P)), csh(k, (H, P)), csh(v, (H, P))
    lic = csh(logi.astype(jnp.float32), (H,))
    lfc = csh(logf.astype(jnp.float32), (H,))

    def body(state, xs):
        c_prev, n_prev, m_prev = state
        qi, ki, vi, li, lf = xs
        qf = qi.astype(jnp.float32)
        kf = ki.astype(jnp.float32)
        vf = vi.astype(jnp.float32)
        cum = jnp.cumsum(lf, axis=1)                       # [B,c,H] inclusive
        # D[b,i,j,h] = cum_i - cum_j + li_j   for j <= i
        D = cum[:, :, None, :] - cum[:, None, :, :] + li[:, None, :, :]
        mask = jnp.tril(jnp.ones((c, c), bool))
        D = jnp.where(mask[None, :, :, None], D, -jnp.inf)
        # stabilizer: max over history (carried m enters via cum + m_prev)
        m_loc = jnp.max(D, axis=2)                          # [B,i,H]
        m_comb = jnp.maximum(m_loc, cum + m_prev[:, None, :])
        m_comb = jnp.maximum(m_comb, -1e30)                 # avoid -inf
        w = jnp.exp(D - m_comb[:, :, None, :])              # [B,i,j,H]
        qk = jnp.einsum("bihp,bjhp->bijh", qf, kf)
        s = qk * w
        h_intra = jnp.einsum("bijh,bjhp->bihp", s, vf)
        n_intra = jnp.einsum("bijh,bjhp->bihp", w, kf)
        # inter-chunk contribution
        scale_in = jnp.exp(cum + m_prev[:, None, :] - m_comb)   # [B,i,H]
        h_inter = jnp.einsum("bihp,bhpr->bihr", qf, c_prev) \
            * scale_in[..., None]
        n_inter = n_prev[:, None] * scale_in[..., None]
        h_num = h_intra + h_inter
        n_all = n_intra + n_inter
        denom = jnp.maximum(jnp.abs(jnp.einsum("bihp,bihp->bih", n_all, qf)),
                            jnp.exp(-m_comb))
        h = h_num / denom[..., None]
        # carry update
        total = cum[:, -1, :]                               # [B,H]
        m_new = jnp.maximum(total + m_prev, jnp.max(
            total[:, None, :] - cum + li, axis=1))
        wk = jnp.exp(total[:, None, :] - cum + li - m_new[:, None, :])
        c_new = c_prev * jnp.exp(total + m_prev - m_new)[..., None, None] \
            + jnp.einsum("bjhp,bjhr->bhpr", kf * wk[..., None], vf)
        n_new = n_prev * jnp.exp(total + m_prev - m_new)[..., None] \
            + jnp.einsum("bjhp,bjh->bhp", kf, wk)
        return (c_new, n_new, m_new), h

    st0 = (jnp.zeros((B, H, P, P), jnp.float32),
           jnp.zeros((B, H, P), jnp.float32),
           jnp.full((B, H), -1e30, jnp.float32))
    _, hs = jax.lax.scan(body, st0, (qc, kc, vc, lic, lfc))
    return hs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, P)
