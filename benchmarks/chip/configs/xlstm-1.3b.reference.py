"""Plain float32 reference of xlstm-1.3b as the configuration states it
(arXiv:2405.04517, xLSTM[7:1]: seven mLSTM blocks, then one sLSTM block).

Each block is pre-norm residual, x + core(RMSNorm(x)), with no separate
feed-forward; a final RMSNorm and an untied head give the mean next-token
cross-entropy.

mLSTM block (d → di = 2d, nh heads of P = di / nh): [x̃, z] = u·W_up;
q = x̃·W_q, k = x̃·W_k / √P, v = x̃·W_v; per head the input and forget
pre-activations ĩ = x̃·W_i + b_i, f̃ = x̃·W_f + b_f, log f = −softplus(−f̃).
The stabilized matrix memory

    m_t = max(log f_t + m_{t−1}, ĩ_t)
    C_t = e^{log f_t + m_{t−1} − m_t} C_{t−1} + e^{ĩ_t − m_t} v_t k_tᵀ
    n_t = e^{log f_t + m_{t−1} − m_t} n_{t−1} + e^{ĩ_t − m_t} k_t
    h_t = C_t q_t / max(|n_tᵀ q_t|, e^{−m_t})

is evaluated chunk by chunk: within a chunk the same sums are written out
as a masked [C, C] matrix of log weights, across chunks (C, n, m) are
carried; the result is the recurrence's, up to rounding.  Then
y = RMSNorm(h ⊙ silu(z))·W_down.

sLSTM block: gate pre-activations x·W_in (i, f, z, o; 4d) plus a
block-diagonal recurrence on h_{t−1} (4 blocks of d/4 per gate) plus a
bias; m_t = max(log f_t + m_{t−1}, ĩ_t), c_t = f′ c_{t−1} + i′ tanh(z̃),
n_t = f′ n_{t−1} + i′, h_t = σ(õ) c_t / max(n_t, 1), a sequential scan
over the sequence; then RMSNorm and a GeLU feed-forward (d → 4d/3 → d).

Departures from the paper that the configuration states (``assumed`` in
its file): dense q/k/v, no causal convolution or learnable skip in the
mLSTM block, max(n, 1) in the sLSTM normalizer.  It reads the weights
from the parameter tree's keys and imports nothing of the system under
test.
"""

from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "bench_reference", Path(__file__).resolve().parents[1] / "reference.py")
R = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(R)

CHUNK = 256


def mlstm_cell(q, k, v, li, lf, mm, chunk: int = CHUNK):
    """q, k, v [B, S, H, P] (k scaled), li, lf [B, S, H] -> h [B, S, H, P]."""
    b, s, h, p = q.shape
    c = min(chunk, s)
    nc = s // c

    def chunks(t):
        return t.reshape((b, nc, c) + t.shape[2:]).swapaxes(0, 1)

    lower = jnp.tril(jnp.ones((c, c), bool))

    @jax.checkpoint
    def body(carry, xs):
        cm, nm, m_prev = carry                  # [B,H,P,P], [B,H,P], [B,H]
        qi, ki, vi, lii, lfi = xs
        fcum = jnp.cumsum(lfi, axis=1)          # [B, c, H]
        # log weight of key j at query i: F_i − F_j + ĩ_j (j ≤ i)
        dm = fcum[:, :, None, :] - fcum[:, None, :, :] + lii[:, None, :, :]
        dm = jnp.where(lower[None, :, :, None], dm, -jnp.inf)
        carried = fcum + m_prev[:, None, :]     # log weight of (C, n)
        m = jnp.maximum(jnp.max(dm, axis=2), carried)
        w = jnp.exp(dm - m[:, :, None, :])      # [B, i, j, H]
        qk = mm.einsum("bihp,bjhp->bijh", qi, ki)
        num = mm.einsum("bijh,bjhp->bihp", qk * w, vi)
        den = mm.einsum("bijh,bjhp->bihp", w, ki)
        scale = jnp.exp(carried - m)[..., None]
        num = num + mm.einsum("bihp,bhpr->bihr", qi, cm) * scale
        den = den + nm[:, None] * scale
        dq = jnp.abs(jnp.sum(den * qi, axis=-1))
        hid = num / jnp.maximum(dq, jnp.exp(-m))[..., None]
        # carry to the chunk's end: weights of every key at the last row
        total = fcum[:, -1, :]
        m_new = jnp.maximum(total + m_prev,
                            jnp.max(total[:, None, :] - fcum + lii, axis=1))
        wk = jnp.exp(total[:, None, :] - fcum + lii - m_new[:, None, :])
        decay = jnp.exp(total + m_prev - m_new)
        cm = cm * decay[..., None, None] + mm.einsum(
            "bjhp,bjhr->bhpr", ki * wk[..., None], vi)
        nm = nm * decay[..., None] + jnp.sum(ki * wk[..., None], axis=1)
        return (cm, nm, m_new), hid

    carry = (jnp.zeros((b, h, p, p), jnp.float32),
             jnp.zeros((b, h, p), jnp.float32),
             jnp.full((b, h), -1e30, jnp.float32))
    _, hs = jax.lax.scan(body, carry, tuple(
        chunks(t) for t in (q, k, v, li, lf)))
    return hs.swapaxes(0, 1).reshape(b, s, h, p)


def mlstm_block(u, p, model, mm):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    b, s, d = u.shape
    di = int(model["mlstm_expand"] * d)
    nh = model["n_heads"]
    hp = di // nh
    xz = mm(u, f32(p["up"]["w"]))
    x, z = xz[..., :di], xz[..., di:]
    q = mm(x, f32(p["q"]["w"])).reshape(b, s, nh, hp)
    k = mm(x, f32(p["k"]["w"])).reshape(b, s, nh, hp) / math.sqrt(hp)
    v = mm(x, f32(p["v"]["w"])).reshape(b, s, nh, hp)
    li = mm(x, f32(p["igate"]["w"])) + f32(p["igate"]["b"])
    lf = -jax.nn.softplus(-(mm(x, f32(p["fgate"]["w"]))
                            + f32(p["fgate"]["b"])))
    hid = mlstm_cell(q, k, v, li, lf, mm).reshape(b, s, di)
    hid = R.rmsnorm(hid * jax.nn.silu(z), f32(p["out_norm"]["scale"]),
                    model["norm_eps"])
    return mm(hid, f32(p["down"]["w"]))


def slstm_block(u, p, model, mm):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    b, s, d = u.shape
    nb = model["slstm_blocks"]
    bs = d // nb
    w_rec = f32(p["w_rec"])                     # [4, nb, bs, bs]
    xg = mm(u, f32(p["w_in"])) + f32(p["bias"])  # [B, S, 4d]

    def step(carry, x_t):
        c, n, hid, m = carry
        rec = mm.einsum("bnk,gnkl->bgnl", hid.reshape(b, nb, bs),
                        w_rec).reshape(b, 4 * d)
        g = x_t + rec
        gi, gf, gz, go = jnp.split(g, 4, axis=-1)
        log_f = -jax.nn.softplus(-gf)
        m_new = jnp.maximum(log_f + m, gi)
        i_w = jnp.exp(gi - m_new)
        f_w = jnp.exp(log_f + m - m_new)
        c = f_w * c + i_w * jnp.tanh(gz)
        n = f_w * n + i_w
        hid = jax.nn.sigmoid(go) * c / jnp.maximum(n, 1.0)
        return (c, n, hid, m_new), hid

    zero = jnp.zeros((b, d), jnp.float32)
    carry = (zero, zero, zero, jnp.full((b, d), -1e30, jnp.float32))
    _, hs = jax.lax.scan(step, carry, xg.swapaxes(0, 1))
    hid = R.rmsnorm(hs.swapaxes(0, 1), f32(p["out_norm"]["scale"]),
                    model["norm_eps"])
    y = jax.nn.gelu(mm(hid, f32(p["ffn_up"]["w"])))
    return mm(y, f32(p["ffn_down"]["w"]))


def loss(params, tokens, labels, model: dict, mm, mask=None):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    eps = model["norm_eps"]
    pattern = model["pattern"]
    groups = model["n_layers"] // len(pattern)
    x = f32(params["embed"]["tok"])[tokens]
    core = {"mlstm": mlstm_block, "slstm": slstm_block}
    for g in range(groups):
        for i, kind in enumerate(pattern):
            p = jax.tree.map(lambda t: t[g], params["layers"][i])

            @jax.checkpoint
            def block(x, p, kind=kind):
                y = R.rmsnorm(x, f32(p["norm1"]["scale"]), eps)
                return x + core[kind](y, p[kind], model, mm)

            x = block(x, p)
    x = R.rmsnorm(x, f32(params["final_norm"]["scale"]), eps)
    return R.cross_entropy(x, f32(params["head"]["w"]), labels, mm, mask)
