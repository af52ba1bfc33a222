"""xLSTM blocks: mLSTM (matrix memory) and sLSTM (scalar memory).

arXiv:2405.04517.  The 1.3B config interleaves 7 mLSTM : 1 sLSTM.

TPU adaptation (DESIGN.md §8): the paper's CUDA mLSTM walks time
sequentially per thread-block; here the mLSTM runs in the *stabilized
chunkwise* form — intra-chunk attention-shaped matmuls with a log-space
gate mask plus an inter-chunk carried matrix memory (C, n, m) — which is
MXU/VMEM-shaped.  ``mlstm_chunk`` is a SAPPHIRE knob.  A sequential oracle
(``mlstm_reference``) backs the tests and single-token decode.  sLSTM is
inherently sequential (hidden-state recurrence); it runs as a time scan
with block-diagonal recurrent weights (4 blocks), exactly as the paper
describes for its own kernels.

Recurrence (per head, stabilized):
    C_t = f_t C_{t-1} + i_t v_t k_t^T        (matrix memory  [P, P_k])
    n_t = f_t n_{t-1} + i_t k_t              (normalizer     [P_k])
    m_t = max(log f_t + m_{t-1}, log i_t)    (stabilizer)
    h_t = (C_t q_t) / max(|n_t . q_t|, exp(-m_t))
with f = sigmoid(f̃) (log f = -softplus(-f̃)) and i = exp(ĩ) folded into the
stabilized weights.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (dense_apply, dense_axes, dense_init,
    norm_apply, norm_init, trunc_normal)
from repro.models.config import ModelConfig
from repro.runconfig import RunConfig


def mlstm_dims(cfg: ModelConfig) -> Tuple[int, int, int]:
    di = int(cfg.mlstm_expand * cfg.d_model)
    nh = cfg.n_heads
    return di, nh, di // nh


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def mlstm_init(rng, cfg: ModelConfig, dtype=jnp.bfloat16):
    di, nh, P = mlstm_dims(cfg)
    d = cfg.d_model
    ks = jax.random.split(rng, 8)
    return {
        "up": dense_init(ks[0], d, 2 * di, dtype=dtype),          # x, z
        "q": dense_init(ks[1], di, di, dtype=dtype),
        "k": dense_init(ks[2], di, di, dtype=dtype),
        "v": dense_init(ks[3], di, di, dtype=dtype),
        "igate": dense_init(ks[4], di, nh, bias=True, dtype=jnp.float32),
        "fgate": dense_init(ks[5], di, nh, bias=True, dtype=jnp.float32),
        "out_norm": norm_init(di, "rmsnorm", dtype),
        "down": dense_init(ks[6], di, d, dtype=dtype,
                           scale=1.0 / math.sqrt(2 * cfg.n_layers)),
    }


def mlstm_axes(cfg: ModelConfig):
    return {
        "up": dense_axes("ssm_in", "ssm_inner"),
        "q": dense_axes("ssm_inner", "ssm_inner"),
        "k": dense_axes("ssm_inner", "ssm_inner"),
        "v": dense_axes("ssm_inner", "ssm_inner"),
        "igate": dense_axes("ssm_inner", None, bias=True),
        "fgate": dense_axes("ssm_inner", None, bias=True),
        "out_norm": {"scale": ("ssm_inner",)},
        "down": dense_axes("ssm_inner", "o_out"),
    }


class MlstmState(NamedTuple):
    c: jnp.ndarray    # [B, H, P, P]
    n: jnp.ndarray    # [B, H, P]
    m: jnp.ndarray    # [B, H]


def mlstm_init_state(batch: int, cfg: ModelConfig) -> MlstmState:
    _, nh, P = mlstm_dims(cfg)
    return MlstmState(
        c=jnp.zeros((batch, nh, P, P), jnp.float32),
        n=jnp.zeros((batch, nh, P), jnp.float32),
        m=jnp.full((batch, nh), -1e30, jnp.float32),
    )


def mlstm_state_axes(cfg: ModelConfig):
    return MlstmState(c=("batch", None, None, None),
                      n=("batch", None, None),
                      m=("batch", None))


def _mlstm_qkvg(params, u, cfg):
    """Project inputs.  u [B,S,d] -> q,k,v [B,S,H,P], logi/logf [B,S,H], z."""
    di, nh, P = mlstm_dims(cfg)
    B, S, _ = u.shape
    xz = dense_apply(params["up"], u)
    x, z = jnp.split(xz, 2, axis=-1)
    q = dense_apply(params["q"], x).reshape(B, S, nh, P)
    k = dense_apply(params["k"], x).reshape(B, S, nh, P) / math.sqrt(P)
    v = dense_apply(params["v"], x).reshape(B, S, nh, P)
    logi = dense_apply(params["igate"], x).astype(jnp.float32)     # ĩ
    logf = -jax.nn.softplus(-dense_apply(params["fgate"], x).astype(jnp.float32))
    return q, k, v, logi, logf, z


def mlstm_apply(params, u, cfg: ModelConfig, rc: RunConfig):
    """Chunkwise-parallel stabilized mLSTM.  u [B,S,d] -> [B,S,d].

    On TPU the chunk recurrence dispatches to the Pallas kernel
    (kernels/mlstm_chunk, differentiable through the jnp form's VJP);
    elsewhere the jnp chunked form (``ref.mlstm_chunked``) is the compiled
    path and the kernel's oracle.
    """
    B, S, _ = u.shape
    di, nh, P = mlstm_dims(cfg)
    q, k, v, logi, logf, z = _mlstm_qkvg(params, u, cfg)

    if jax.default_backend() == "tpu":
        from repro.kernels.mlstm_chunk.ops import mlstm_chunk
        h = mlstm_chunk(q, k, v, logi, logf, chunk=rc.mlstm_chunk)
    else:
        from repro.kernels.mlstm_chunk.ref import mlstm_chunked
        h = mlstm_chunked(q, k, v, logi, logf, chunk=rc.mlstm_chunk)
    h = h.reshape(B, S, di)
    h = norm_apply(params["out_norm"],
                   h.astype(u.dtype) * jax.nn.silu(z.astype(jnp.float32)).astype(u.dtype),
                   kind="rmsnorm", eps=cfg.norm_eps)
    return dense_apply(params["down"], h)


def mlstm_reference(params, u, cfg: ModelConfig):
    """Sequential stabilized recurrence (oracle)."""
    B, S, _ = u.shape
    di, nh, P = mlstm_dims(cfg)
    q, k, v, logi, logf, z = _mlstm_qkvg(params, u, cfg)
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))

    def step(state, t):
        c, n, m = state
        lf_t, li_t = logf[:, t], logi[:, t]                 # [B,H]
        m_new = jnp.maximum(lf_t + m, li_t)
        fw = jnp.exp(lf_t + m - m_new)
        iw = jnp.exp(li_t - m_new)
        c = c * fw[..., None, None] + iw[..., None, None] * jnp.einsum(
            "bhp,bhr->bhpr", kf[:, t], vf[:, t])
        n = n * fw[..., None] + iw[..., None] * kf[:, t]
        num = jnp.einsum("bhp,bhpr->bhr", qf[:, t], c)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhp,bhp->bh", n, qf[:, t])),
                          jnp.exp(-m_new))
        h = num / den[..., None]
        return (c, n, m_new), h

    st = (jnp.zeros((B, nh, P, P), jnp.float32),
          jnp.zeros((B, nh, P), jnp.float32),
          jnp.full((B, nh), -1e30, jnp.float32))
    _, hs = jax.lax.scan(step, st, jnp.arange(S))
    h = hs.transpose(1, 0, 2, 3).reshape(B, S, di)
    h = norm_apply(params["out_norm"],
                   h.astype(u.dtype) * jax.nn.silu(z.astype(jnp.float32)).astype(u.dtype),
                   kind="rmsnorm", eps=cfg.norm_eps)
    return dense_apply(params["down"], h)


def mlstm_decode_step(params, u, state: MlstmState, cfg: ModelConfig,
                      rc: RunConfig):
    """One-token mLSTM decode.  u [B,1,d]."""
    B = u.shape[0]
    di, nh, P = mlstm_dims(cfg)
    q, k, v, logi, logf, z = _mlstm_qkvg(params, u, cfg)
    qf, kf, vf = (t[:, 0].astype(jnp.float32) for t in (q, k, v))
    lf_t, li_t = logf[:, 0], logi[:, 0]
    m_new = jnp.maximum(lf_t + state.m, li_t)
    fw = jnp.exp(lf_t + state.m - m_new)
    iw = jnp.exp(li_t - m_new)
    c = state.c * fw[..., None, None] + iw[..., None, None] * jnp.einsum(
        "bhp,bhr->bhpr", kf, vf)
    n = state.n * fw[..., None] + iw[..., None] * kf
    num = jnp.einsum("bhp,bhpr->bhr", qf, c)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhp,bhp->bh", n, qf)),
                      jnp.exp(-m_new))
    h = (num / den[..., None]).reshape(B, 1, di)
    h = norm_apply(params["out_norm"],
                   h.astype(u.dtype) * jax.nn.silu(z.astype(jnp.float32)).astype(u.dtype),
                   kind="rmsnorm", eps=cfg.norm_eps)
    return dense_apply(params["down"], h), MlstmState(c=c, n=n, m=m_new)


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

N_SLSTM_BLOCKS = 4


def slstm_init(rng, cfg: ModelConfig, dtype=jnp.bfloat16):
    d = cfg.d_model
    dp = int(cfg.slstm_proj * d)
    nb = N_SLSTM_BLOCKS
    bs = d // nb
    ks = jax.random.split(rng, 6)
    return {
        # input weights for 4 gates (i, f, z, o) at once
        "w_in": trunc_normal(ks[0], (d, 4 * d), 1.0, dtype),
        # block-diagonal recurrent weights [4, nb, bs, bs]
        "w_rec": trunc_normal(ks[1], (4, nb, bs, bs), 1.0, dtype),
        "bias": jnp.zeros((4 * d,), jnp.float32),
        "out_norm": norm_init(d, "rmsnorm", dtype),
        "ffn_up": dense_init(ks[2], d, dp, dtype=dtype),
        "ffn_down": dense_init(ks[3], dp, d, dtype=dtype,
                               scale=1.0 / math.sqrt(2 * cfg.n_layers)),
    }


def slstm_axes(cfg: ModelConfig):
    return {
        "w_in": ("ssm_in", None),
        "w_rec": (None, None, None, None),
        "bias": (None,),
        "out_norm": {"scale": ("embed",)},
        "ffn_up": dense_axes("ff_in", "ff"),
        "ffn_down": dense_axes("ff", "o_out"),
    }


class SlstmState(NamedTuple):
    c: jnp.ndarray    # [B, d]
    n: jnp.ndarray    # [B, d]
    h: jnp.ndarray    # [B, d]
    m: jnp.ndarray    # [B, d]


def slstm_init_state(batch: int, cfg: ModelConfig) -> SlstmState:
    d = cfg.d_model
    z = jnp.zeros((batch, d), jnp.float32)
    return SlstmState(c=z, n=z, h=z, m=jnp.full((batch, d), -1e30, jnp.float32))


def slstm_state_axes(cfg: ModelConfig):
    return SlstmState(c=("batch", "embed"), n=("batch", "embed"),
                      h=("batch", "embed"), m=("batch", "embed"))


def _slstm_cell(params, x_t, state: SlstmState, cfg: ModelConfig):
    """One sLSTM step.  x_t [B, 4d] (pre-projected input gates)."""
    d = cfg.d_model
    nb = N_SLSTM_BLOCKS
    bs = d // nb
    B = state.h.shape[0]
    hb = state.h.reshape(B, nb, bs)
    rec = jnp.einsum("bnk,gnkl->bgnl", hb.astype(jnp.float32),
                     params["w_rec"].astype(jnp.float32)).reshape(B, 4 * d)
    g = x_t.astype(jnp.float32) + rec + params["bias"]
    gi, gf, gz, go = jnp.split(g, 4, axis=-1)
    log_f = -jax.nn.softplus(-gf)                       # log sigmoid(f)
    m_new = jnp.maximum(log_f + state.m, gi)
    i_w = jnp.exp(gi - m_new)
    f_w = jnp.exp(log_f + state.m - m_new)
    c = f_w * state.c + i_w * jnp.tanh(gz)
    n = f_w * state.n + i_w
    h = jax.nn.sigmoid(go) * c / jnp.maximum(n, 1.0)
    return SlstmState(c=c, n=n, h=h, m=m_new)


def slstm_apply(params, u, cfg: ModelConfig, rc: RunConfig):
    """Sequence sLSTM via time scan.  u [B,S,d] -> [B,S,d]."""
    B, S, d = u.shape
    x_gates = dense_apply({"w": params["w_in"]}, u)      # [B,S,4d]

    def step(state, t):
        state = _slstm_cell(params, x_gates[:, t], state, cfg)
        return state, state.h

    st0 = slstm_init_state(B, cfg)
    _, hs = jax.lax.scan(step, st0, jnp.arange(S))
    h = hs.transpose(1, 0, 2).astype(u.dtype)            # [B,S,d]
    h = norm_apply(params["out_norm"], h, kind="rmsnorm", eps=cfg.norm_eps)
    # gelu FFN (proj factor 4/3)
    y = dense_apply(params["ffn_down"],
                    jax.nn.gelu(dense_apply(params["ffn_up"], h)
                                .astype(jnp.float32)).astype(h.dtype))
    return y


def slstm_decode_step(params, u, state: SlstmState, cfg: ModelConfig,
                      rc: RunConfig):
    B = u.shape[0]
    x_gates = dense_apply({"w": params["w_in"]}, u)[:, 0]
    state = _slstm_cell(params, x_gates, state, cfg)
    h = state.h[:, None, :].astype(u.dtype)
    h = norm_apply(params["out_norm"], h, kind="rmsnorm", eps=cfg.norm_eps)
    y = dense_apply(params["ffn_down"],
                    jax.nn.gelu(dense_apply(params["ffn_up"], h)
                                .astype(jnp.float32)).astype(h.dtype))
    return y, state
