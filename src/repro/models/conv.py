"""Gated short convolution: the mixer of LFM2's conv layers.

For h [B, S, d] (already normed):

1. ``[b, c, u] = split(h W_in, 3)``, W_in [d, 3d], no bias;
2. ``v = b ⊙ u``;
3. ``w_t = Σ_{i<W} K_i ⊙ v_{t-W+1+i}``: a depthwise causal convolution of
   width W (``conv_width``, 3 in LFM2) over the sequence, zero-padded, no
   bias;
4. ``y = c ⊙ w``, and the output is ``y W_out`` (W_out [d, d], no bias).

Decode keeps the last W − 1 rows of ``v`` as the layer's state.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.common import (dense_apply, dense_axes, dense_init,
                                 trunc_normal)
from repro.models.config import ModelConfig
from repro.runconfig import RunConfig


class ConvState(NamedTuple):
    v: jnp.ndarray        # [B, W-1, d]: the last W-1 gated inputs


def init(rng, cfg: ModelConfig, dtype=jnp.bfloat16):
    k1, k2, k3 = jax.random.split(rng, 3)
    d = cfg.d_model
    return {
        "in_proj": dense_init(k1, d, 3 * d, dtype=dtype),
        "conv_w": trunc_normal(k2, (cfg.conv_width, d), 1.0, dtype),
        "out_proj": dense_init(k3, d, d, dtype=dtype,
                               scale=1.0 / math.sqrt(2 * cfg.n_layers)),
    }


def axes(cfg: ModelConfig):
    return {
        "in_proj": dense_axes("ssm_in", "ssm_inner"),
        "conv_w": (None, "ssm_inner"),
        "out_proj": dense_axes("ssm_inner", "o_out"),
    }


def _gates(params, h):
    b, c, u = jnp.split(dense_apply(params["in_proj"], h), 3, axis=-1)
    return c, b * u


def _depthwise(v, w):
    """Causal depthwise convolution of v [B, S, d] by w [W, d], in f32."""
    W, S = w.shape[0], v.shape[1]
    vp = jnp.pad(v, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros(v.shape, jnp.float32)
    for i in range(W):   # W is tiny (3): unrolled adds
        out = out + vp[:, i: i + S].astype(jnp.float32) \
            * w[i].astype(jnp.float32)
    return out


def apply(params, h, cfg: ModelConfig, rc: RunConfig):
    """h [B, S, d] -> [B, S, d]."""
    c, v = _gates(params, h)
    y = (c.astype(jnp.float32) * _depthwise(v, params["conv_w"])) \
        .astype(h.dtype)
    return dense_apply(params["out_proj"], y)


def init_state(batch: int, cfg: ModelConfig) -> ConvState:
    return ConvState(v=jnp.zeros((batch, cfg.conv_width - 1, cfg.d_model),
                                 jnp.bfloat16))


def state_axes(cfg: ModelConfig) -> ConvState:
    return ConvState(v=("batch", None, "ssm_inner"))


def final_state(params, h, cfg: ModelConfig) -> ConvState:
    """The state after the prompt h [B, S, d]: its last W − 1 gated rows
    (zeros in front where S < W − 1)."""
    _, v = _gates(params, h)
    W = cfg.conv_width
    v = jnp.pad(v, ((0, 0), (W - 1, 0), (0, 0)))
    return ConvState(v=v[:, v.shape[1] - (W - 1):].astype(jnp.bfloat16))


def decode_step(params, h, state: ConvState, cfg: ModelConfig,
                rc: RunConfig):
    """One token: h [B, 1, d] -> ([B, 1, d], new state)."""
    c, v = _gates(params, h)
    window = jnp.concatenate([state.v.astype(v.dtype), v], axis=1)  # [B,W,d]
    w = jnp.sum(window.astype(jnp.float32)
                * params["conv_w"].astype(jnp.float32)[None], axis=1,
                keepdims=True)
    y = (c.astype(jnp.float32) * w).astype(h.dtype)
    new = ConvState(v=window[:, 1:].astype(state.v.dtype))
    return dense_apply(params["out_proj"], y), new
