"""Plain float32 reference of LFM2-8B-A1B as the configuration runs it:
one chip's share of its experts (``n_experts`` held, from
``expert_offset``, of the ``router_experts`` the router scores).

Every block is pre-norm: h = x + mixer(RMSNorm(x)), then
out = h + ffn(RMSNorm(h)).  A final RMSNorm and a head tied to the
embedding (which is not scaled) give the mean next-token cross-entropy.

* conv: [b, c, u] = split(h·W_in, 3); v = b ⊙ u;
  w_t = Σ_{i<W} K_i ⊙ v_{t−W+1+i}, a depthwise causal convolution of
  width W over the sequence, zero-padded; y = c ⊙ w; out y·W_out.
* attn: q = RMSNorm_q(h·W_q), k = RMSNorm_k(h·W_k) over each head;
  rotary embeddings (rotate-half); causal grouped-query softmax attention
  with scale 1/√head_dim; W_o.  No biases.
* dense ffn: SwiGLU.
* moe ffn: s = σ(h·W_r) over all router_experts; the selection is the
  top k of s + b (b, the expert bias, only selects: no gradient reaches
  it); each selected expert's weight is s_e / (Σ_selected s + 1e-6).  Each
  held expert (SwiGLU) runs on every token and is weighted by its weight,
  zero where the token did not select it; the experts held elsewhere
  give nothing, as on the chip.

It reads the weights from the parameter tree's keys (``embed/tok``,
``layers/<i>/{norm1, conv | attn, norm2, mlp | moe}``, ``final_norm``),
each layer leaf stacked over one group, and imports nothing of the
system under test.  ``mask`` (optional, [B, S]) restricts the mean.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "bench_reference", Path(__file__).resolve().parents[1] / "reference.py")
R = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(R)

ATTN_BLOCK = 128     # query rows per block of the attention


def _f32(a):
    return a.astype(jnp.float32)


def conv_mixer(p, h, mm):
    b, c, u = jnp.split(mm(h, _f32(p["in_proj"]["w"])), 3, axis=-1)
    v = b * u
    k = _f32(p["conv_w"])                                   # [W, d]
    width, s = k.shape[0], v.shape[1]
    vp = jnp.pad(v, ((0, 0), (width - 1, 0), (0, 0)))
    w = sum(k[i] * vp[:, i: i + s] for i in range(width))
    return mm(c * w, _f32(p["out_proj"]["w"]))


def attention(p, h, model: dict, mm):
    b, s, _ = h.shape
    nh, kh, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    eps = model["norm_eps"]
    q = mm(h, _f32(p["q"]["w"])).reshape(b, s, nh, hd)
    k = mm(h, _f32(p["k"]["w"])).reshape(b, s, kh, hd)
    v = mm(h, _f32(p["v"]["w"])).reshape(b, s, kh, hd)
    q = R.rmsnorm(q, _f32(p["q_norm"]["scale"]), eps)
    k = R.rmsnorm(k, _f32(p["k_norm"]["scale"]), eps)
    q = R.rope(q, model["rope_theta"])
    k = R.rope(k, model["rope_theta"])
    o = R.causal_attention(q, k, v, mm, block=min(ATTN_BLOCK, s))
    return mm(o.reshape(b, s, nh * hd), _f32(p["o"]["w"]))


def swiglu(gate, up, down, h, mm):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def routing(p, h, model: dict, mm):
    """[B, S, router_experts] weights: s_e / (Σ_selected s + 1e-6) where the
    token selected expert e, else 0."""
    s = jax.nn.sigmoid(mm(h, _f32(p["router"]["w"])))
    select = s + jax.lax.stop_gradient(_f32(p["router"]["bias"]))
    _, top = jax.lax.top_k(select, model["n_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(top, s.shape[-1]), axis=-2)
    w = s * chosen
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)


def moe(p, h, model: dict, mm):
    """The held experts' part of the layer's result."""
    w = routing(p, h, model, mm)
    ex = p["experts"]
    first = model["expert_offset"]
    out = jnp.zeros_like(h)
    for e in range(model["n_experts"]):
        y = jax.checkpoint(lambda g, u, dn, x: swiglu(g, u, dn, x, mm))(
            _f32(ex["gate"][e]), _f32(ex["up"][e]), _f32(ex["down"][e]), h)
        out = out + w[..., first + e, None] * y
    return out


def hidden(params, tokens, model: dict, mm):
    """Final hidden states [B, S, d] (after the final RMSNorm)."""
    eps = model["norm_eps"]
    x = _f32(params["embed"]["tok"])[tokens]
    for i, (kind, ffn) in enumerate(zip(model["pattern"], model["mlp"])):
        p = jax.tree.map(lambda t: t[0], params["layers"][i])

        @jax.checkpoint
        def block(x, p, kind=kind, ffn=ffn):
            y = R.rmsnorm(x, _f32(p["norm1"]["scale"]), eps)
            if kind == "conv":
                x = x + conv_mixer(p["conv"], y, mm)
            else:
                x = x + attention(p["attn"], y, model, mm)
            y = R.rmsnorm(x, _f32(p["norm2"]["scale"]), eps)
            if ffn == "dense":
                f = p["mlp"]
                return x + swiglu(_f32(f["gate"]["w"]), _f32(f["up"]["w"]),
                                  _f32(f["down"]["w"]), y, mm)
            return x + moe(p["moe"], y, model, mm)

        x = block(x, p)
    return R.rmsnorm(x, _f32(params["final_norm"]["scale"]), eps)


def logits(params, tokens, model: dict, mm):
    return mm(hidden(params, tokens, model, mm),
              _f32(params["embed"]["tok"]).T)


def loss(params, tokens, labels, model: dict, mm, mask=None):
    x = hidden(params, tokens, model, mm)
    return R.cross_entropy(x, _f32(params["embed"]["tok"]).T, labels, mm,
                           mask)
