"""Plain float32 reference of yi-6b (Llama architecture), arXiv:2403.04652.

Pre-norm decoder: RMSNorm; grouped-query attention (32 query heads over
4 key/value heads of 128) with rotary embeddings (rotate-half layout,
θ = 5·10⁶) and a causal softmax; SwiGLU feed-forward; final RMSNorm and
an untied vocabulary head; mean next-token cross-entropy.

It reads the weights from the parameter tree's keys (``embed/tok``,
``layers/0/{norm1,attn,norm2,mlp}``, ``final_norm``, ``head``), each
layer leaf stacked over the layer axis, and imports nothing of the
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


def loss(params, tokens, labels, model: dict, mm, mask=None):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    b, s = tokens.shape
    h, kh = model["n_heads"], model["n_kv_heads"]
    hd = model["d_model"] // h
    eps = model["norm_eps"]
    x = f32(params["embed"]["tok"])[tokens]
    lay = params["layers"][0]

    @jax.checkpoint
    def block(x, p):
        y = R.rmsnorm(x, f32(p["norm1"]["scale"]), eps)
        a = p["attn"]
        q = mm(y, f32(a["q"]["w"])).reshape(b, s, h, hd)
        k = mm(y, f32(a["k"]["w"])).reshape(b, s, kh, hd)
        v = mm(y, f32(a["v"]["w"])).reshape(b, s, kh, hd)
        q = R.rope(q, model["rope_theta"])
        k = R.rope(k, model["rope_theta"])
        o = R.causal_attention(q, k, v, mm).reshape(b, s, h * hd)
        x = x + mm(o, f32(a["o"]["w"]))
        y = R.rmsnorm(x, f32(p["norm2"]["scale"]), eps)
        f = p["mlp"]
        g = jax.nn.silu(mm(y, f32(f["gate"]["w"]))) * mm(y, f32(f["up"]["w"]))
        return x + mm(g, f32(f["down"]["w"]))

    for i in range(model["n_layers"]):
        x = block(x, jax.tree.map(lambda t: t[i], lay))
    x = R.rmsnorm(x, f32(params["final_norm"]["scale"]), eps)
    return R.cross_entropy(x, f32(params["head"]["w"]), labels, mm, mask)
