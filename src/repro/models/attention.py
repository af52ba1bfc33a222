"""GQA attention: reference, chunked (memory-efficient), flash, and decode.

Three selectable implementations (the ``attention_impl`` knob — a C3
module-selector in SAPPHIRE's space):

* ``reference`` — plain einsum softmax attention; materializes the [S, S]
  score matrix.  The pure-jnp oracle for everything else.
* ``chunked``   — online-softmax over KV chunks via ``lax.scan``; never
  materializes [S, S].  Same memory asymptotics as flash attention and
  compilable on any backend — this is what the dry-run lowers when the
  flash kernel is selected (the Pallas kernel itself targets TPU and is
  validated in interpret mode; see kernels/flash_attention.py).
* ``flash``     — Pallas TPU kernel (kernels/flash_attention.py) with
  BlockSpec VMEM tiling; block sizes are tuned knobs.

Decode attends a 1-token query against a KV cache (layout knob bshd/bhsd,
dtype knob bf16/int8-sim).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.ref import chunked_attention
from repro.models import common
from repro.models.common import (dense_apply, dense_axes, dense_init,
                                 norm_apply, norm_init)
from repro.models.config import ModelConfig
from repro.models.rotary import apply_rope
from repro.runconfig import RunConfig

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init(rng, cfg: ModelConfig, dtype=jnp.bfloat16):
    kq, kk, kv, ko = jax.random.split(rng, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        "q": dense_init(kq, d, qd, bias=cfg.qkv_bias, dtype=dtype),
        "k": dense_init(kk, d, kvd, bias=cfg.qkv_bias, dtype=dtype),
        "v": dense_init(kv, d, kvd, bias=cfg.qkv_bias, dtype=dtype),
        "o": dense_init(ko, qd, d, dtype=dtype, scale=1.0 / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_init(cfg.resolved_head_dim, "rmsnorm", dtype)
        p["k_norm"] = norm_init(cfg.resolved_head_dim, "rmsnorm", dtype)
    return p


def axes(cfg: ModelConfig):
    b = cfg.qkv_bias
    ax = {
        "q": dense_axes("qkv_in", "heads", bias=b),
        "k": dense_axes("qkv_in", "kv_heads", bias=b),
        "v": dense_axes("qkv_in", "kv_heads", bias=b),
        "o": dense_axes("heads", "o_out"),
    }
    if cfg.qk_norm:
        ax["q_norm"] = {"scale": ("head_dim",)}
        ax["k_norm"] = {"scale": ("head_dim",)}
    return ax


def _qk_norm(params, t, which: str, cfg: ModelConfig):
    """RMSNorm of q or k [..., heads, head_dim] over each head, where the
    configuration asks for it (``qk_norm``)."""
    if not cfg.qk_norm:
        return t
    return norm_apply(params[which], t, kind="rmsnorm", eps=cfg.norm_eps)


# ---------------------------------------------------------------------------
# core softmax attention paths
# ---------------------------------------------------------------------------

def _causal_mask(sq: int, sk: int, offset: int, window: Optional[int]):
    """[sq, sk] boolean mask.  offset = absolute position of query row 0
    minus that of key column 0 (0 for self-attention over same range)."""
    qi = jnp.arange(sq)[:, None] + offset
    ki = jnp.arange(sk)[None, :]
    m = ki <= qi
    if window is not None:
        m &= ki > (qi - window)
    return m


def reference_attention(q, k, v, *, causal: bool, window: Optional[int],
                        softcap: Optional[float], offset: int = 0):
    """q [B,Sq,H,D], k/v [B,Sk,Kh,D] -> [B,Sq,H,D].  Materializes scores."""
    B, Sq, H, D = q.shape
    Kh = k.shape[2]
    rep = H // Kh
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(D)
    scores = common.softcap(scores, softcap)
    if causal or window is not None:
        # All assigned archs use causal (optionally windowed) masks; a window
        # without causal still masks causally (sliding windows are causal).
        m = _causal_mask(Sq, k.shape[1], offset, window)
        scores = jnp.where(m[None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vr.dtype), vr,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def flash_attention_dispatch(q, k, v, *, causal, window, softcap, rc: RunConfig):
    """Route to the Pallas kernel on TPU (differentiable: its backward is
    the Pallas dK/dV and dQ kernels); chunked equivalent elsewhere."""
    backend = jax.default_backend()
    if backend == "tpu":
        from repro.kernels.flash_attention import ops as flash_ops
        return flash_ops.flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            block_q=rc.flash_block_q, block_k=rc.flash_block_k)
    # CPU/GPU dry-run: same memory asymptotics via the chunked path.
    return chunked_attention(q, k, v, causal=causal, window=window,
                             softcap=softcap, chunk=rc.flash_block_k)


# ---------------------------------------------------------------------------
# layer-level apply (projections + rope + attention + output proj)
# ---------------------------------------------------------------------------

def apply(params, x, positions, cfg: ModelConfig, rc: RunConfig, *,
          causal: bool = True, window: Optional[int] = None,
          kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
          use_rope: bool = True):
    """Full-sequence attention (train / prefill).

    x [B, S, d_model]; positions [B, S] (or [3,B,S] for M-RoPE).
    kv_override: (k, v) already-projected tensors for cross-attention.
    """
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    prec = jax.lax.Precision(rc.matmul_precision) \
        if rc.matmul_precision != "default" else None

    # q/k/v have no fwd AR (contraction is replicated) but their BWD
    # dgrad contracts the TP-sharded head dim -> partial sums; the bf16
    # reduce path halves those too
    red = common.reduce_dtype(rc)
    q = dense_apply(params["q"], x, precision=prec,
                    preferred=red).reshape(B, S, cfg.n_heads, hd)
    q = _qk_norm(params, q, "q_norm", cfg)
    if kv_override is None:
        k = dense_apply(params["k"], x, precision=prec,
                        preferred=red).reshape(B, S, cfg.n_kv_heads, hd)
        k = _qk_norm(params, k, "k_norm", cfg)
        v = dense_apply(params["v"], x, precision=prec,
                        preferred=red).reshape(B, S, cfg.n_kv_heads, hd)
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
            k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        k, v = kv_override
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)

    impl = rc.attention_impl
    if impl == "reference":
        out = reference_attention(q, k, v, causal=causal, window=window,
                                  softcap=cfg.logit_softcap)
    elif impl == "chunked":
        out = chunked_attention(q, k, v, causal=causal, window=window,
                                softcap=cfg.logit_softcap, chunk=rc.chunk_size_k)
    elif impl == "flash":
        out = flash_attention_dispatch(q, k, v, causal=causal, window=window,
                                       softcap=cfg.logit_softcap, rc=rc)
    else:
        raise ValueError(f"unknown attention_impl {impl!r}")

    out = out.reshape(B, S, cfg.q_dim)
    # o-proj contracts the TP-sharded heads dim -> partial sums cross
    # shards; rc.tp_reduce_dtype picks the reduction dtype
    return dense_apply(params["o"], out, precision=prec,
                       preferred=common.reduce_dtype(rc))


def project_kv(params, x, positions, cfg: ModelConfig, *, use_rope: bool = True):
    """Project (and rotate) K/V for cache fill / cross-attention memory."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    k = dense_apply(params["k"], x).reshape(B, S, cfg.n_kv_heads, hd)
    k = _qk_norm(params, k, "k_norm", cfg)
    v = dense_apply(params["v"], x).reshape(B, S, cfg.n_kv_heads, hd)
    if use_rope:
        k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    return k, v


# ---------------------------------------------------------------------------
# decode step with KV cache
# ---------------------------------------------------------------------------

def decode_apply(params, x, cache_k, cache_v, pos, cfg: ModelConfig,
                 rc: RunConfig, *, window: Optional[int] = None,
                 cross: bool = False, cross_len: Optional[int] = None,
                 use_rope: bool = True):
    """One-token decode.

    x        [B, 1, d_model]
    cache_k/v: layout per rc.kv_layout —
               bshd: [B, S_max, Kh, D]; bhsd: [B, Kh, S_max, D]
    pos      int32 scalar OR [B] vector — tokens already in each cache
             row.  The vector form is what continuous batching needs:
             every slot decodes at its own position (serve/engine.py).
    cross    : cross-attention (cache holds encoder memory; no update).
    Returns (out [B,1,d_model], new_cache_k, new_cache_v).
    """
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    pos = jnp.asarray(pos, jnp.int32)
    pos_vec = jnp.broadcast_to(pos.reshape(-1), (B,)) if pos.ndim <= 1 \
        else pos
    q = dense_apply(params["q"], x).reshape(B, 1, cfg.n_heads, hd)
    q = _qk_norm(params, q, "q_norm", cfg)
    positions = pos_vec[:, None]
    if not cross:
        if use_rope:
            q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k_new = dense_apply(params["k"], x).reshape(B, 1, cfg.n_kv_heads, hd)
        k_new = _qk_norm(params, k_new, "k_norm", cfg)
        v_new = dense_apply(params["v"], x).reshape(B, 1, cfg.n_kv_heads, hd)
        if use_rope:
            k_new = apply_rope(k_new, positions, cfg.rope_theta, cfg.mrope_sections)
        cache_k = _cache_insert(cache_k, k_new, pos_vec, rc)
        cache_v = _cache_insert(cache_v, v_new, pos_vec, rc)
        kv_len = pos_vec + 1                              # [B]
    else:
        kv_len = jnp.broadcast_to(jnp.asarray(cross_len, jnp.int32), (B,))

    k = _cache_read(cache_k, rc)           # [B, S_max, Kh, D] bf16/f32
    v = _cache_read(cache_v, rc)
    S_max = k.shape[1]

    rep = cfg.n_heads // cfg.n_kv_heads
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    s = common.softcap(s, cfg.logit_softcap)
    kidx = jnp.arange(S_max)
    m = kidx[None, :] < kv_len[:, None]                   # [B, S_max]
    if window is not None and not cross:
        m &= kidx[None, :] > (kv_len[:, None] - 1 - window)
    s = jnp.where(m[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vr.dtype), vr,
                     preferred_element_type=jnp.float32)
    out = out.astype(x.dtype).reshape(B, 1, cfg.q_dim)
    out = dense_apply(params["o"], out, preferred=common.reduce_dtype(rc))
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# KV cache helpers (layout + dtype knobs)
# ---------------------------------------------------------------------------

def init_cache(batch: int, s_max: int, cfg: ModelConfig, rc: RunConfig):
    """One layer's (k, v) cache buffers."""
    shape_bshd = (batch, s_max, cfg.n_kv_heads, cfg.resolved_head_dim)
    if rc.kv_layout == "bhsd":
        shape = (batch, cfg.n_kv_heads, s_max, cfg.resolved_head_dim)
    else:
        shape = shape_bshd
    if rc.kv_cache_dtype == "int8":
        k = jnp.zeros(shape, jnp.int8)
        v = jnp.zeros(shape, jnp.int8)
    else:
        k = jnp.zeros(shape, common.dtype_of(rc.kv_cache_dtype))
        v = jnp.zeros(shape, common.dtype_of(rc.kv_cache_dtype))
    return k, v


def cache_axes(rc: RunConfig):
    if rc.kv_layout == "bhsd":
        ax = ("batch", "kv_heads", "kv_seq", "head_dim")
    else:
        ax = ("batch", "kv_seq", "kv_heads", "head_dim")
    return ax, ax


_INT8_SCALE = 127.0 / 8.0   # static symmetric scale for simulated int8 KV


def _quantize(x):
    return jnp.clip(jnp.round(x.astype(jnp.float32) * _INT8_SCALE),
                    -127, 127).astype(jnp.int8)


def _dequantize(x):
    return (x.astype(jnp.float32) / _INT8_SCALE).astype(jnp.bfloat16)


def _cache_insert(cache, new, pos, rc: RunConfig):
    """Insert new [B,1,Kh,D] at per-row position pos [B] (layout-aware)."""
    if cache.dtype == jnp.int8:
        new = _quantize(new)
    else:
        new = new.astype(cache.dtype)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1),
                           (cache.shape[0],))
    if rc.kv_layout == "bhsd":
        new = new.transpose(0, 2, 1, 3)    # [B,Kh,1,D]
        return jax.vmap(
            lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (0, p, 0))
        )(cache, new, pos)
    return jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (p, 0, 0))
    )(cache, new, pos)


def _cache_read(cache, rc: RunConfig):
    """Return cache as [B, S_max, Kh, D] in a compute dtype."""
    x = cache
    if rc.kv_layout == "bhsd":
        x = x.transpose(0, 2, 1, 3)
    if x.dtype == jnp.int8:
        x = _dequantize(x)
    return x


def read_cache_full(cache, rc: RunConfig):
    """Whole cache as [B, S, Kh, D] in compute dtype (cross-attn memory)."""
    return _cache_read(cache, rc)


def fill_cache(cache, kv, rc: RunConfig):
    """Bulk-fill a cache prefix with prefill K/V [B, S, Kh, D]."""
    if cache.dtype == jnp.int8:
        kv = _quantize(kv)
    else:
        kv = kv.astype(cache.dtype)
    if rc.kv_layout == "bhsd":
        kv = kv.transpose(0, 2, 1, 3)
        return jax.lax.dynamic_update_slice(cache, kv, (0, 0, 0, 0))
    return jax.lax.dynamic_update_slice(cache, kv, (0, 0, 0, 0))
