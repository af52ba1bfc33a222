"""Pallas TPU flash attention (causal / windowed / soft-capped GQA):
the forward and its two backward kernels.

TPU-native design (not a CUDA port):

* the reduction loop is the **last grid dimension** — on TPU the grid is
  executed sequentially per core, so running state (the online-softmax
  m, l, acc of the forward; the dK/dV and dQ accumulators of the
  backward) lives in VMEM scratch and survives across iterations; there
  is no cross-block shared-memory protocol like on GPU;
* BlockSpecs tile q/k/v/o into VMEM; block sizes are SAPPHIRE knobs
  (``flash_block_q``/``flash_block_k``, C2-aligned to multiples of 128 so
  the [bq, bk] score tile is MXU-shaped);
* fully-masked tiles (strictly above the causal diagonal, or outside
  the sliding window) are *skipped* with ``pl.when`` under one predicate
  (:func:`_never_visible`) — for causal attention this halves the
  executed MACs, matching the cost model's 0.5 causal factor; the
  backward's index maps also clamp a skipped step onto a visible block,
  so the pipeline re-uses the block it holds and starts no DMA for it;
* GQA is resolved in the index maps: query head h reads KV head
  ``h // (H // Kh)`` — no materialized ``jnp.repeat`` of K/V (the
  reference path pays that HBM cost; the kernel does not).

The backward follows FlashAttention-2: the forward can also write the
f32 log-sum-exp of each query row, ``P = exp(s − lse)`` is recomputed
tile by tile, and two kernels accumulate the gradients —
:func:`flash_attention_bwd` runs a dK/dV kernel (one KV block against
every query block of every query head of its group, so dK/dV of a KV head
never leave VMEM until they are whole) and a dQ kernel (one query block
against every KV block).  Operands enter the MXU in the input dtype
(bf16 products are exact in the f32 accumulators); the scale is applied
after the product; softmax statistics, ``exp``, dS and the accumulators
are f32.

Validated in interpret mode against ``ref.reference_attention`` and its
gradient over a shape/dtype/window/softcap sweep (tests/test_kernels.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tuning_compiler_params

NEG_INF = -1e30
LANES = 128          # TPU lane width: scratch running stats use a full lane
_NT = (((1,), (1,)), ((), ()))   # a @ bᵀ
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _never_visible(q_start, k_start, *, causal: bool,
                   window: Optional[int], block_q: int, block_k: int):
    """Whether no (query, key) pair of the tile at these block starts is
    visible: the keys lie strictly above the causal diagonal or wholly
    outside the window.  A traced predicate on the block indices."""
    out = jnp.logical_and(jnp.asarray(causal), k_start > q_start + block_q - 1)
    if window is not None:
        out = jnp.logical_or(out, k_start + block_k - 1 <= q_start - window)
    return out


def _visible(qi, ki, *, causal: bool, window: Optional[int],
             sq_valid: int, sk_valid: int):
    """Elementwise mask of the visible pairs (query rows ``qi``, key rows
    ``ki``, absolute positions), padding included."""
    mask = jnp.logical_and(qi < sq_valid, ki < sk_valid)
    if causal:
        mask = jnp.logical_and(mask, ki <= qi)
    if window is not None:
        mask = jnp.logical_and(mask, ki > qi - window)
    return mask


def _kv_span(i, *, causal: bool, window: Optional[int], block_q: int,
             block_k: int, n_kb: int):
    """First and last KV block that query block ``i`` can see."""
    q_start = i * block_q
    hi = jnp.minimum((q_start + block_q - 1) // block_k, n_kb - 1) \
        if causal else n_kb - 1
    lo = 0 if window is None else \
        jnp.maximum((q_start - window + 1) // block_k, 0)
    return lo, hi


def _q_span(j, *, causal: bool, window: Optional[int], block_q: int,
            block_k: int, n_qb: int):
    """First and last query block that can see KV block ``j``."""
    k_start = j * block_k
    lo = k_start // block_q if causal else 0
    hi = n_qb - 1 if window is None else \
        jnp.minimum((k_start + block_k + window - 2) // block_q, n_qb - 1)
    return lo, hi


def _clamp(x, lo, hi):
    return jnp.minimum(jnp.maximum(x, lo), hi)


def _kernel(q_ref, k_ref, v_ref, *refs, scale: float, causal: bool,
            window: Optional[int], softcap: Optional[float], sq_valid: int,
            sk_valid: int, block_q: int, block_k: int, n_kb: int,
            with_lse: bool):
    if with_lse:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
    i = pl.program_id(1)          # q block index
    j = pl.program_id(2)          # kv block index (sequential innermost)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i * block_q
    k_start = j * block_k

    # Static-shape masks are built from iota; whether the block can be
    # skipped entirely is a *traced* predicate on (i, j).
    never_visible = _never_visible(q_start, k_start, causal=causal,
                                   window=window, block_q=block_q,
                                   block_k=block_k)

    @pl.when(jnp.logical_not(never_visible))
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # [bq, d]
        k = k_ref[0].astype(jnp.float32)                    # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        ki = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = _visible(qi, ki, causal=causal, window=window,
                        sq_valid=sq_valid, sk_valid=sk_valid)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                               # [bq, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)           # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                     # rescale old state
        p = jnp.exp(s - m_new)                              # [bq, bk]
        p = jnp.where(mask, p, 0.0)                         # kill -inf rows
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, _NN, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_kb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if with_lse:
            # one f32 per query row, stored as a row of the [1, bq] block
            lse_ref[0] = (m_ref[:, :1] + jnp.log(l))[:, 0]


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        block_q: int = 512, block_k: int = 512,
                        num_warps: Optional[int] = None,
                        pipeline: Optional[int] = None,
                        sq_valid: Optional[int] = None,
                        sk_valid: Optional[int] = None,
                        with_lse: bool = False,
                        interpret: bool = False):
    """q [BH, Sq, D]; k/v [BKh, Sk, D]; Sq % block_q == Sk % block_k == 0.

    BH = B·H, BKh = B·Kh with H % Kh == 0; returns [BH, Sq, D] in q.dtype
    and, with ``with_lse``, also the f32 log-sum-exp of each query row's
    scores, [BH, 1, Sq] (the residual :func:`flash_attention_bwd` reads).
    ``sq_valid``/``sk_valid`` mark the unpadded lengths.
    """
    BH, Sq, D = q.shape
    BKh, Sk, _ = k.shape
    assert BH % BKh == 0, "GQA: q heads must be a multiple of kv heads"
    assert Sq % block_q == 0 and Sk % block_k == 0
    rep_total = BH // BKh
    n_qb, n_kb = Sq // block_q, Sk // block_k
    sq_valid = Sq if sq_valid is None else sq_valid
    sk_valid = Sk if sk_valid is None else sk_valid

    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(D), causal=causal, window=window,
        softcap=softcap, sq_valid=sq_valid, sk_valid=sk_valid,
        block_q=block_q, block_k=block_k, n_kb=n_kb, with_lse=with_lse)

    # GQA in the index map: flat q index b -> flat kv index.  BH is laid
    # out [B, H] and BKh as [B, Kh]; with rep = H // Kh this is
    # (b // H) * Kh + (b % H) // rep == b // rep_total ... only when Kh
    # divides contiguously — we flatten as [B*Kh, rep] on the wrapper side
    # so the map is simply b // rep_total.
    kv_map = lambda b, i, j: (b // rep_total, j, 0)       # noqa: E731

    o_spec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    o_shape = jax.ShapeDtypeStruct((BH, Sq, D), q.dtype)
    if with_lse:
        o_spec = [o_spec,
                  pl.BlockSpec((None, 1, block_q), lambda b, i, j: (b, 0, i))]
        o_shape = [o_shape, jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32)]
    extra = {}
    cp = tuning_compiler_params(num_warps, pipeline, interpret)
    if cp is not None:
        extra["compiler_params"] = cp
    return pl.pallas_call(
        kernel,
        grid=(BH, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
        ],
        out_specs=o_spec,
        out_shape=o_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),      # acc
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running max m
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running sum l
        ],
        interpret=interpret,
        **extra,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _capped(s, softcap: Optional[float]):
    return s if softcap is None else softcap * jnp.tanh(s / softcap)


def _dscore(p, dp, delta, t, softcap: Optional[float]):
    """dL/ds of one tile: the softmax's ``P∘(dP − δ)`` and, under a soft
    cap ``t = c·tanh(s/c)``, its derivative ``1 − (t/c)²``."""
    ds = p * (dp - delta)
    if softcap is not None:
        ds = ds * (1.0 - (t / softcap) ** 2)
    return ds


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                 causal: bool, window: Optional[int],
                 softcap: Optional[float], sq_valid: int, sk_valid: int,
                 block_q: int, block_k: int, n_qb: int, n_steps: int):
    j = pl.program_id(1)          # kv block
    t = pl.program_id(2)          # (query head of the group, q block)
    i = t % n_qb

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = i * block_q
    k_start = j * block_k
    never_visible = _never_visible(q_start, k_start, causal=causal,
                                   window=window, block_q=block_q,
                                   block_k=block_k)

    @pl.when(jnp.logical_not(never_visible))
    def _compute():
        # Key rows by query columns: the query rows' statistics arrive as
        # [1, bq] rows and broadcast down the tile with no transpose.
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = _capped(_dot(k, q, _NT) * scale, softcap)       # [bk, bq]
        ki = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = _visible(qi, ki, causal=causal, window=window,
                        sq_valid=sq_valid, sk_valid=sk_valid)
        p = jnp.where(mask, jnp.exp(s - lse_ref[...]), 0.0)
        dv_acc[...] += _dot(p.astype(do.dtype), do, _NN)
        ds = _dscore(p, _dot(v, do, _NT), delta_ref[...], s, softcap)
        dk_acc[...] += _dot(ds.astype(q.dtype), q, _NN)

    @pl.when(t == n_steps - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale: float, causal: bool,
               window: Optional[int], softcap: Optional[float],
               sq_valid: int, sk_valid: int, block_q: int, block_k: int,
               n_kb: int):
    i = pl.program_id(1)          # q block
    j = pl.program_id(2)          # kv block (sequential innermost)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = i * block_q
    k_start = j * block_k
    never_visible = _never_visible(q_start, k_start, causal=causal,
                                   window=window, block_q=block_q,
                                   block_k=block_k)

    @pl.when(jnp.logical_not(never_visible))
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = _capped(_dot(q, k, _NT) * scale, softcap)       # [bq, bk]
        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        ki = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = _visible(qi, ki, causal=causal, window=window,
                        sq_valid=sq_valid, sk_valid=sk_valid)
        lse = jnp.expand_dims(lse_ref[0], -1)               # [bq, 1]
        delta = jnp.expand_dims(delta_ref[0], -1)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        ds = _dscore(p, _dot(do, v, _NT), delta, s, softcap)
        dq_acc[...] += _dot(ds.astype(k.dtype), k, _NN)

    @pl.when(j == n_kb - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, do, lse, delta, *, causal: bool = True,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        block_q: int = 512, block_k: int = 512,
                        num_warps: Optional[int] = None,
                        pipeline: Optional[int] = None,
                        sq_valid: Optional[int] = None,
                        sk_valid: Optional[int] = None,
                        interpret: bool = False):
    """Gradients of :func:`flash_attention_fwd` in its layout.

    q/do [BH, Sq, D]; k/v [BKh, Sk, D]; ``lse`` the forward's log-sum-exp
    and ``delta`` = rowsum(dO∘O), both f32 [BH, 1, Sq].  Returns dq, dk, dv
    in the dtypes of q, k, v.
    """
    BH, Sq, D = q.shape
    BKh, Sk, _ = k.shape
    assert BH % BKh == 0 and Sq % block_q == 0 and Sk % block_k == 0
    rep = BH // BKh
    n_qb, n_kb = Sq // block_q, Sk // block_k
    sq_valid = Sq if sq_valid is None else sq_valid
    sk_valid = Sk if sk_valid is None else sk_valid
    common = dict(scale=1.0 / math.sqrt(D), causal=causal, window=window,
                  softcap=softcap, sq_valid=sq_valid, sk_valid=sk_valid,
                  block_q=block_q, block_k=block_k)
    spans = dict(causal=causal, window=window, block_q=block_q,
                 block_k=block_k)
    extra = {}
    cp = tuning_compiler_params(num_warps, pipeline, interpret)
    if cp is not None:
        extra["compiler_params"] = cp

    # dK/dV: grid (kv head, kv block, (query head of the group, q block));
    # a skipped step's q block is clamped into the visible span.
    def q_block(j, t):
        lo, hi = _q_span(j, n_qb=n_qb, **spans)
        return _clamp(t % n_qb, lo, hi)

    q_map = lambda b, j, t: (b * rep + t // n_qb, q_block(j, t), 0)  # noqa: E731
    row_map = lambda b, j, t: (b * rep + t // n_qb, 0, q_block(j, t))  # noqa: E731
    own_kv = lambda b, j, t: (b, j, 0)                      # noqa: E731
    n_steps = rep * n_qb
    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, n_qb=n_qb, n_steps=n_steps,
                          **common),
        grid=(BKh, n_kb, n_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, block_k, D), own_kv),
            pl.BlockSpec((1, block_k, D), own_kv),
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((None, 1, block_q), row_map),
            pl.BlockSpec((None, 1, block_q), row_map),
        ],
        out_specs=[pl.BlockSpec((1, block_k, D), own_kv),
                   pl.BlockSpec((1, block_k, D), own_kv)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),   # dk
                        pltpu.VMEM((block_k, D), jnp.float32)],  # dv
        interpret=interpret,
        name="flash_attention_dkdv",
        **extra,
    )(q, k, v, do, lse, delta)

    # dQ: grid (q head, q block, kv block); a skipped step's kv block is
    # clamped into the visible span.
    def kv_block(i, j):
        lo, hi = _kv_span(i, n_kb=n_kb, **spans)
        return _clamp(j, lo, hi)

    qo_map = lambda b, i, j: (b, i, 0)                      # noqa: E731
    kv_map = lambda b, i, j: (b // rep, kv_block(i, j), 0)  # noqa: E731
    stat_map = lambda b, i, j: (b, 0, i)                    # noqa: E731
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_kb=n_kb, **common),
        grid=(BH, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, D), qo_map),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_q, D), qo_map),
            pl.BlockSpec((None, 1, block_q), stat_map),
            pl.BlockSpec((None, 1, block_q), stat_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), qo_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
        **extra,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
