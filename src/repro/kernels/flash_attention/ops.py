"""jit'd wrapper: model-layout in/out, padding, backend dispatch.

``flash_attention(q, k, v)`` takes the model-zoo layout [B, S, H, D] /
[B, S, Kh, D], pads sequence lengths up to the block grid, flattens heads,
runs the Pallas kernel (interpret mode off-TPU) and restores the layout.
The kernel computes the forward only; its VJP is that of the chunked
online-softmax form it matches (``ref.chunked_attention``), so the train
step can differentiate through the kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import chunked_attention


def _pad_to(x, target: int, axis: int):
    pad = target - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "num_warps", "pipeline", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    num_warps: Optional[int] = None,
                    pipeline: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q [B,Sq,H,D], k/v [B,Sk,Kh,D] -> [B,Sq,H,D] (q.dtype).

    ``block_q``/``block_k``/``num_warps``/``pipeline`` are SAPPHIRE
    autotune knobs (:func:`autotune_space`); the output is
    tiling-invariant (tests/test_kernels.py guards this).  Differentiable
    (see the module docstring).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _flash(q, k, v, causal, window, softcap, block_q, block_k,
                  num_warps, pipeline, interpret)


def _forward(q, k, v, causal, window, softcap, block_q, block_k, num_warps,
             pipeline, interpret):
    B, Sq, H, D = q.shape
    _, Sk, Kh, _ = k.shape

    bq = min(block_q, max(_round_up(Sq, 8), 8))
    bk = min(block_k, max(_round_up(Sk, 8), 8))
    sq_pad = _round_up(Sq, bq)
    sk_pad = _round_up(Sk, bk)

    qf = _pad_to(q, sq_pad, 1).transpose(0, 2, 1, 3).reshape(B * H, sq_pad, D)
    kf = _pad_to(k, sk_pad, 1).transpose(0, 2, 1, 3).reshape(B * Kh, sk_pad, D)
    vf = _pad_to(v, sk_pad, 1).transpose(0, 2, 1, 3).reshape(B * Kh, sk_pad, D)

    o = flash_attention_fwd(qf, kf, vf, causal=causal, window=window,
                            softcap=softcap, block_q=bq, block_k=bk,
                            num_warps=num_warps, pipeline=pipeline,
                            sq_valid=Sq, sk_valid=Sk, interpret=interpret)
    o = o.reshape(B, H, sq_pad, D).transpose(0, 2, 1, 3)
    return o[:, :Sq]


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(3, 11)))
def _flash(q, k, v, causal, window, softcap, block_q, block_k, num_warps,
           pipeline, interpret):
    return _forward(q, k, v, causal, window, softcap, block_q, block_k,
                    num_warps, pipeline, interpret)


def _flash_fwd(q, k, v, causal, window, softcap, block_q, block_k,
               num_warps, pipeline, interpret):
    out = _forward(q, k, v, causal, window, softcap, block_q, block_k,
                   num_warps, pipeline, interpret)
    return out, (q, k, v)


def _flash_bwd(causal, window, softcap, block_q, block_k, num_warps,
               pipeline, interpret, res, g):
    """VJP of the chunked form, one query block at a time: attention rows
    are independent, so each block's VJP (keys offset by the block start)
    is exact, and the saved softmax tiles are [block_q, Sk] instead of
    [Sq, Sk].  f32 operands keep the per-block dk/dv unrounded."""
    q, k, v = res
    B, Sq, H, D = q.shape
    bq = min(block_q, Sq)
    n_qb = -(-Sq // bq)
    f32 = jnp.float32

    def blocks(x):
        x = _pad_to(x.astype(f32), n_qb * bq, 1)
        return x.reshape(B, n_qb, bq, H, D).transpose(1, 0, 2, 3, 4)

    def body(carry, xs):
        dk, dv = carry
        i, qb, gb = xs
        _, vjp = jax.vjp(
            lambda qb, kf, vf: chunked_attention(
                qb, kf, vf, causal=causal, window=window, softcap=softcap,
                chunk=block_k, offset=i * bq),
            qb, k.astype(f32), v.astype(f32))
        dq_b, dk_b, dv_b = vjp(gb)
        return (dk + dk_b, dv + dv_b), dq_b

    zeros = (jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32))
    (dk, dv), dq = jax.lax.scan(body, zeros,
                                (jnp.arange(n_qb), blocks(q), blocks(g)))
    dq = dq.transpose(1, 0, 2, 3, 4).reshape(B, n_qb * bq, H, D)[:, :Sq]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# autotune hooks (repro.kernels.autotune)
# ---------------------------------------------------------------------------

def autotune_space():
    """Tunable tiling/scheduling space of the flash forward."""
    from repro.core.space import Knob, ProductLeq, Space, pow2_knob
    return Space(
        knobs=(
            pow2_knob("block_q", 512, 16, 1024,
                      description="query tile rows"),
            pow2_knob("block_k", 512, 16, 1024,
                      description="kv tile rows"),
            pow2_knob("num_warps", 4, 1, 8, inert=True,
                      description="GPU warps per block (inert off-GPU)"),
            Knob("pipeline", "int", 2, lo=1, hi=4, inert=True,
                 description="GPU pipeline stages (inert off-GPU)"),
        ),
        # the [bq, bk] score tile's VMEM budget
        constraints=(ProductLeq(("block_q", "block_k"), limit=512 * 512),),
    )


def autotune_bench(B: int = 1, S: int = 192, H: int = 4, Kh: int = 2,
                   D: int = 64, causal: bool = True, seed: int = 0):
    """``build(cfg) -> run()`` factory for :class:`KernelEvaluator`."""
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Kh, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Kh, D), jnp.float32)

    def build(cfg):
        bq, bk = int(cfg["block_q"]), int(cfg["block_k"])
        nw = int(cfg.get("num_warps", 0)) or None
        ps = int(cfg.get("pipeline", 0)) or None

        def run():
            return flash_attention(q, k, v, causal=causal, block_q=bq,
                                   block_k=bk, num_warps=nw, pipeline=ps)
        return run
    return build
