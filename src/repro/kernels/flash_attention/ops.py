"""jit'd wrapper: model-layout in/out, padding, backend dispatch.

``flash_attention(q, k, v)`` takes the model-zoo layout [B, S, H, D] /
[B, S, Kh, D], pads sequence lengths up to the block grid, flattens heads,
runs the Pallas kernels (interpret mode off-TPU) and restores the layout.
It is differentiable: a custom VJP on the flat, padded layout saves the
forward's output and per-row log-sum-exp, computes δ = rowsum(dO∘O) in
jnp, and runs the two Pallas backward kernels
(``kernel.flash_attention_bwd``), which skip the masked tiles as the
forward does.  Autodiff of the padding and layout changes around it
returns the gradients in the model layout.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import (flash_attention_bwd,
                                                  flash_attention_fwd)


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
    autotune knobs (:func:`autotune_space`); the output and its gradient
    are tiling-invariant (tests/test_kernels.py guards this).
    Differentiable (see the module docstring).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, Sq, H, D = q.shape
    _, Sk, Kh, _ = k.shape

    bq = min(block_q, max(_round_up(Sq, 8), 8))
    bk = min(block_k, max(_round_up(Sk, 8), 8))
    sq_pad = _round_up(Sq, bq)
    sk_pad = _round_up(Sk, bk)

    def flat(x, s_pad):
        x = _pad_to(x, s_pad, 1).transpose(0, 2, 1, 3)
        return x.reshape(-1, s_pad, D)

    o = _flash(flat(q, sq_pad), flat(k, sk_pad), flat(v, sk_pad), causal,
               window, softcap, bq, bk, Sq, Sk, num_warps, pipeline,
               interpret)
    o = o.reshape(B, H, sq_pad, D).transpose(0, 2, 1, 3)
    return o[:, :Sq]


def _kernel_args(causal, window, softcap, bq, bk, sq_valid, sk_valid,
                 num_warps, pipeline, interpret):
    return dict(causal=causal, window=window, softcap=softcap, block_q=bq,
                block_k=bk, sq_valid=sq_valid, sk_valid=sk_valid,
                num_warps=num_warps, pipeline=pipeline, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(3, 13)))
def _flash(q, k, v, *args):
    return flash_attention_fwd(q, k, v, **_kernel_args(*args))


def _flash_fwd(q, k, v, *args):
    o, lse = flash_attention_fwd(q, k, v, with_lse=True,
                                 **_kernel_args(*args))
    return o, (q, k, v, o, lse)


def _flash_bwd(*args):
    # custom_vjp passes the nondiff arguments, the residuals, the cotangent
    *args, (q, k, v, o, lse), g = args
    delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32),
                    axis=-1)[:, None, :]
    return flash_attention_bwd(q, k, v, g.astype(q.dtype), lse, delta,
                               **_kernel_args(*args))


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
