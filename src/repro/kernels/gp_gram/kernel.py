"""Pallas TPU Matérn-5/2 Gram-matrix kernel.

SAPPHIRE's own compute hot-spot: the GP surrogate's O(n²·d) kernel matrix
(gp.py builds it every BO iteration, and every acquisition evaluation
computes an [m, n] cross-Gram against thousands of candidates).  On a
fleet the tuner runs on an accelerator host, so the Gram matrix is a
legitimate TPU kernel target — and it is a textbook BlockSpec exercise:

  tile the [n, m] output into [bn, bm] VMEM blocks; each block needs one
  [bn, d] row-tile and one [bm, d] column-tile; the squared distance is a
  rank-d matmul on the MXU plus elementwise Matérn on the VPU.

Validated in interpret mode against the jnp oracle (gp.matern52).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tuning_compiler_params

SQRT5 = math.sqrt(5.0)


def _kernel(xa_ref, xb_ref, o_ref, *, signal_var: float):
    a = xa_ref[...]                          # [bn, d] pre-scaled by 1/ls
    b = xb_ref[...]                          # [bm, d]
    a2 = jnp.sum(a * a, axis=1, keepdims=True)           # [bn, 1]
    b2 = jnp.sum(b * b, axis=1, keepdims=True).T         # [1, bm]
    # full f32 precision: a single bf16 pass (the TPU default) loses the
    # small distances to the a² + b² − 2ab cancellation
    ab = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    d2 = jnp.maximum(a2 + b2 - 2.0 * ab, 0.0)
    safe = jnp.where(d2 > 1e-12, d2, 1.0)
    r = jnp.where(d2 > 1e-12, jnp.sqrt(safe), 0.0)
    s = SQRT5 * r
    o_ref[...] = (signal_var * (1.0 + s + s * s / 3.0) * jnp.exp(-s)
                  ).astype(o_ref.dtype)


def matern52_gram_fwd(xa, xb, *, signal_var: float = 1.0,
                      block_n: int = 128, block_m: int = 128,
                      num_warps=None, pipeline=None,
                      interpret: bool = False):
    """xa [n, d], xb [m, d] — already scaled by 1/lengthscale.

    n % block_n == 0 and m % block_m == 0 (wrapper pads).
    ``num_warps``/``pipeline`` are the GPU scheduling knobs (inert on
    TPU/interpret — see :func:`repro.kernels.tuning_compiler_params`).
    """
    n, d = xa.shape
    m, _ = xb.shape
    assert n % block_n == 0 and m % block_m == 0
    kernel = functools.partial(_kernel, signal_var=signal_var)
    extra = {}
    cp = tuning_compiler_params(num_warps, pipeline, interpret)
    if cp is not None:
        extra["compiler_params"] = cp
    return pl.pallas_call(
        kernel,
        grid=(n // block_n, m // block_m),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_m, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, block_m), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        interpret=interpret,
        **extra,
    )(xa, xb)
