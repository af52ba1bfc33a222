"""jit'd wrapper: lengthscale scaling, padding, backend dispatch."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gp_gram.kernel import matern52_gram_fwd


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("block", "block_m",
                                             "num_warps", "pipeline",
                                             "interpret"))
def matern52_gram(x, lengthscale, signal_var, *, block: int = 128,
                  block_m: int = None, num_warps: int = None,
                  pipeline: int = None, interpret: bool = None):
    """x [n, d] -> Matérn-5/2 Gram [n, n] (f32); ARD lengthscale [d].

    ``block``/``block_m`` tile the output rows/columns (``block_m=None``:
    square tiles); ``num_warps``/``pipeline`` are the GPU scheduling
    knobs.  All four are SAPPHIRE autotune knobs (:func:`autotune_space`)
    — the output is tiling-invariant, only the wall-clock moves.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, d = x.shape
    xs = (x / lengthscale).astype(jnp.float32)
    bn = min(block, _round_up(n, 8))
    bm = min(block_m if block_m else block, _round_up(n, 8))
    npad_r, npad_c = _round_up(n, bn), _round_up(n, bm)
    # pad rows far away (distance huge -> kernel ~0); sliced off below.
    # Rows and columns pad independently: rectangular tiles need the two
    # operands at different multiples.
    xr = (jnp.pad(xs, ((0, npad_r - n), (0, 0)), constant_values=1e4)
          if npad_r > n else xs)
    xc = (jnp.pad(xs, ((0, npad_c - n), (0, 0)), constant_values=1e4)
          if npad_c > n else xs)
    g = matern52_gram_fwd(xr, xc, signal_var=1.0, block_n=bn, block_m=bm,
                          num_warps=num_warps, pipeline=pipeline,
                          interpret=interpret)
    return g[:n, :n] * signal_var


@functools.partial(jax.jit, static_argnames=("block", "block_m",
                                             "num_warps", "pipeline",
                                             "interpret"))
def matern52_cross(xa, xb, lengthscale, signal_var, *, block: int = 128,
                   block_m: int = None, num_warps: int = None,
                   pipeline: int = None, interpret: bool = None):
    """Cross-Gram [n, m] for acquisition batches.

    ``block`` tiles the xa rows, ``block_m`` the xb rows (None: square
    tiles) — the same autotune knobs as :func:`matern52_gram`."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, d = xa.shape
    m, _ = xb.shape
    a = (xa / lengthscale).astype(jnp.float32)
    b = (xb / lengthscale).astype(jnp.float32)
    bn = min(block, _round_up(n, 8))
    bm = min(block_m if block_m else block, _round_up(m, 8))
    np_, mp = _round_up(n, bn), _round_up(m, bm)
    if np_ > n:
        a = jnp.pad(a, ((0, np_ - n), (0, 0)), constant_values=1e4)
    if mp > m:
        b = jnp.pad(b, ((0, mp - m), (0, 0)), constant_values=-1e4)
    g = matern52_gram_fwd(a, b, signal_var=1.0, block_n=bn, block_m=bm,
                          num_warps=num_warps, pipeline=pipeline,
                          interpret=interpret)
    return g[:n, :m] * signal_var


# ---------------------------------------------------------------------------
# autotune hooks (repro.kernels.autotune)
# ---------------------------------------------------------------------------

def autotune_space():
    """The gram kernel's tunable tiling/scheduling space."""
    from repro.core.space import Knob, ProductLeq, Space, pow2_knob
    return Space(
        knobs=(
            pow2_knob("block_n", 128, 8, 512,
                      description="output row tile"),
            # the column tile is the output block's lane dimension: the
            # TPU lowering takes a multiple of 128 (or the whole padded
            # width, which the wrapper clamps wider tiles to)
            pow2_knob("block_m", 128, 128, 512,
                      description="output column tile"),
            pow2_knob("num_warps", 4, 1, 8, inert=True,
                      description="GPU warps per block (inert off-GPU)"),
            Knob("pipeline", "int", 2, lo=1, hi=4, inert=True,
                 description="GPU pipeline stages (inert off-GPU)"),
        ),
        # VMEM/SMEM budget: the [bn, bm] output tile must fit
        constraints=(ProductLeq(("block_n", "block_m"), limit=256 * 256),),
    )


def autotune_bench(n: int = 136, d: int = 8, seed: int = 0):
    """``build(cfg) -> run()`` factory for :class:`KernelEvaluator`.

    Default shape n=136: off the 128 ladder, so the hand-picked square
    128 tile pads 136→256 and runs a 2×2 grid while a ≥144 tile runs the
    whole Gram in one call — a real tiling decision for the tuner to
    find."""
    key = jax.random.key(seed)
    x = jax.random.uniform(key, (n, d), jnp.float32)
    ls = jnp.full((d,), 0.3, jnp.float32)

    def build(cfg):
        bn, bm = int(cfg["block_n"]), int(cfg["block_m"])
        nw = int(cfg.get("num_warps", 0)) or None
        ps = int(cfg.get("pipeline", 0)) or None

        def run():
            return matern52_gram(x, ls, 1.0, block=bn, block_m=bm,
                                 num_warps=nw, pipeline=ps)
        return run
    return build
