"""jit'd wrapper for the chunkwise mLSTM kernel (model layout in/out).

The kernel computes the forward only; its VJP is that of the jnp chunked
form it matches (``ref.mlstm_chunked``), so the train step can
differentiate through the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.mlstm_chunk.kernel import mlstm_chunk_fwd
from repro.kernels.mlstm_chunk.ref import mlstm_chunked


def _forward(q, k, v, logi, logf, chunk, num_warps, pipeline, interpret):
    B, S, H, P = q.shape
    to_flat = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, P)  # noqa
    gate_flat = lambda t: t.transpose(0, 2, 1).reshape(B * H, S, 1)   # noqa
    h = mlstm_chunk_fwd(to_flat(q), to_flat(k), to_flat(v),
                        gate_flat(logi).astype(jnp.float32),
                        gate_flat(logf).astype(jnp.float32),
                        chunk=chunk, num_warps=num_warps,
                        pipeline=pipeline, interpret=interpret)
    return h.reshape(B, H, S, P).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _mlstm(q, k, v, logi, logf, chunk, num_warps, pipeline, interpret):
    return _forward(q, k, v, logi, logf, chunk, num_warps, pipeline,
                    interpret)


def _mlstm_fwd(q, k, v, logi, logf, chunk, num_warps, pipeline, interpret):
    out = _forward(q, k, v, logi, logf, chunk, num_warps, pipeline,
                   interpret)
    return out, (q, k, v, logi, logf)


def _mlstm_bwd(chunk, num_warps, pipeline, interpret, res, g):
    dtype = res[0].dtype
    _, vjp = jax.vjp(
        lambda *a: mlstm_chunked(*a, chunk=chunk).astype(dtype), *res)
    return vjp(g)


_mlstm.defvjp(_mlstm_fwd, _mlstm_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "num_warps",
                                             "pipeline", "interpret"))
def mlstm_chunk(q, k, v, logi, logf, *, chunk: int = 256,
                num_warps: int = None, pipeline: int = None,
                interpret: bool = None):
    """q/k/v [B,S,H,P], logi/logf [B,S,H] -> h [B,S,H,P].

    k must already carry the 1/sqrt(P) scale (as models/xlstm.py projects).
    ``chunk``/``num_warps``/``pipeline`` are SAPPHIRE autotune knobs
    (:func:`autotune_space`).  Differentiable (see the module docstring).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _mlstm(q, k, v, logi, logf, min(chunk, q.shape[1]), num_warps,
                  pipeline, interpret)


# ---------------------------------------------------------------------------
# autotune hooks (repro.kernels.autotune)
# ---------------------------------------------------------------------------

def autotune_space():
    """Tunable chunking/scheduling space of the mLSTM forward.

    No cross-knob constraint: the carry scratch is [P, P] regardless of
    chunk, and the [C, C] decay tile grows quadratically but stays within
    budget over the whole ladder."""
    from repro.core.space import Knob, Space, pow2_knob
    return Space(
        knobs=(
            pow2_knob("chunk", 256, 16, 512,
                      description="sequence chunk width"),
            pow2_knob("num_warps", 4, 1, 8, inert=True,
                      description="GPU warps per block (inert off-GPU)"),
            Knob("pipeline", "int", 2, lo=1, hi=4, inert=True,
                 description="GPU pipeline stages (inert off-GPU)"),
        ),
    )


def autotune_bench(B: int = 1, S: int = 256, H: int = 2, P: int = 32,
                   seed: int = 0):
    """``build(cfg) -> run()`` factory for :class:`KernelEvaluator`."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(ks[0], (B, S, H, P), jnp.float32) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, P), jnp.float32) * 0.5 / (P ** 0.5)
    v = jax.random.normal(ks[2], (B, S, H, P), jnp.float32) * 0.5
    logi = jax.random.normal(ks[3], (B, S, H), jnp.float32)
    logf = -jax.nn.softplus(-jax.random.normal(ks[4], (B, S, H)) * 2.0)

    def build(cfg):
        c = int(cfg["chunk"])
        nw = int(cfg.get("num_warps", 0)) or None
        ps = int(cfg.get("pipeline", 0)) or None

        def run():
            return mlstm_chunk(q, k, v, logi, logf, chunk=c, num_warps=nw,
                               pipeline=ps)
        return run
    return build
