"""jit'd, differentiable grouped matmul: the dropless expert layer's
products (``models/moe.py``, ``moe_impl="grouped"``).

``grouped_matmul(lhs, rhs, group_sizes)``: lhs [m, k] holds rows sorted
by group, rhs [G, k, n] one matrix per held group, and group_sizes
[G + 1] the rows of each held group and then of none.  Row r of the
result is lhs[r] @ rhs[g] for the held group g it lies in, and zero
outside them.  The backward is two more kernel calls: the rows' gradient
(``gmm`` against rhsᵀ) and the weights' (``tgmm``, per group).  Every
call runs under a jitted function whose name holds ``grouped_matmul``,
which names the kernels in a compiled program's metadata.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.grouped_matmul import kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul_fwd(lhs, rhs, group_sizes, interpret):
    return kernel.gmm(lhs, rhs, group_sizes, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul_bwd(lhs, rhs, group_sizes, g, interpret):
    dlhs = kernel.gmm(g, rhs, group_sizes, transpose_rhs=True,
                      interpret=interpret)
    drhs = kernel.tgmm(lhs, g, group_sizes, rhs.shape[0],
                       interpret=interpret)
    return dlhs, drhs


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(lhs, rhs, group_sizes, interpret):
    return grouped_matmul_fwd(lhs, rhs, group_sizes, interpret)


def _gmm_vjp_fwd(lhs, rhs, group_sizes, interpret):
    return (grouped_matmul_fwd(lhs, rhs, group_sizes, interpret),
            (lhs, rhs, group_sizes))


def _gmm_vjp_bwd(interpret, res, g):
    lhs, rhs, group_sizes = res
    dlhs, drhs = grouped_matmul_bwd(lhs, rhs, group_sizes,
                                    g.astype(lhs.dtype), interpret)
    return dlhs, drhs.astype(rhs.dtype), None


_gmm.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)


def grouped_matmul(lhs, rhs, group_sizes, *, interpret: bool = None):
    """lhs [m, k], rhs [G, k, n], group_sizes [G + 1] int32 -> [m, n] in
    lhs's dtype (float32 accumulation).  Interpret mode off the TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m = lhs.shape[0]
    tm = kernel.row_tile(m)
    pad = -m % tm
    if pad:     # rows past every group: zero in the result, dropped here
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    return _gmm(lhs, rhs.astype(lhs.dtype), group_sizes.astype(jnp.int32),
                interpret)[:m]
