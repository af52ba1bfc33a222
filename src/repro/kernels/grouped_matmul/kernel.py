"""The grouped matrix multiplications of a dropless expert layer: JAX's
megablox Pallas TPU kernels (``jax.experimental.pallas.ops.tpu.megablox``),
called with the tiles chosen here.

Rows are sorted by group.  ``group_sizes`` [G + 1] counts the rows of each
of the G groups that ``rhs`` holds, then the rows that belong to none of
them (a chip's share of an expert layer: the pairs routed to experts held
elsewhere).  The kernels' grid is sized by the groups' tiles at run time,
so only the held groups' rows are computed, rounded up to whole row tiles;
the other rows of the output are zero.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as mb_gmm
from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm as mb_tgmm

ROW_TILE = 512
_COL_TILES = (1024, 896, 768, 512, 384, 256, 128)


def _col_tile(x: int) -> int:
    """The widest column tile that divides ``x`` (all of it if none)."""
    return next((t for t in _COL_TILES if x % t == 0), x)


def row_tile(m: int) -> int:
    """Rows of one tile for ``m`` rows: ``ROW_TILE``, or ``m`` rounded up
    to a multiple of 8 where that is less."""
    return min(ROW_TILE, -(-m // 8) * 8)


def gmm(lhs, rhs, group_sizes, *, transpose_rhs: bool = False,
        interpret: bool = False):
    """lhs [m, k] @ rhs[g] (rhs [G, k, n], or [G, n, k] transposed) for the
    rows of each group g < G; zero for the rest.  m is a multiple of
    :func:`row_tile`.  Accumulates in float32, returns lhs's dtype."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiling = (row_tile(m), _col_tile(k), _col_tile(n))
    return mb_gmm(lhs, rhs, group_sizes, lhs.dtype, tiling,
                  transpose_rhs=transpose_rhs, interpret=interpret)


def tgmm(lhs, rhs, group_sizes, num_groups: int, *,
         interpret: bool = False):
    """Per group g < ``num_groups``: lhs[rows of g]ᵀ @ rhs[rows of g], for
    lhs [m, k] and rhs [m, n] -> [num_groups, k, n] in rhs's dtype (a
    group with no rows gives zeros)."""
    m, k = lhs.shape
    n = rhs.shape[1]
    tiling = (row_tile(m), _col_tile(k), _col_tile(n))
    return mb_tgmm(jnp.swapaxes(lhs, 0, 1), rhs, group_sizes, rhs.dtype,
                   tiling, num_actual_groups=num_groups,
                   interpret=interpret)
