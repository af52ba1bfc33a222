"""Plain jnp oracle of the grouped matmul (``ops.grouped_matmul``)."""

from __future__ import annotations

import jax.numpy as jnp


def row_groups(group_sizes, m: int):
    """The group of each of m sorted rows (G for rows of no held group)."""
    ends = jnp.cumsum(group_sizes)
    return jnp.searchsorted(ends, jnp.arange(m), side="right")


def grouped_matmul_ref(lhs, rhs, group_sizes):
    """lhs [m, k], rhs [G, k, n], group_sizes [G + 1] -> [m, n] float32:
    each row times its group's matrix, zero outside the held groups."""
    G = rhs.shape[0]
    g = row_groups(group_sizes, lhs.shape[0])
    held = g < G
    w = rhs.astype(jnp.float32)[jnp.minimum(g, G - 1)]          # [m, k, n]
    out = jnp.einsum("mk,mkn->mn", lhs.astype(jnp.float32), w)
    return jnp.where(held[:, None], out, 0.0)
