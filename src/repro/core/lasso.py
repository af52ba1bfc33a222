"""Lasso regression via cyclic coordinate descent, in JAX (paper §3.3).

The paper selects important knobs with L1-penalized least squares; we
implement it from scratch (no sklearn in this container):

* ``lasso_fit``   — coordinate descent for one λ (soft-thresholding),
  jit-compiled; warm-startable.
* ``lasso_path``  — geometric λ grid from λ_max (all-zero solution) down,
  warm-started — the standard pathwise algorithm (Friedman et al.).
* ``ridge_fit``   — closed-form L2 baseline (the paper's comparison: ridge
  can't zero out coefficients, so it can't *select*).

Features are standardized internally (zero mean / unit variance); returned
coefficients are on the standardized scale, which is exactly what the
importance ranking wants (comparable magnitudes across knobs).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# f32 matmuls at full precision: the TPU's default is one bf16 pass, which
# perturbs the Gram and the gradient steps of the path well beyond the
# solver's tolerance (the CPU computes f32 either way)
_mm = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


class Standardized(NamedTuple):
    x: jnp.ndarray       # [n, d] standardized features
    y: jnp.ndarray       # [n] centered target
    x_mean: jnp.ndarray
    x_std: jnp.ndarray
    y_mean: jnp.ndarray


def standardize(x: jnp.ndarray, y: jnp.ndarray) -> Standardized:
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    xm = x.mean(axis=0)
    xs = x.std(axis=0)
    xs = jnp.where(xs < 1e-12, 1.0, xs)   # constant cols -> coef stays 0
    ym = y.mean()
    return Standardized((x - xm) / xs, y - ym, xm, xs, ym)


def lambda_max(std: Standardized) -> float:
    """Smallest λ with all-zero solution: max |xᵀy| / n."""
    n = std.x.shape[0]
    return float(jnp.max(jnp.abs(_mm(std.x.T, std.y))) / n)


def _cd_impl(x, y, lam, beta0, max_iter: int = 500, tol: float = 1e-6):
    """Cyclic coordinate descent.  x standardized [n,d], y centered [n].
    Traceable core shared by the one-λ jit and the whole-path scan."""
    n, d = x.shape
    col_sq = jnp.sum(x * x, axis=0) / n            # ~1 after standardization

    def one_sweep(beta):
        def body(j, state):
            beta, r = state                        # r = y - x @ beta
            bj = beta[j]
            xj = x[:, j]
            rho = _mm(xj, r) / n + col_sq[j] * bj
            bj_new = jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - lam, 0.0) \
                / jnp.maximum(col_sq[j], 1e-12)
            r = r + xj * (bj - bj_new)
            beta = beta.at[j].set(bj_new)
            return beta, r

        r = y - _mm(x, beta)
        beta_new, _ = jax.lax.fori_loop(0, d, body, (beta, r))
        return beta_new

    def cond(state):
        beta, beta_prev, it = state
        delta = jnp.max(jnp.abs(beta - beta_prev))
        return jnp.logical_and(it < max_iter, delta > tol)

    def step(state):
        beta, _, it = state
        return one_sweep(beta), beta, it + 1

    beta0 = jnp.asarray(beta0, jnp.float32)
    init = (one_sweep(beta0), beta0, jnp.asarray(1))
    beta, _, _ = jax.lax.while_loop(cond, step, init)
    return beta


_cd = partial(jax.jit, static_argnames=("max_iter",))(_cd_impl)


@partial(jax.jit, static_argnames=("max_iter",))
def _fista_path(x, y, lams, max_iter: int, tol: float = 1e-7):
    """Warm-started FISTA over the whole λ grid inside ONE jit.

    Works on the Gram matrix, so each inner iteration is a single [d,d]
    matvec — fully vectorized across features, unlike cyclic CD's
    inherently sequential per-column sweep (the ranking-phase hot spot:
    ~380 dummy-coded features × 50 λs).  Lasso is convex, so FISTA and CD
    converge to the same path up to tolerance; a lax.scan carries β down
    the grid (the standard pathwise warm start) in one dispatch.
    """
    n, d = x.shape
    g = _mm(x.T, x) / n                             # [d, d] gram
    b = _mm(x.T, y) / n                             # [d]
    # Lipschitz constant of ∇(½‖y−xβ‖²/n): the exact top eigenvalue (an
    # underestimate would make the gradient step overshoot and the whole
    # warm-started path diverge silently). One [d,d] eigh per path call
    # is cheap next to the λ-grid solve itself.
    lip = jnp.maximum(jnp.linalg.eigvalsh(g)[-1], 1e-6) * 1.01

    def soft(u, t):
        return jnp.sign(u) * jnp.maximum(jnp.abs(u) - t, 0.0)

    def per_lam(beta, lam):
        def cond(state):
            beta, _, _, prev, it = state
            return jnp.logical_and(it < max_iter,
                                   jnp.max(jnp.abs(beta - prev)) > tol)

        def step(state):
            beta, z, t, _, it = state
            beta_new = soft(z - (_mm(g, z) - b) / lip, lam / lip)
            t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            z = beta_new + (t - 1.0) / t_new * (beta_new - beta)
            return beta_new, z, t_new, beta, it + 1

        init = step((beta, beta, jnp.asarray(1.0, jnp.float32),
                     beta + 2 * tol, jnp.asarray(0)))
        beta, _, _, _, _ = jax.lax.while_loop(cond, step, init)
        return beta, beta

    _, betas = jax.lax.scan(per_lam, jnp.zeros((d,), jnp.float32), lams)
    return betas


def lasso_fit(x, y, lam: float, beta0=None, max_iter: int = 500) -> np.ndarray:
    """Fit one λ; returns standardized-scale coefficients [d]."""
    std = standardize(x, y)
    d = std.x.shape[1]
    if beta0 is None:
        beta0 = jnp.zeros((d,), jnp.float32)
    beta = _cd(std.x, std.y, jnp.asarray(lam, jnp.float32), beta0,
               max_iter=max_iter)
    return np.asarray(beta)


def lasso_path(x, y, n_lambdas: int = 50, eps: float = 1e-3,
               max_iter: int = 300) -> Tuple[np.ndarray, np.ndarray]:
    """Pathwise CD over a geometric λ grid (warm starts).

    Returns (lambdas [L] descending, betas [L, d] standardized scale).
    """
    std = standardize(x, y)
    lmax = max(lambda_max(std), 1e-12)
    lams = np.geomspace(lmax, lmax * eps, n_lambdas)
    betas = _fista_path(std.x, std.y, jnp.asarray(lams, jnp.float32),
                        max_iter=max_iter)
    return lams, np.asarray(betas)


def ridge_fit(x, y, lam: float) -> np.ndarray:
    """Closed-form ridge (comparison baseline; cannot select features)."""
    std = standardize(x, y)
    n, d = std.x.shape
    a = _mm(std.x.T, std.x) / n + lam * jnp.eye(d, dtype=jnp.float32)
    b = _mm(std.x.T, std.y) / n
    return np.asarray(jnp.linalg.solve(a, b))


def path_importance(lams: np.ndarray, betas: np.ndarray) -> np.ndarray:
    """Per-feature importance from a lasso path.

    λ-weighted area under |β_j(λ)|: ∫ λ·|β_j(λ)| dlogλ.  Features that
    enter *early* (at large λ, where the L1 penalty only admits strong
    signals) dominate; spurious features that creep in at the small-λ
    overfitting tail get negligible weight.  More stable than |β| at one λ
    and consistent with entry-order ranking (paper Fig. 6's drastically
    dropping curve is this quantity, normalized).
    """
    logl = np.log(lams)
    w = np.abs(np.gradient(logl)) * lams   # λ·dlogλ weights
    return np.einsum("l,ld->d", w, np.abs(betas))
