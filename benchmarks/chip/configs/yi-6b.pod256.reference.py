"""Plain float64 NumPy reference of what a Sapphire tuning session
computes on the device, for the yi-6b pod configuration.

* :func:`qei_gaps` — the GP posterior and constant-liar q-EI of one ask:
  a Matérn-5/2 ARD Gram over the observed points, the same diagonal
  (noise + 10⁻⁴·signal + 10⁻⁶), a Cholesky solve, expected improvement
  for minimization with ξ = 0.01, and after each pick the pick appended
  as an observation at the incumbent (the liar), targets standardized
  again over real and fantasy points.  It conditions on the program's own
  picks and returns, for each, how far its EI lies below the best EI the
  reference finds at that step, in units of the ask's target std.
* :func:`lasso_path` — standardized features and target, a geometric λ
  grid given by the program's call, and warm-started FISTA on the Gram
  with the exact top eigenvalue as Lipschitz constant, the same iteration
  cap and stopping rule, in float64.

The GP's hyperparameters are part of the ask's answer (the program fits
them); the reference takes them as given and judges the posterior and the
picks under them.  It imports nothing of the system under test.
"""

from __future__ import annotations

import math

import numpy as np
from scipy.linalg import cho_factor, cho_solve, solve_triangular
from scipy.special import erf

SQRT5 = math.sqrt(5.0)


def matern52(xa, xb, ls, sv):
    a, b = xa / ls, xb / ls
    d2 = np.maximum((a * a).sum(1)[:, None] + (b * b).sum(1)[None, :]
                    - 2.0 * a @ b.T, 0.0)
    s = SQRT5 * np.sqrt(d2)
    return sv * (1.0 + s + s * s / 3.0) * np.exp(-s)


def qei_gaps(x, y, ls, sv, nv, cand, best_y, picks, xi: float = 0.01):
    """x [n, d], y [n] observed (log) targets, picks [q] -> gaps [q]."""
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    cand = np.asarray(cand, np.float64)
    ls = np.asarray(ls, np.float64)
    jitter = nv + 1e-4 * sv + 1e-6
    taken = np.zeros(len(cand), bool)
    xa, ya = x, y
    unit = None
    gaps = []
    for pick in picks:
        mu = ya.mean()
        sd = ya.std()
        sd = 1.0 if sd < 1e-12 else sd
        if unit is None:
            unit = sd
        ys = (ya - mu) / sd
        k = matern52(xa, xa, ls, sv) + np.eye(len(xa)) * jitter
        c = cho_factor(k, lower=True)
        kc = matern52(cand, xa, ls, sv)
        mean = kc @ cho_solve(c, ys) * sd + mu
        v = solve_triangular(c[0], kc.T, lower=True)
        std = np.sqrt(np.maximum(sv - np.sum(v * v, axis=0), 1e-12)) * sd
        std = np.maximum(std, 1e-9)
        imp = best_y - xi - mean
        z = imp / std
        ei = imp * 0.5 * (1 + erf(z / math.sqrt(2))) \
            + std * np.exp(-0.5 * z * z) / math.sqrt(2 * math.pi)
        ei[taken] = -np.inf
        gaps.append((np.max(ei) - ei[pick]) / unit)
        taken[pick] = True
        xa = np.vstack([xa, cand[pick][None]])
        ya = np.append(ya, best_y)
    return np.asarray(gaps)


def lasso_path(x, y, lams, max_iter: int, tol: float = 1e-7):
    """betas [L, d] on the standardized scale."""
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    xs = x.std(axis=0)
    xs = np.where(xs < 1e-12, 1.0, xs)
    xz = (x - x.mean(axis=0)) / xs
    yz = y - y.mean()
    n, d = xz.shape
    g = xz.T @ xz / n
    b = xz.T @ yz / n
    lip = max(np.linalg.eigvalsh(g)[-1], 1e-6) * 1.01

    def soft(u, t):
        return np.sign(u) * np.maximum(np.abs(u) - t, 0.0)

    def step(beta, z, t, lam):
        new = soft(z - (g @ z - b) / lip, lam / lip)
        t_new = 0.5 * (1.0 + math.sqrt(1.0 + 4.0 * t * t))
        return new, new + (t - 1.0) / t_new * (new - beta), t_new

    beta = np.zeros(d)
    out = []
    for lam in np.asarray(lams, np.float64):
        prev = beta
        beta, z, t = step(beta, beta, 1.0, lam)
        it = 1
        while it < max_iter and np.max(np.abs(beta - prev)) > tol:
            prev = beta
            beta, z, t = step(beta, z, t, lam)
            it += 1
        out.append(beta)
    return np.stack(out)


# ---------------------------------------------------------------------------
# the control: the same computations in float32 on the device at a lower
# matrix-product precision ("high", three bf16 passes), in the program's
# place; its picks and path are judged by the float64 functions above
# ---------------------------------------------------------------------------

def qei_picks_f32(x, y, ls, sv, nv, cand, best_y, q: int, xi: float = 0.01,
                  precision: str = "high"):
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import cho_solve as jcho_solve
    from jax.scipy.linalg import solve_triangular as jsolve

    def kern(a, b):
        a, b = a / ls, b / ls
        d2 = jnp.maximum((a * a).sum(1)[:, None] + (b * b).sum(1)[None, :]
                         - 2.0 * a @ b.T, 0.0)
        s = SQRT5 * jnp.sqrt(d2)
        return sv * (1.0 + s + s * s / 3.0) * jnp.exp(-s)

    with jax.default_matmul_precision(precision):
        f = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        xa, ya, cand = f(x), f(y), f(cand)
        ls = f(ls)
        taken = jnp.zeros(cand.shape[0], bool)
        picks = []
        for _ in range(q):
            mu, sd = ya.mean(), ya.std()
            sd = jnp.where(sd < 1e-12, 1.0, sd)
            k = kern(xa, xa) + jnp.eye(xa.shape[0]) * (nv + 1e-4 * sv + 1e-6)
            chol = jnp.linalg.cholesky(k)
            kc = kern(cand, xa)
            mean = kc @ jcho_solve((chol, True), (ya - mu) / sd) * sd + mu
            v = jsolve(chol, kc.T, lower=True)
            std = jnp.sqrt(jnp.maximum(sv - jnp.sum(v * v, 0), 1e-12)) * sd
            std = jnp.maximum(std, 1e-9)
            imp = best_y - xi - mean
            z = imp / std
            ei = imp * 0.5 * (1 + jax.scipy.special.erf(z / math.sqrt(2))) \
                + std * jnp.exp(-0.5 * z * z) / math.sqrt(2 * math.pi)
            i = int(jnp.argmax(jnp.where(taken, -jnp.inf, ei)))
            picks.append(i)
            taken = taken.at[i].set(True)
            xa = jnp.vstack([xa, cand[i][None]])
            ya = jnp.append(ya, f(best_y))
    return np.asarray(picks)


def lasso_path_f32(x, y, lams, max_iter: int, tol: float = 1e-7,
                   precision: str = "high"):
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision(precision):
        x = jnp.asarray(x, jnp.float32)
        y = jnp.asarray(y, jnp.float32)
        xs = x.std(axis=0)
        xs = jnp.where(xs < 1e-12, 1.0, xs)
        xz = (x - x.mean(axis=0)) / xs
        yz = y - y.mean()
        n = xz.shape[0]
        g = xz.T @ xz / n
        b = xz.T @ yz / n
        lip = jnp.maximum(jnp.linalg.eigvalsh(g)[-1], 1e-6) * 1.01

        def soft(u, t):
            return jnp.sign(u) * jnp.maximum(jnp.abs(u) - t, 0.0)

        @jax.jit
        def one(beta, lam):
            def step(st):
                beta, z, t, _, it = st
                new = soft(z - (g @ z - b) / lip, lam / lip)
                t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
                return (new, new + (t - 1.0) / t_new * (new - beta), t_new,
                        beta, it + 1)

            def cond(st):
                beta, _, _, prev, it = st
                return jnp.logical_and(it < max_iter,
                                       jnp.max(jnp.abs(beta - prev)) > tol)

            st = step((beta, beta, jnp.float32(1.0), beta, 0))
            return jax.lax.while_loop(cond, step, st)[0]

        beta = jnp.zeros(x.shape[1], jnp.float32)
        out = []
        for lam in np.asarray(lams, np.float32):
            beta = one(beta, lam)
            out.append(beta)
    return np.asarray(jnp.stack(out), np.float64)
