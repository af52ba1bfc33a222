"""Gaussian-process regression for noisy black-box objectives (paper §3.4).

The paper's argument for BO-with-GP is noise tolerance: the GP's noise
hyperparameter lets it approximate the objective *through* noise-corrupted
observations.  Implementation:

* Matérn-5/2 (default) and RBF kernels over the unit cube;
* exact GP with Cholesky solves (≤ a few hundred points — the paper's
  regime, where each point costs a cluster benchmark);
* hyperparameters (lengthscale per-dim or shared, signal var, noise var)
  fit by maximizing the log marginal likelihood with Adam on log-params,
  jit-compiled end to end;
* the Gram matrix hot spot is a Pallas TPU kernel
  (kernels/gp_gram) with a jnp fallback — on a fleet the tuner itself may
  run on an accelerator host, and the Gram matrix is its only O(n²·d) op.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

SQRT5 = math.sqrt(5.0)


def _mm(a, b):
    """f32 matmul at full precision.  The TPU's default is one bf16 pass,
    which the Gram's a² + b² − 2ab cancellation and the posterior solves
    cannot absorb; the CPU computes f32 either way."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _sqdist(xa, xb, inv_ls):
    """Scaled squared distance: xa [n,d], xb [m,d], inv_ls [d] -> [n,m]."""
    a = xa * inv_ls
    b = xb * inv_ls
    a2 = jnp.sum(a * a, axis=1)[:, None]
    b2 = jnp.sum(b * b, axis=1)[None, :]
    return jnp.maximum(a2 + b2 - 2.0 * _mm(a, b.T), 0.0)


def matern52(xa, xb, lengthscale, signal_var):
    """Matérn-5/2: smooth enough for GP-BO, rougher than RBF (default).

    The sqrt is guarded with the double-``where`` trick: d/dr sqrt(r)|₀ is
    ∞, and zero distances (diagonal) would otherwise poison the
    marginal-likelihood gradients with NaN.
    """
    inv_ls = 1.0 / lengthscale
    d2 = _sqdist(xa, xb, inv_ls)
    safe = jnp.where(d2 > 1e-12, d2, 1.0)
    r = jnp.where(d2 > 1e-12, jnp.sqrt(safe), 0.0)
    s = SQRT5 * r
    return signal_var * (1.0 + s + s * s / 3.0) * jnp.exp(-s)


def rbf(xa, xb, lengthscale, signal_var):
    inv_ls = 1.0 / lengthscale
    return signal_var * jnp.exp(-0.5 * _sqdist(xa, xb, inv_ls))


KERNELS = {"matern52": matern52, "rbf": rbf}


def gram(kind: str, x, lengthscale, signal_var, *, use_pallas: bool = False):
    """Kernel Gram matrix; optionally via the Pallas tile kernel."""
    if use_pallas and kind == "matern52":
        from repro.kernels.gp_gram.ops import matern52_gram
        return matern52_gram(x, lengthscale, signal_var)
    return KERNELS[kind](x, x, lengthscale, signal_var)


# ---------------------------------------------------------------------------
# GP posterior
# ---------------------------------------------------------------------------

class GPParams(NamedTuple):
    log_lengthscale: jnp.ndarray   # [d] (ARD)
    log_signal_var: jnp.ndarray    # []
    log_noise_var: jnp.ndarray     # []


class GPState(NamedTuple):
    params: GPParams
    x: jnp.ndarray                 # [n, d] training inputs (unit cube)
    y: jnp.ndarray                 # [n] standardized targets
    chol: jnp.ndarray              # [n, n] cholesky of K + σ²I
    alpha: jnp.ndarray             # [n] K⁻¹ y
    y_mean: jnp.ndarray
    y_std: jnp.ndarray


def init_params(d: int, lengthscale: float = 0.3, signal: float = 1.0,
                noise: float = 1e-2) -> GPParams:
    return GPParams(
        log_lengthscale=jnp.full((d,), math.log(lengthscale), jnp.float32),
        log_signal_var=jnp.asarray(math.log(signal), jnp.float32),
        log_noise_var=jnp.asarray(math.log(noise), jnp.float32),
    )


def params_to_dict(params: GPParams) -> dict:
    """JSON-serializable snapshot of the GP hyperparameters — the
    first-class artifact warm restarts ship across processes
    (:meth:`repro.core.strategy.BOStrategy.state_dict`).  Values are the
    log-domain parameters exactly as fitted, so a roundtrip through
    :func:`params_from_dict` is bit-exact at f32."""
    return {
        "log_lengthscale": [float(v)
                            for v in np.asarray(params.log_lengthscale)],
        "log_signal_var": float(params.log_signal_var),
        "log_noise_var": float(params.log_noise_var),
    }


def params_from_dict(d: dict) -> GPParams:
    """Inverse of :func:`params_to_dict`."""
    return GPParams(
        log_lengthscale=jnp.asarray(d["log_lengthscale"], jnp.float32),
        log_signal_var=jnp.asarray(float(d["log_signal_var"]), jnp.float32),
        log_noise_var=jnp.asarray(float(d["log_noise_var"]), jnp.float32),
    )


PAD_NOISE = 1e6   # pseudo-point noise: pads contribute ~nothing to the fit


class MTGPParams(NamedTuple):
    """Multi-task (ICM) hyperparameters: the base-kernel triple shared
    across tasks plus a rank-1-plus-diagonal task covariance and a
    per-task mean offset.  ``task_w``/``log_task_kappa``/``task_offset``
    are [T]; everything else matches :class:`GPParams`."""
    log_lengthscale: jnp.ndarray   # [d] (ARD, shared across tasks)
    log_signal_var: jnp.ndarray    # []
    log_noise_var: jnp.ndarray     # []
    task_w: jnp.ndarray            # [T] rank-1 factor of the task kernel
    log_task_kappa: jnp.ndarray    # [T] per-task diagonal boost
    task_offset: jnp.ndarray       # [T] per-task mean (standardized y)


class MTGPState(NamedTuple):
    params: MTGPParams
    x: jnp.ndarray                 # [n, d] inputs (unit cube, no task col)
    tasks: jnp.ndarray             # [n] int32 task indices
    y: jnp.ndarray                 # [n] standardized targets
    chol: jnp.ndarray              # [n, n]
    alpha: jnp.ndarray             # [n] K⁻¹ (y - offset[tasks])
    y_mean: jnp.ndarray
    y_std: jnp.ndarray


def _jitter(nv, sv):
    """Relative diagonal jitter: keeps the condition number f32-safe even
    when the fitted signal variance is large / lengthscale long (K near
    rank-1).  Shared by the posterior build and the select_batch fantasy
    appends — the two paths must stamp identical diagonals."""
    return nv + 1e-4 * sv + 1e-6


def _build(params: GPParams, x, y, kind: str, extra_noise=None,
           use_pallas: bool = False):
    ls = jnp.exp(params.log_lengthscale)
    sv = jnp.exp(params.log_signal_var)
    nv = jnp.exp(params.log_noise_var)
    k = gram(kind, x, ls, sv, use_pallas=use_pallas)
    n = x.shape[0]
    diag = jnp.full((n,), _jitter(nv, sv), k.dtype)
    if extra_noise is not None:
        diag = diag + extra_noise
    kn = k + jnp.diag(diag)
    chol = jnp.linalg.cholesky(kn)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y)
    return chol, alpha


# jitted entry for posterior (re)builds outside the Adam loop — the
# constant-liar fantasy update calls this once per batch pick
_build_jit = partial(jax.jit, static_argnames=("kind", "use_pallas"))(_build)


def neg_log_marginal(params: GPParams, x, y, kind: str, extra_noise=None):
    chol, alpha = _build(params, x, y, kind, extra_noise)
    n = x.shape[0]
    return (0.5 * _mm(y, alpha)
            + jnp.sum(jnp.log(jnp.diagonal(chol)))
            + 0.5 * n * math.log(2 * math.pi))


@partial(jax.jit, static_argnames=("kind", "steps"))
def _fit(params: GPParams, x, y, kind: str, steps: int = 200,
         lr: float = 0.05, extra_noise=None):
    """Adam on log-hyperparameters maximizing the marginal likelihood."""
    grad_fn = jax.value_and_grad(
        lambda p: neg_log_marginal(p, x, y, kind, extra_noise))

    def step(carry, _):
        p, m, v, t = carry
        loss, g = grad_fn(p)
        g = jax.tree.map(lambda gi: jnp.nan_to_num(gi), g)  # NaN-proof step
        t = t + 1
        m = jax.tree.map(lambda mi, gi: 0.9 * mi + 0.1 * gi, m, g)
        v = jax.tree.map(lambda vi, gi: 0.999 * vi + 0.001 * gi * gi, v, g)
        mhat = jax.tree.map(lambda mi: mi / (1 - 0.9 ** t), m)
        vhat = jax.tree.map(lambda vi: vi / (1 - 0.999 ** t), v)
        p = jax.tree.map(lambda pi, mh, vh: pi - lr * mh / (jnp.sqrt(vh) + 1e-8),
                         p, mhat, vhat)
        # clamp hyperparams to sane boxes (noise floor keeps Cholesky PSD)
        p = GPParams(
            log_lengthscale=jnp.clip(p.log_lengthscale, math.log(1e-2), math.log(3.0)),
            log_signal_var=jnp.clip(p.log_signal_var, math.log(1e-2), math.log(1e2)),
            log_noise_var=jnp.clip(p.log_noise_var, math.log(1e-4), math.log(1.0)),
        )
        return (p, m, v, t), loss

    zeros = jax.tree.map(jnp.zeros_like, params)
    (p, _, _, _), losses = jax.lax.scan(
        step, (params, zeros, zeros, jnp.asarray(0, jnp.float32)),
        None, length=steps)
    return p, losses


def _bucket(n: int) -> int:
    """Pad count: next multiple of 16 — bounds jit recompiles to O(n/16)
    shapes instead of one per BO iteration."""
    return ((n + 15) // 16) * 16


def _prepare(x: np.ndarray, y: np.ndarray, pad: bool,
             pad_to: Optional[int] = None,
             obs_var: Optional[np.ndarray] = None):
    """Standardize y and append huge-noise pseudo-points up to the target
    shape (``pad_to`` or the next bucket).  ``obs_var`` [n] is the
    per-observation measurement variance in *raw* y units (replicated
    measurements report the variance of their mean); it lands on the same
    extra-noise diagonal the pads use, rescaled by 1/y_std² to match the
    standardized targets."""
    x = np.asarray(x, np.float32)
    y_raw = np.asarray(y, np.float32)
    n, d = x.shape
    y_mean, y_std = float(y_raw.mean()), float(y_raw.std())
    if y_std < 1e-12:
        y_std = 1.0
    ys = (y_raw - y_mean) / y_std
    extra = None
    if obs_var is not None:
        extra = np.asarray(obs_var, np.float32) / (y_std * y_std)
    if pad or pad_to:
        m = max(_bucket(n), pad_to or 0)
        if m > n:
            x = np.vstack([x, np.full((m - n, d), 0.5, np.float32)])
            ys = np.concatenate([ys, np.zeros(m - n, np.float32)])
            padded = np.zeros(m, np.float32)
            if extra is not None:
                padded[:n] = extra
            padded[n:] = PAD_NOISE
            extra = padded
    xj = jnp.asarray(x)
    yj = jnp.asarray(ys)
    ej = None if extra is None else jnp.asarray(extra)
    return xj, yj, ej, y_mean, y_std


def fit(x: np.ndarray, y: np.ndarray, kind: str = "matern52",
        steps: int = 200, params: Optional[GPParams] = None,
        pad: bool = True, pad_to: Optional[int] = None,
        use_pallas: bool = False,
        obs_var: Optional[np.ndarray] = None,
        tasks: Optional[np.ndarray] = None):
    """Standardize y, fit hyperparameters, build the posterior.

    ``pad`` appends huge-noise pseudo-points up to a shape bucket so the
    jit caches of ``_fit``/``predict`` are reused across BO iterations
    (the pads' posterior influence is ~1/PAD_NOISE — negligible).
    ``pad_to`` pins the padded size outright: a BO run that knows its
    total budget compiles each jit exactly once instead of once per
    16-point growth bucket.

    ``params`` warm-starts the hyperparameter optimization (e.g. from the
    previous BO round's posterior); with ``steps=0`` they are used as-is.

    ``obs_var`` [n] makes the GP heteroscedastic: per-observation
    measurement variance (raw y units — replicated measurements report
    the variance of their pooled mean) added to the noise diagonal on top
    of the fitted global scalar, through the same ``extra_noise``
    machinery the pads use.  The jitted ``lax.scan`` Adam loop and the
    Pallas gram route are untouched — extra noise only enters the
    diagonal stamp.  ``None`` (the default) is bit-identical to the
    homoscedastic path, and :func:`predict` / :func:`select_batch` /
    :func:`select_batch_sharded` need no variance argument: the
    heteroscedastic diagonal is baked into ``state.chol``, and fantasy
    appends deliberately keep the global-scalar diagonal (a fantasy point
    has no empirical repeat variance).

    ``use_pallas`` routes the posterior Gram build through the
    kernels/gp_gram tile kernel (matern52 only; jnp fallback otherwise).
    The marginal-likelihood Adam loop stays on the jnp kernel — it is
    differentiated, and the Pallas kernel defines no VJP.

    ``tasks`` [n] switches on the multi-task (ICM) path: integer task
    indices aligned with the rows of ``x``.  With more than one distinct
    task the fit routes through :func:`fit_multitask` and returns an
    :class:`MTGPState`; with exactly one distinct task the column is
    dropped and this is *exactly* the single-task fit (same jit cache,
    same GPState) — the fallback the transfer layer relies on when a
    corpus collapses to a single workload.
    """
    if tasks is not None:
        t = np.asarray(tasks, np.int32)
        if t.shape[0] != np.asarray(x).shape[0]:
            raise ValueError(
                f"tasks has {t.shape[0]} rows, x has "
                f"{np.asarray(x).shape[0]}")
        if t.size and int(t.max()) > 0:
            if params is not None and not isinstance(params, MTGPParams):
                raise TypeError("multi-task fit warm-start needs MTGPParams")
            return fit_multitask(x, y, t, kind=kind, steps=steps,
                                 params=params, obs_var=obs_var)
        # exact single-task fallback: one task present, column dropped
    xj, yj, ej, y_mean, y_std = _prepare(x, y, pad, pad_to, obs_var)
    if params is None:
        params = init_params(int(xj.shape[1]))
    if steps > 0:
        params, _ = _fit(params, xj, yj, kind, steps=steps, extra_noise=ej)
    chol, alpha = _build_jit(params, xj, yj, kind, ej,
                             use_pallas=use_pallas)
    return GPState(params, xj, yj, chol, alpha,
                   jnp.asarray(y_mean), jnp.asarray(y_std))


def condition(params: GPParams, x: np.ndarray, y: np.ndarray,
              kind: str = "matern52", pad: bool = True,
              pad_to: Optional[int] = None,
              use_pallas: bool = False,
              obs_var: Optional[np.ndarray] = None) -> GPState:
    """Posterior for (x, y) under *fixed* hyperparameters — no
    marginal-likelihood refit.  This is the constant-liar fantasy update
    of q-batch acquisition: one Cholesky rebuild, no Adam.  (The
    device-resident :func:`select_batch` replaces this per-pick rebuild
    with an O(n²) :func:`chol_append`; ``condition`` remains the
    reference path and the entry for one-off posterior updates.)"""
    return fit(x, y, kind, steps=0, params=params, pad=pad, pad_to=pad_to,
               use_pallas=use_pallas, obs_var=obs_var)


# ---------------------------------------------------------------------------
# multi-task GP (intrinsic coregionalization, rank-1 + diagonal)
# ---------------------------------------------------------------------------

def init_mt_params(d: int, n_tasks: int, lengthscale: float = 0.3,
                   signal: float = 1.0, noise: float = 1e-2,
                   offsets: Optional[np.ndarray] = None) -> MTGPParams:
    """ICM init: ``task_w = 1`` (tasks fully correlated a priori) with a
    small diagonal boost, per-task offsets from the data when given."""
    off = (jnp.zeros((n_tasks,), jnp.float32) if offsets is None
           else jnp.asarray(offsets, jnp.float32))
    return MTGPParams(
        log_lengthscale=jnp.full((d,), math.log(lengthscale), jnp.float32),
        log_signal_var=jnp.asarray(math.log(signal), jnp.float32),
        log_noise_var=jnp.asarray(math.log(noise), jnp.float32),
        task_w=jnp.ones((n_tasks,), jnp.float32),
        log_task_kappa=jnp.full((n_tasks,), math.log(0.1), jnp.float32),
        task_offset=off,
    )


def mt_params_to_dict(params: MTGPParams) -> dict:
    """JSON snapshot of the multi-task hyperparameters (log-domain values
    as fitted, like :func:`params_to_dict`)."""
    return {
        "log_lengthscale": [float(v)
                            for v in np.asarray(params.log_lengthscale)],
        "log_signal_var": float(params.log_signal_var),
        "log_noise_var": float(params.log_noise_var),
        "task_w": [float(v) for v in np.asarray(params.task_w)],
        "log_task_kappa": [float(v)
                           for v in np.asarray(params.log_task_kappa)],
        "task_offset": [float(v) for v in np.asarray(params.task_offset)],
    }


def mt_params_from_dict(d: dict) -> MTGPParams:
    return MTGPParams(
        log_lengthscale=jnp.asarray(d["log_lengthscale"], jnp.float32),
        log_signal_var=jnp.asarray(float(d["log_signal_var"]), jnp.float32),
        log_noise_var=jnp.asarray(float(d["log_noise_var"]), jnp.float32),
        task_w=jnp.asarray(d["task_w"], jnp.float32),
        log_task_kappa=jnp.asarray(d["log_task_kappa"], jnp.float32),
        task_offset=jnp.asarray(d["task_offset"], jnp.float32),
    )


def shared_params(params: MTGPParams) -> GPParams:
    """Project the shared base-kernel triple out of a multi-task fit —
    the warm start a single-task GP on a *new* workload inherits."""
    return GPParams(log_lengthscale=params.log_lengthscale,
                    log_signal_var=params.log_signal_var,
                    log_noise_var=params.log_noise_var)


def _task_cov(params: MTGPParams):
    """B = w wᵀ + diag(exp κ) — rank-1 plus diagonal, always PSD."""
    w = params.task_w
    return w[:, None] * w[None, :] + jnp.diag(
        jnp.exp(params.log_task_kappa))


def _mt_build(params: MTGPParams, x, tasks, y, kind: str,
              extra_noise=None):
    ls = jnp.exp(params.log_lengthscale)
    sv = jnp.exp(params.log_signal_var)
    nv = jnp.exp(params.log_noise_var)
    b = _task_cov(params)
    k = KERNELS[kind](x, x, ls, sv) * b[tasks[:, None], tasks[None, :]]
    n = x.shape[0]
    diag = jnp.full((n,), _jitter(nv, sv), k.dtype)
    if extra_noise is not None:
        diag = diag + extra_noise
    kn = k + jnp.diag(diag)
    chol = jnp.linalg.cholesky(kn)
    r = y - params.task_offset[tasks]
    alpha = jax.scipy.linalg.cho_solve((chol, True), r)
    return chol, alpha, r


def mt_neg_log_marginal(params: MTGPParams, x, tasks, y, kind: str,
                        extra_noise=None):
    chol, alpha, r = _mt_build(params, x, tasks, y, kind, extra_noise)
    n = x.shape[0]
    return (0.5 * _mm(r, alpha)
            + jnp.sum(jnp.log(jnp.diagonal(chol)))
            + 0.5 * n * math.log(2 * math.pi))


@partial(jax.jit, static_argnames=("kind", "steps"))
def _mt_fit(params: MTGPParams, x, tasks, y, kind: str, steps: int = 200,
            lr: float = 0.05, extra_noise=None):
    """Adam on the joint (base + task) log-marginal — the same scan body
    as :func:`_fit` with the task blocks clamped to their own boxes."""
    grad_fn = jax.value_and_grad(
        lambda p: mt_neg_log_marginal(p, x, tasks, y, kind, extra_noise))

    def step(carry, _):
        p, m, v, t = carry
        loss, g = grad_fn(p)
        g = jax.tree.map(lambda gi: jnp.nan_to_num(gi), g)
        t = t + 1
        m = jax.tree.map(lambda mi, gi: 0.9 * mi + 0.1 * gi, m, g)
        v = jax.tree.map(lambda vi, gi: 0.999 * vi + 0.001 * gi * gi, v, g)
        mhat = jax.tree.map(lambda mi: mi / (1 - 0.9 ** t), m)
        vhat = jax.tree.map(lambda vi: vi / (1 - 0.999 ** t), v)
        p = jax.tree.map(
            lambda pi, mh, vh: pi - lr * mh / (jnp.sqrt(vh) + 1e-8),
            p, mhat, vhat)
        p = MTGPParams(
            log_lengthscale=jnp.clip(p.log_lengthscale,
                                     math.log(1e-2), math.log(3.0)),
            log_signal_var=jnp.clip(p.log_signal_var,
                                    math.log(1e-2), math.log(1e2)),
            log_noise_var=jnp.clip(p.log_noise_var,
                                   math.log(1e-4), math.log(1.0)),
            task_w=jnp.clip(p.task_w, -3.0, 3.0),
            log_task_kappa=jnp.clip(p.log_task_kappa,
                                    math.log(1e-4), math.log(10.0)),
            task_offset=jnp.clip(p.task_offset, -5.0, 5.0),
        )
        return (p, m, v, t), loss

    zeros = jax.tree.map(jnp.zeros_like, params)
    (p, _, _, _), losses = jax.lax.scan(
        step, (params, zeros, zeros, jnp.asarray(0, jnp.float32)),
        None, length=steps)
    return p, losses


def fit_multitask(x: np.ndarray, y: np.ndarray, tasks: np.ndarray,
                  kind: str = "matern52", steps: int = 200,
                  params: Optional[MTGPParams] = None,
                  obs_var: Optional[np.ndarray] = None) -> MTGPState:
    """Fit the ICM multi-task GP over stacked per-task observations.

    Targets are standardized *globally* (one μ/σ over every task) and the
    per-task level differences are absorbed by the learned ``task_offset``
    mean — initialized at each task's empirical standardized mean so the
    Adam loop starts from the right basin.  No shape padding: a corpus
    fit happens once per transfer warm-start, not once per BO round, so
    jit-cache churn is not on the hot path.
    """
    x = np.asarray(x, np.float32)
    y_raw = np.asarray(y, np.float32)
    t = np.asarray(tasks, np.int32)
    n_tasks = int(t.max()) + 1
    y_mean, y_std = float(y_raw.mean()), float(y_raw.std())
    if y_std < 1e-12:
        y_std = 1.0
    ys = (y_raw - y_mean) / y_std
    extra = None
    if obs_var is not None:
        extra = jnp.asarray(
            np.asarray(obs_var, np.float32) / (y_std * y_std))
    if params is None:
        offsets = np.zeros(n_tasks, np.float32)
        for i in range(n_tasks):
            sel = t == i
            if sel.any():
                offsets[i] = float(ys[sel].mean())
        params = init_mt_params(int(x.shape[1]), n_tasks, offsets=offsets)
    xj, tj, yj = jnp.asarray(x), jnp.asarray(t), jnp.asarray(ys)
    if steps > 0:
        params, _ = _mt_fit(params, xj, tj, yj, kind, steps=steps,
                            extra_noise=extra)
    chol, alpha, _ = _mt_build(params, xj, tj, yj, kind, extra)
    return MTGPState(params, xj, tj, yj, chol, alpha,
                     jnp.asarray(y_mean), jnp.asarray(y_std))


@partial(jax.jit, static_argnames=("kind",))
def _mt_predict(state: MTGPState, xq, w_q, kappa_q, off_q, kind: str):
    ls = jnp.exp(state.params.log_lengthscale)
    sv = jnp.exp(state.params.log_signal_var)
    kq = (KERNELS[kind](xq, state.x, ls, sv)
          * (w_q * state.params.task_w)[state.tasks][None, :])
    mean_s = off_q + _mm(kq, state.alpha)
    v = jax.scipy.linalg.solve_triangular(state.chol, kq.T, lower=True)
    prior = (w_q * w_q + kappa_q) * sv
    var_s = jnp.maximum(prior - jnp.sum(v * v, axis=0), 1e-12)
    mean = mean_s * state.y_std + state.y_mean
    std = jnp.sqrt(var_s) * state.y_std
    return mean, std


def predict_multitask(state: MTGPState, xq, task: Optional[int] = None,
                      kind: str = "matern52"):
    """Posterior mean/std at ``xq`` for one task (original y scale).

    ``task=None`` is the **stacked prior** for an *unseen* task: its
    rank-1 weight, diagonal and mean offset are the averages over the
    fitted tasks, so the prediction borrows exactly the structure every
    corpus workload shares and stays honestly wide where they disagree
    (the averaged ``w`` shrinks the cross-covariance, inflating the
    posterior variance — which is what pseudo-observation inflation
    feeds on)."""
    p = state.params
    if task is None:
        w_q = jnp.mean(p.task_w)
        kappa_q = jnp.mean(jnp.exp(p.log_task_kappa))
        off_q = jnp.mean(p.task_offset)
    else:
        w_q = p.task_w[task]
        kappa_q = jnp.exp(p.log_task_kappa)[task]
        off_q = p.task_offset[task]
    return _mt_predict(state, jnp.asarray(xq, jnp.float32),
                       w_q, kappa_q, off_q, kind)


@partial(jax.jit, static_argnames=("kind", "use_pallas"))
def predict(state: GPState, xq, kind: str = "matern52",
            use_pallas: bool = False):
    """Posterior mean/std at query points xq [m,d] (original y scale)."""
    ls = jnp.exp(state.params.log_lengthscale)
    sv = jnp.exp(state.params.log_signal_var)
    if use_pallas and kind == "matern52":
        from repro.kernels.gp_gram.ops import matern52_cross
        kq = matern52_cross(xq, state.x, ls, sv)     # [m, n]
    else:
        kq = KERNELS[kind](xq, state.x, ls, sv)      # [m, n]
    mean_s = _mm(kq, state.alpha)
    v = jax.scipy.linalg.solve_triangular(state.chol, kq.T, lower=True)
    var_s = jnp.maximum(sv - jnp.sum(v * v, axis=0), 1e-12)
    mean = mean_s * state.y_std + state.y_mean
    std = jnp.sqrt(var_s) * state.y_std
    return mean, std


def expected_improvement(state: GPState, xq, best_y: float,
                         kind: str = "matern52", xi: float = 0.01):
    """EI for *minimization* of y (y = step time / negative bandwidth)."""
    mean, std = predict(state, xq, kind)
    std = jnp.maximum(std, 1e-9)
    imp = best_y - xi - mean
    z = imp / std
    cdf = 0.5 * (1 + jax.scipy.special.erf(z / math.sqrt(2)))
    pdf = jnp.exp(-0.5 * z * z) / math.sqrt(2 * math.pi)
    return imp * cdf + std * pdf


def ucb(state: GPState, xq, kind: str = "matern52", beta: float = 2.0):
    """Lower-confidence bound for minimization (returns negated for argmax)."""
    mean, std = predict(state, xq, kind)
    return -(mean - beta * std)


# ---------------------------------------------------------------------------
# device-resident q-batch selection
# ---------------------------------------------------------------------------

def chol_append(chol, k_vec, k_ss):
    """Incremental Cholesky append (O(n²), vs the O(n³) rebuild).

    Given ``chol`` (lower-triangular L with L Lᵀ = K, [n, n]), the cross
    column ``k_vec = K(x_new, X)`` [n] and the diagonal entry ``k_ss =
    k(x_new, x_new) + noise``, returns ``(l, d)`` such that
    ``[[L, 0], [lᵀ, d]]`` is the Cholesky factor of the (n+1)-point
    matrix ``[[K, k_vec], [k_vecᵀ, k_ss]]``.  This is the constant-liar
    fantasy update of q-batch acquisition without rebuilding anything.
    """
    l = jax.scipy.linalg.solve_triangular(chol, k_vec, lower=True)
    d = jnp.sqrt(jnp.maximum(k_ss - _mm(l, l), 1e-12))
    return l, d


@partial(jax.jit,
         static_argnames=("q", "kind", "fantasy", "acquisition",
                          "use_pallas"))
def select_batch(state: GPState, cand, y_raw, n, best_y, q: int,
                 kind: str = "matern52", fantasy: str = "liar",
                 acquisition: str = "ei", xi: float = 0.01,
                 use_pallas: bool = False):
    """Fantasized q-EI batch selection as ONE compiled program.

    Replaces the host loop (q acquisition jit calls, q host argmax round
    trips, q full ``condition`` rebuilds at O(n³) each) with a single
    ``lax.scan`` over picks: score the candidate pool, masked argmax,
    fantasize the pick's outcome (constant liar at ``best_y`` or Kriging
    believer at the posterior mean) and append it to the posterior via
    :func:`chol_append` — O(n²) per fantasy point, never leaving the
    device.

    Layout: the fitted padded state (``state.x`` [m, d], ``state.chol``
    [m, m], pads included exactly as :func:`fit` built them) occupies the
    leading block of a fixed [m+q-1]-size working set; fantasy points are
    *appended* into the trailing slots, so every shape is pinned by
    (m, q, |cand|) and the whole selection compiles once per run.  Like
    the rebuild path, the target standardization is recomputed over the
    real + fantasy observations at every pick (``gp.condition`` restamps
    y_mean/y_std per rebuild; this must match to reproduce its picks).

    Args:
      state: posterior from :func:`fit` (padded or not).
      cand:  [M, d] float32 candidate pool (unit cube).
      y_raw: [m] float32 raw targets aligned with ``state.x``; entries at
             index ≥ n (pads) are ignored.
      n:     number of real observations (traced — growing n does not
             recompile).
      best_y: incumbent best raw target (the EI threshold and the liar).
      q:     batch width (static).
      fantasy: "liar" | "believer";  acquisition: "ei" | "ucb".

    Returns ``picks`` [q] int32 — indices into ``cand``, identical to the
    legacy per-pick rebuild loop on the same inputs.
    """
    m, d_dim = state.x.shape
    M = cand.shape[0]
    S = q - 1                               # fantasy slots
    T = m + S
    ls = jnp.exp(state.params.log_lengthscale)
    sv = jnp.exp(state.params.log_signal_var)
    nv = jnp.exp(state.params.log_noise_var)
    kfn = KERNELS[kind]
    cand = cand.astype(jnp.float32)
    y_raw = y_raw.astype(jnp.float32)
    best_y = jnp.asarray(best_y, jnp.float32)

    # the one O(M·m·d) pass over the whole candidate pool (LHS + local
    # ball + axis sweeps fused): cross-Gram against the training block —
    # the Pallas tile kernel's natural shape
    if use_pallas and kind == "matern52":
        from repro.kernels.gp_gram.ops import matern52_cross
        k_cx = matern52_cross(cand, state.x, ls, sv)        # [M, m]
    else:
        k_cx = kfn(cand, state.x, ls, sv)                   # [M, m]

    # fixed-shape working set; inactive fantasy rows are identity rows of
    # L with zeroed cross entries, so prefix arithmetic is exact
    chol0 = jnp.zeros((T, T), jnp.float32)
    chol0 = chol0.at[:m, :m].set(state.chol)
    if S:
        fdiag = jnp.arange(m, T)
        chol0 = chol0.at[fdiag, fdiag].set(1.0)
    real = jnp.arange(m) < n                # real rows of the padded state
    noise_ss = _jitter(nv, sv)              # _build's diagonal, exactly

    # forward-substitution state, computed ONCE against the fitted block
    # and grown one row per pick.  Appending a Cholesky row leaves every
    # existing forward-solve entry untouched, so the O(n²·M) candidate
    # solve is paid once — each scan step only appends its own row:
    #   V [T, M] = L⁻¹ Kᵀ(X, cand)      (posterior-variance vectors)
    #   a [T]    = L⁻¹ (masked raw y)    (mean numerator, raw scale)
    #   b [T]    = L⁻¹ (active mask)     (mean's standardization shift)
    # mean_s = Vᵀ(a − μ·b)/σ exactly reproduces kq @ K⁻¹ys: ys is linear
    # in the raw targets and the active-row indicator, and the per-pick
    # re-standardization (μ, σ over real+fantasy targets — what the
    # rebuild path's _prepare recomputes every condition call) only mixes
    # those two solved vectors.
    y_masked = jnp.where(real, y_raw, 0.0)
    v0 = jnp.zeros((T, M), jnp.float32)
    v0 = v0.at[:m, :].set(jax.scipy.linalg.solve_triangular(
        state.chol, k_cx.T, lower=True))
    a0 = jnp.zeros((T,), jnp.float32)
    a0 = a0.at[:m].set(jax.scipy.linalg.solve_triangular(
        state.chol, y_masked, lower=True))
    b0 = jnp.zeros((T,), jnp.float32)
    b0 = b0.at[:m].set(jax.scipy.linalg.solve_triangular(
        state.chol, real.astype(jnp.float32), lower=True))

    carry0 = (
        chol0, v0, a0, b0,
        jnp.zeros((S,), jnp.float32),       # fantasy raw targets
        jnp.zeros((S, d_dim), jnp.float32),  # fantasy inputs
        jnp.zeros((M,), bool),              # taken mask
    )

    def step(carry, j):
        chol, v, a, b, y_f, x_f, taken = carry
        active = jnp.arange(S) < j if S else jnp.zeros((0,), bool)
        # per-pick re-standardization over real + fantasy targets
        w = jnp.concatenate([real, active]).astype(jnp.float32)
        yr = jnp.concatenate([y_masked, jnp.where(active, y_f, 0.0)])
        cnt = jnp.sum(w)
        mu_y = jnp.sum(yr) / cnt            # masked entries are zero
        std_y = jnp.sqrt(jnp.sum(w * (yr - mu_y) ** 2) / cnt)
        std_y = jnp.where(std_y < 1e-12, 1.0, std_y)

        mean_s = _mm(v.T, a - mu_y * b) / std_y
        var_s = jnp.maximum(sv - jnp.sum(v * v, axis=0), 1e-12)
        mean = mean_s * std_y + mu_y
        std = jnp.sqrt(var_s) * std_y

        if acquisition == "ei":
            std_c = jnp.maximum(std, 1e-9)
            imp = best_y - xi - mean
            z = imp / std_c
            cdf = 0.5 * (1 + jax.scipy.special.erf(z / math.sqrt(2)))
            pdf = jnp.exp(-0.5 * z * z) / math.sqrt(2 * math.pi)
            acq = imp * cdf + std_c * pdf
        else:                               # ucb (minimization, negated)
            acq = -(mean - 2.0 * std)
        acq = jnp.where(taken, -jnp.inf, acq)
        i = jnp.argmax(acq)
        taken = taken.at[i].set(True)

        if S:                               # fantasy-append (skipped q=1)
            x_new = cand[i]
            lie = mean[i] if fantasy == "believer" else best_y
            k_f_new = jnp.where(active, kfn(x_new[None], x_f, ls, sv)[0],
                                0.0)
            k_vec = jnp.concatenate([k_cx[i], k_f_new])
            l, dg = chol_append(chol, k_vec, sv + noise_ss)
            slot = jnp.minimum(j, S - 1)
            row = m + slot
            grow = j < S                    # the last pick appends nothing
            chol = jnp.where(grow, chol.at[row, :].set(l.at[row].set(dg)),
                             chol)
            # grow the forward-substitution state by the appended row
            col_c = kfn(cand, x_new[None], ls, sv)[:, 0]
            v = jnp.where(grow, v.at[row, :].set((col_c - _mm(l, v)) / dg), v)
            a = jnp.where(grow, a.at[row].set((lie - _mm(l, a)) / dg), a)
            b = jnp.where(grow, b.at[row].set((1.0 - _mm(l, b)) / dg), b)
            y_f = jnp.where(grow, y_f.at[slot].set(lie), y_f)
            x_f = jnp.where(grow, x_f.at[slot, :].set(x_new), x_f)
        return (chol, v, a, b, y_f, x_f, taken), i

    _, picks = jax.lax.scan(step, carry0, jnp.arange(q))
    return picks


# ---------------------------------------------------------------------------
# sharded q-batch selection (multi-device candidate pool)
# ---------------------------------------------------------------------------

_INT32_MAX = np.iinfo(np.int32).max


def _select_scan_sharded(state: GPState, cand_l, taken0_l, y_raw, n, best_y,
                         xi, *, q: int, kind: str, fantasy: str,
                         acquisition: str, use_pallas: bool,
                         axis: str = "pool"):
    """Shard-local body of :func:`select_batch_sharded`.

    Runs under ``jax.shard_map`` with ``cand_l`` [Ml, d] the local
    shard of the pool and everything else replicated.  Mirrors
    :func:`select_batch` step for step; the only cross-device traffic per
    pick is the argmax reduction (one pmax + one pmin) and three masked
    psum gathers of the winner's row — O(m + d) floats, independent of
    pool size.

    Bit-exactness contract: the replicated carry (chol/a/b/fantasy
    block) sees exactly the arithmetic of the single-device path, and the
    per-candidate columns (v, mean, acq) are computed per shard with the
    same per-column ops.  The collective argmax reproduces jnp.argmax's
    first-occurrence tie-break: take the max acquisition via ``pmax``,
    then the *smallest global index* attaining it via ``pmin`` (losing
    shards contribute int32-max).  Exactly one shard owns the winner, so
    each masked psum adds the winner's row to zeros — no rounding.
    """
    m, d_dim = state.x.shape
    Ml = cand_l.shape[0]
    S = q - 1
    T = m + S
    ls = jnp.exp(state.params.log_lengthscale)
    sv = jnp.exp(state.params.log_signal_var)
    nv = jnp.exp(state.params.log_noise_var)
    kfn = KERNELS[kind]
    cand_l = cand_l.astype(jnp.float32)
    y_raw = y_raw.astype(jnp.float32)
    best_y = jnp.asarray(best_y, jnp.float32)
    idx0 = jax.lax.axis_index(axis).astype(jnp.int32) * Ml

    if use_pallas and kind == "matern52":
        from repro.kernels.gp_gram.ops import matern52_cross
        k_cx = matern52_cross(cand_l, state.x, ls, sv)      # [Ml, m]
    else:
        k_cx = kfn(cand_l, state.x, ls, sv)                 # [Ml, m]

    chol0 = jnp.zeros((T, T), jnp.float32)
    chol0 = chol0.at[:m, :m].set(state.chol)
    if S:
        fdiag = jnp.arange(m, T)
        chol0 = chol0.at[fdiag, fdiag].set(1.0)
    real = jnp.arange(m) < n
    noise_ss = _jitter(nv, sv)

    y_masked = jnp.where(real, y_raw, 0.0)
    v0 = jnp.zeros((T, Ml), jnp.float32)
    v0 = v0.at[:m, :].set(jax.scipy.linalg.solve_triangular(
        state.chol, k_cx.T, lower=True))
    a0 = jnp.zeros((T,), jnp.float32)
    a0 = a0.at[:m].set(jax.scipy.linalg.solve_triangular(
        state.chol, y_masked, lower=True))
    b0 = jnp.zeros((T,), jnp.float32)
    b0 = b0.at[:m].set(jax.scipy.linalg.solve_triangular(
        state.chol, real.astype(jnp.float32), lower=True))

    carry0 = (
        chol0, v0, a0, b0,
        jnp.zeros((S,), jnp.float32),
        jnp.zeros((S, d_dim), jnp.float32),
        taken0_l,                           # pool pads pre-marked taken
    )

    def step(carry, j):
        chol, v, a, b, y_f, x_f, taken = carry
        active = jnp.arange(S) < j if S else jnp.zeros((0,), bool)
        w = jnp.concatenate([real, active]).astype(jnp.float32)
        yr = jnp.concatenate([y_masked, jnp.where(active, y_f, 0.0)])
        cnt = jnp.sum(w)
        mu_y = jnp.sum(yr) / cnt
        std_y = jnp.sqrt(jnp.sum(w * (yr - mu_y) ** 2) / cnt)
        std_y = jnp.where(std_y < 1e-12, 1.0, std_y)

        mean_s = _mm(v.T, a - mu_y * b) / std_y             # [Ml]
        var_s = jnp.maximum(sv - jnp.sum(v * v, axis=0), 1e-12)
        mean = mean_s * std_y + mu_y
        std = jnp.sqrt(var_s) * std_y

        if acquisition == "ei":
            std_c = jnp.maximum(std, 1e-9)
            imp = best_y - xi - mean
            z = imp / std_c
            cdf = 0.5 * (1 + jax.scipy.special.erf(z / math.sqrt(2)))
            pdf = jnp.exp(-0.5 * z * z) / math.sqrt(2 * math.pi)
            acq = imp * cdf + std_c * pdf
        else:
            acq = -(mean - 2.0 * std)
        acq = jnp.where(taken, -jnp.inf, acq)

        # collective first-occurrence argmax over the global pool
        li = jnp.argmax(acq).astype(jnp.int32)
        lmax = acq[li]
        gmax = jax.lax.pmax(lmax, axis)
        gi = jax.lax.pmin(
            jnp.where(lmax == gmax, idx0 + li, _INT32_MAX), axis)
        off = gi - idx0
        has = (off >= 0) & (off < Ml)       # this shard owns the winner
        il = jnp.clip(off, 0, Ml - 1)
        taken = jnp.where(has, taken.at[il].set(True), taken)

        # replicate the winner's row: exactly one shard contributes
        x_new = jax.lax.psum(
            jnp.where(has, cand_l[il], jnp.zeros((d_dim,), jnp.float32)),
            axis)
        k_ci = jax.lax.psum(
            jnp.where(has, k_cx[il], jnp.zeros((m,), jnp.float32)), axis)
        mean_i = jax.lax.psum(jnp.where(has, mean[il], 0.0), axis)

        if S:
            lie = mean_i if fantasy == "believer" else best_y
            k_f_new = jnp.where(active, kfn(x_new[None], x_f, ls, sv)[0],
                                0.0)
            k_vec = jnp.concatenate([k_ci, k_f_new])
            l, dg = chol_append(chol, k_vec, sv + noise_ss)
            slot = jnp.minimum(j, S - 1)
            row = m + slot
            grow = j < S
            chol = jnp.where(grow, chol.at[row, :].set(l.at[row].set(dg)),
                             chol)
            col_c = kfn(cand_l, x_new[None], ls, sv)[:, 0]
            v = jnp.where(grow, v.at[row, :].set((col_c - _mm(l, v)) / dg), v)
            a = jnp.where(grow, a.at[row].set((lie - _mm(l, a)) / dg), a)
            b = jnp.where(grow, b.at[row].set((1.0 - _mm(l, b)) / dg), b)
            y_f = jnp.where(grow, y_f.at[slot].set(lie), y_f)
            x_f = jnp.where(grow, x_f.at[slot, :].set(x_new), x_f)
        return (chol, v, a, b, y_f, x_f, taken), gi

    _, picks = jax.lax.scan(step, carry0, jnp.arange(q))
    return picks


# compiled sharded selectors, keyed by (devices, q, kind, fantasy,
# acquisition, use_pallas) — shapes retrace under jit
_SHARDED_CACHE: dict = {}


def _sharded_fn(devs, q, kind, fantasy, acquisition, use_pallas):
    key = (devs, q, kind, fantasy, acquisition, use_pallas)
    fn = _SHARDED_CACHE.get(key)
    if fn is not None:
        return fn
    from jax.sharding import Mesh, PartitionSpec as P
    body = partial(_select_scan_sharded, q=q, kind=kind, fantasy=fantasy,
                   acquisition=acquisition, use_pallas=use_pallas)
    mesh = Mesh(np.array(devs), ("pool",))
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P("pool"), P("pool"), P(), P(), P(), P()),
        out_specs=P(), check_vma=False))
    _SHARDED_CACHE[key] = fn
    return fn


def select_batch_sharded(state: GPState, cand, y_raw, n, best_y, q: int,
                         kind: str = "matern52", fantasy: str = "liar",
                         acquisition: str = "ei", xi: float = 0.01,
                         use_pallas: bool = False, devices=None):
    """:func:`select_batch` with the candidate pool sharded over devices.

    The pool (LHS + local ball + axis sweeps, [M, d]) is split row-wise
    across ``devices`` (default: all host devices) by ``jax.shard_map``;
    each device scores its shard against the replicated posterior and a
    masked all-reduce argmax picks every winner.  Per-pick traffic is
    O(m + d) — constant in pool size — so M can grow with
    ``jax.device_count()`` at constant wall-clock.

    Picks are bit-identical to :func:`select_batch` on the same pool (see
    :func:`_select_scan_sharded` for the tie-break argument).  The pool
    is padded to a multiple of the device count with unit-cube midpoints
    pre-marked taken, so padding never changes a pick.
    """
    devs = tuple(devices) if devices is not None else tuple(jax.devices())
    nd = len(devs)
    cand = jnp.asarray(cand, jnp.float32)
    M, d = cand.shape
    Ml = -(-M // nd)
    Mp = Ml * nd
    if Mp > M:
        cand = jnp.concatenate(
            [cand, jnp.full((Mp - M, d), 0.5, jnp.float32)])
    taken0 = jnp.arange(Mp) >= M
    fn = _sharded_fn(devs, q, kind, fantasy, acquisition, bool(use_pallas))
    return fn(state, cand, taken0, jnp.asarray(y_raw, jnp.float32),
              jnp.asarray(n, jnp.int32), jnp.asarray(best_y, jnp.float32),
              jnp.asarray(xi, jnp.float32))
