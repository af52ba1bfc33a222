"""Tune driver: Sapphire tuning sessions back to back on one chip.

Each session is ``Sapphire(arch, shape, ...)`` seeded from (``--seed``,
session index), driven through its three stages (rank, search, validate)
with the configuration's ``AnalyticEvaluator`` behind a thin wrapper that
counts evaluations and marks each evaluator call.  Set-up runs session 0
whole, which compiles every program the traffic uses at its only shapes
(the GP's size is pinned by the budget, the candidate pool by the
configuration).  The window then runs sessions 1, 2, … until ``--seconds``
have passed; the first evaluator call after that closes the window and
ends the session in flight.

End-to-end: ``tune_evals_per_s`` counts every evaluation told in the
window over the window; ``tune_ask_ms_p95`` is the 95th percentile of the
wall time of every search-stage ``ask`` that started in the window.

The check, once the window has closed: for a sample of the window's GP
asks drawn from the seed, with the ask of the most observations always in
it, the float64 reference judges each of the program's q-EI picks
(``pick_gap``) and that the GP saw exactly the values told so far
(``told_gap``, exact); for a sample of the window's rankings it recomputes
the Lasso path (``lasso_gap``).
"""

from __future__ import annotations

import math
import shutil
import time

import jax
import numpy as np

import flops as flopsmod
import harness

ASK_E2E = "tune_ask_ms_p95"
RATE_E2E = "tune_evals_per_s"


class WindowClosed(Exception):
    """Raised by the evaluator wrapper once the window has closed."""


class Evaluator:
    """The configuration's evaluator, counted and marked per call."""

    accepts_seeds = True

    def __init__(self, inner):
        self.inner = inner
        self.deadline = math.inf
        self.closed_at = None
        self.evaluations = 0

    def evaluate_batch_detailed(self, configs, seeds=None):
        if self.closed_at is not None or time.monotonic() >= self.deadline:
            if self.closed_at is None:
                self.closed_at = time.monotonic()
            raise WindowClosed()
        with jax.profiler.TraceAnnotation("bench.evaluate"):
            out = self.inner.evaluate_batch_detailed(configs, seeds=seeds)
        self.evaluations += len(out[0])
        return out

    def evaluate_batch(self, configs):
        return self.evaluate_batch_detailed(configs)[0]


class Recorder:
    """Patches, for this process only, ``BOStrategy.ask`` (timed, with the
    values told so far), ``gp.select_batch`` (inputs and picks) and the
    ranking's ``lasso_path`` (inputs and path).  Arrays are kept by
    reference; nothing is copied to the host inside the window."""

    def __init__(self):
        from repro.core import gp, ranking, strategy
        self.asks = []          # (t_start, seconds, session)
        self.selects = []       # dict per select_batch call
        self.lassos = []        # dict per ranking
        self.session = -1
        self._current = None
        orig_ask = strategy.BOStrategy.ask
        orig_select = gp.select_batch
        orig_lasso = ranking.lasso_path
        rec = self

        def ask(strat, n=None):
            told = (strat.space, list(strat.trace.configs),
                    list(strat.trace.values))
            rec._current = told
            t = time.monotonic()
            with jax.profiler.TraceAnnotation("bench.ask"):
                out = orig_ask(strat, n)
            rec.asks.append((t, time.monotonic() - t, rec.session))
            rec._current = None
            return out

        def select(state, cand, y_raw, n, best_y, q, **kw):
            idx = orig_select(state, cand, y_raw, n, best_y, q, **kw)
            rec.selects.append({"state": state, "cand": cand, "y_raw": y_raw,
                                "n": n, "best_y": best_y, "q": q, "kw": kw,
                                "picks": idx, "told": rec._current,
                                "session": rec.session})
            return idx

        def lasso(x, y, *a, **kw):
            lams, betas = orig_lasso(x, y, *a, **kw)
            rec.lassos.append({"x": x, "y": y, "lams": lams, "betas": betas,
                               "session": rec.session})
            return lams, betas

        self._undo = [(strategy.BOStrategy, "ask", orig_ask),
                      (gp, "select_batch", orig_select),
                      (ranking, "lasso_path", orig_lasso)]
        strategy.BOStrategy.ask = ask
        gp.select_batch = select
        ranking.lasso_path = lasso

    def restore(self):
        for obj, name, orig in self._undo:
            setattr(obj, name, orig)


def _session(cell, seed: int, index: int, evaluator):
    from repro.core.strategy import BOConfig
    from repro.core.tuner import Sapphire
    t = cell.traffic
    s_seed = harness.seed_words(seed, index)
    s = Sapphire(arch=cell.config["arch"], shape=t["shape"],
                 multi_pod=t["mesh"] == "MULTI_POD", top_k=t["top_k"],
                 n_rank_samples=t["n_rank_samples"],
                 batch_size=t["batch_size"], noise_sigma=t["noise_sigma"],
                 bo_config=BOConfig(seed=s_seed, **t["bo"]), seed=s_seed,
                 evaluator=evaluator)
    _, _, _, space, _, _, ctrl = s._setup()
    with jax.profiler.TraceAnnotation("bench.rank"):
        rk = s.rank_stage(ctrl, space)
    with jax.profiler.TraceAnnotation("bench.search"):
        s.search_stage(ctrl, space, rk)
    with jax.profiler.TraceAnnotation("bench.validate"):
        s.validate_stage(ctrl, space)


def make_evaluator(cell, seed: int, index: int):
    from repro.configs import get_config
    from repro.core import costmodel
    from repro.core.evaluators import AnalyticEvaluator
    from repro.models.config import SHAPES_BY_NAME
    t = cell.traffic
    return Evaluator(AnalyticEvaluator(
        get_config(cell.config["arch"]), SHAPES_BY_NAME[t["shape"]],
        getattr(costmodel, t["mesh"]), noise_sigma=t["noise_sigma"],
        seed=harness.seed_words(seed, index)))


def gp_ask_flops(n: int, pool: int, d: int, q: int, fit_steps: int) -> float:
    """Operations of one GP ask: the hyperparameter fit (forward and
    backward of the marginal likelihood per Adam step: Gram, Cholesky,
    solve), the posterior build and the q-EI selection (cross-Gram,
    triangular solve of the pool, q picks of mean/variance updates and
    Cholesky appends)."""
    chol = n ** 3 / 3.0
    gram = flopsmod.gp_gram(n, n, d)[0]
    fit = fit_steps * 3.0 * (gram + chol + 2.0 * n * n)
    build = gram + chol + 4.0 * n * n
    tt = n + q - 1
    select = flopsmod.gp_gram(pool, n, d)[0] + n * n * pool \
        + q * (6.0 * tt * pool + tt * tt + 2.0 * pool * d)
    return fit + build + select


# ---------------------------------------------------------------------------
# faults planted under the timed path (tests and calibration only); each
# patches the program for this process and returns the undo
# ---------------------------------------------------------------------------

def _patch(obj, name, new):
    orig = getattr(obj, name)
    setattr(obj, name, new(orig))
    return lambda: setattr(obj, name, orig)


def fault_altered():
    """The q-EI's first pick of every ask moved to the next candidate."""
    from repro.core import gp

    def new(orig):
        def select(state, cand, *a, **kw):
            idx = orig(state, cand, *a, **kw)
            return idx.at[0].set((idx[0] + 1) % cand.shape[0])
        return select
    return _patch(gp, "select_batch", new)


def fault_unchanged():
    """The GP's posterior is never refit: every ask reuses the first."""
    from repro.core import gp
    first = []

    def new(orig):
        def fit(*a, **kw):
            if not first:
                first.append(orig(*a, **kw))
            return first[0]
        return fit
    return _patch(gp, "fit", new)


def fault_half_batch():
    """The GP is fitted on every other observation only."""
    from repro.core import gp

    def new(orig):
        def fit(x, y, *a, **kw):
            return orig(x[::2], y[::2], *a, **kw)
        return fit
    return _patch(gp, "fit", new)


FAULTS = {"altered": fault_altered, "unchanged": fault_unchanged,
          "half_batch": fault_half_batch}


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def _sample(items, k: int, rng, key=None):
    if not items:
        return []
    idx = set(rng.choice(len(items), size=min(k, len(items)),
                         replace=False).tolist())
    if key is not None:
        idx.add(int(np.argmax([key(x) for x in items])))
    return [items[i] for i in sorted(idx)]


def check(cell, seed: int, rec: Recorder, warm: dict, control=False):
    """The numbers compared, from the window's asks and rankings; with
    ``control``, also the control's numbers on the same asks and rankings."""
    ref = cell.reference()
    t = cell.traffic
    rng = np.random.default_rng(harness.seed_words(seed, 7))
    selects = [s for s in rec.selects[warm["selects"]:]]
    lassos = rec.lassos[warm["lassos"]:]
    pick_gap, told_gap = 0.0, 0.0
    picks_checked = 0
    ctl = {"pick_gap": 0.0, "lasso_gap": 0.0}
    for s in _sample(selects, t["check_asks"], rng, key=lambda s: s["n"]):
        st = s["state"]
        n = int(s["n"])
        x = np.asarray(st.x, np.float64)[:n]
        y_raw = np.asarray(s["y_raw"], np.float32)[:n]
        if s["told"] is None or len(s["told"][2]) != n:
            told_gap = math.inf
            continue
        space, configs, values = s["told"]
        x_told = space.encode_batch(configs).astype(np.float32)
        y_told = np.log(np.maximum(np.asarray(values, np.float64), 1e-12)
                        ).astype(np.float32)
        told_gap = max(told_gap, float(np.max(np.abs(y_told - y_raw))),
                       float(np.max(np.abs(x_told - x.astype(np.float32)))))
        x = x_told.astype(np.float64)
        p = st.params
        ls = np.exp(np.asarray(p.log_lengthscale, np.float64))
        sv = float(np.exp(np.asarray(p.log_signal_var, np.float64)))
        nv = float(np.exp(np.asarray(p.log_noise_var, np.float64)))
        picks = np.asarray(s["picks"])[: int(s["q"])]
        gaps = ref.qei_gaps(x, y_raw.astype(np.float64), ls, sv, nv,
                            np.asarray(s["cand"], np.float64),
                            float(s["best_y"]), picks)
        pick_gap = max(pick_gap, float(np.max(gaps)))
        picks_checked += len(picks)
        if control:
            args = (x, y_raw.astype(np.float64), ls, sv, nv,
                    np.asarray(s["cand"], np.float64), float(s["best_y"]))
            mine = ref.qei_picks_f32(*args, q=len(picks))
            ctl["pick_gap"] = max(ctl["pick_gap"], float(np.max(
                ref.qei_gaps(*args, mine))))
    lasso_gap = 0.0
    for rk in _sample(lassos, t["check_rankings"], rng):
        want = ref.lasso_path(rk["x"], rk["y"], rk["lams"], max_iter=300)
        got = np.asarray(rk["betas"], np.float64)
        scale = max(float(np.max(np.abs(want))), 1e-12)
        lasso_gap = max(lasso_gap, float(np.max(np.abs(got - want))) / scale)
        if control:
            mine = ref.lasso_path_f32(rk["x"], rk["y"], rk["lams"], 300)
            ctl["lasso_gap"] = max(ctl["lasso_gap"], float(
                np.max(np.abs(mine - want))) / scale)
    if picks_checked == 0:
        pick_gap = math.inf
    if not lassos:
        lasso_gap = math.inf
    return {"pick_gap": pick_gap, "told_gap": told_gap,
            "lasso_gap": lasso_gap}, picks_checked, \
        (ctl if control else None)


def run(cell, *, seed: int, seconds: float, trace: bool, t0: float,
        devices, fault: str = None, control: bool = False) -> dict:
    import trace as tracemod
    undo = FAULTS[fault]() if fault is not None else None
    rec = Recorder()
    tdir = None
    if trace:
        tdir = harness.SCRATCH / "trace" / cell.name
        shutil.rmtree(tdir, ignore_errors=True)
    try:
        win = run_window(cell, seed, seconds, rec, tdir)
    finally:
        rec.restore()
        if undo is not None:
            undo()
    device = harness.device_info(devices)
    asks = [(a, s) for a, s, sess in rec.asks[win["warm"]["asks"]:]]
    ask_ms = [1e3 * s for _, s in asks]
    p95 = float(np.percentile(ask_ms, 95)) if ask_ms else math.inf
    ctx = None
    if trace:
        summary = tracemod.reduce(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        t = cell.traffic
        bo = t["bo"]
        from repro.core import gp
        m = gp._bucket(bo["n_init"] + bo["n_iter"])
        pool = bo["n_candidates"] + bo["n_local"] + 5 * t["gp_dims"]
        q = t["batch_size"]
        gp_asks = rec.selects[win["warm"]["selects"]:]
        first = {}
        for s in gp_asks:
            first.setdefault(s["session"], s)
        steps_warm = max(bo["fit_steps"] // 3, 20)
        ask_flops = sum(gp_ask_flops(
            m, pool, t["gp_dims"], q,
            bo["fit_steps"] if first[s["session"]] is s else steps_warm)
            for s in gp_asks)
        ctx = {**summary, "gp_asks": len(gp_asks),
               "evaluations": win["evaluations"],
               "sessions_started": len({s for _, _, s in
                                        rec.asks[win["warm"]["asks"]:]}),
               "ask_seconds": sum(s for _, s in asks),
               "ask_flops": ask_flops,
               "gram_calls": {"build": flopsmod.gp_gram(m, m, t["gp_dims"]),
                              "select": flopsmod.gp_gram(pool, m,
                                                         t["gp_dims"])},
               "peak": harness.peak(device["kind"]),
               "moves": ASK_E2E}
    values, picks_checked, ctl = check(cell, seed, rec, win["warm"],
                                       control)
    checks, correct = harness.judge(values, cell.limits)
    return {"correct": correct, "attempted": win["evaluations"],
            "failed": 0,
            "end_to_end": {RATE_E2E: win["evaluations"] / win["window_s"],
                           ASK_E2E: p95,
                           "setup_s": win["t_start"] - t0},
            "checks": checks, "device": device, "trace": ctx,
            "window": {k: win[k] for k in ("window_s", "evaluations",
                                           "sessions")},
            "asks": len(ask_ms), "picks_checked": picks_checked,
            **({"control": ctl} if control else {})}


def run_window(cell, seed, seconds, rec, tdir):
    """Set-up session, then the window (traced when ``tdir`` is given)."""
    ev = make_evaluator(cell, seed, 0)
    rec.session = 0
    _session(cell, seed, 0, ev)
    warm = {"asks": len(rec.asks), "selects": len(rec.selects),
            "lassos": len(rec.lassos)}
    if tdir is not None:
        jax.profiler.start_trace(str(tdir))
    t_start = time.monotonic()
    deadline = t_start + seconds
    evaluations, sessions, closed_at, index = 0, 0, None, 1
    with jax.profiler.TraceAnnotation("bench.window"):
        while closed_at is None:
            ev = make_evaluator(cell, seed, index)
            ev.deadline = deadline
            rec.session = index
            with jax.profiler.TraceAnnotation("bench.session"):
                try:
                    _session(cell, seed, index, ev)
                    sessions += 1
                except Exception:
                    if ev.closed_at is None:
                        raise
            evaluations += ev.evaluations
            closed_at = ev.closed_at
            if closed_at is None and time.monotonic() >= deadline:
                closed_at = time.monotonic()
            index += 1
        jax.effects_barrier()
    if tdir is not None:
        jax.profiler.stop_trace()
    return {"t_start": t_start, "window_s": closed_at - t_start,
            "evaluations": evaluations, "sessions": sessions, "warm": warm}
