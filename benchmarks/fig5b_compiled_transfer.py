import os
import sys

if __name__ == "__main__":
    # standalone: claim the production device count before jax loads
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512"
                               ).strip()

"""Paper Fig. 5, strongest form: transfer validated on the COMPILED artifact.

fig5_effectiveness rescores recommendations on the multi-pod *analytic*
evaluator; this benchmark closes the loop on the **product cluster
proper**: the default config and the SAPPHIRE recommendation are applied
to the real train step, ``jit().lower().compile()``d on the production
mesh, and scored by the compiled roofline — the paper's "recommended
settings based on the test environment work similarly well in the large
product environment" claim, measured on the artifact that would actually
run.

Needs 512 placeholder devices => must own the process.  When invoked
from ``benchmarks.run`` (jax already initialized at 1 device) it
re-executes itself in a subprocess.
"""

import json
import subprocess


def _inner(quick: bool, arch: str, shape: str):
    """Hybrid tuning, the paper-faithful design: the paper's test cluster
    is a REAL (small) deployment, not a model — so the ranking phase uses
    the cheap analytic evaluator (hundreds of probes) and the BO phase
    probes the REAL compiled artifact (each probe = one XLA compile, the
    analogue of one Rados-bench run)."""
    from benchmarks.common import save
    from repro.configs import get_config
    from repro.core import bo, ranking
    from repro.core.bo import BOConfig
    from repro.core.costmodel import SINGLE_POD
    from repro.core.evaluators import AnalyticEvaluator, CompiledEvaluator
    from repro.core.knobs import clean_space
    from repro.models.config import SHAPES_BY_NAME

    model_cfg = get_config(arch)
    cell = SHAPES_BY_NAME[shape]
    space, pins, report = clean_space(model_cfg, cell, SINGLE_POD)

    # §3.3 ranking on the analytic test model (cheap)
    an = AnalyticEvaluator(model_cfg, cell, SINGLE_POD, noise_sigma=0.025,
                           seed=0)
    rk = ranking.rank(space, an, n_samples=120 if quick else 300, seed=0,
                      stability_rounds=0 if quick else 8)
    k = 6 if quick else 8
    sub = rk.top_space(k)
    base = space.default_config()

    # §3.4 BO against the COMPILED evaluator (expensive, deterministic)
    comp_ev = CompiledEvaluator(model_cfg, cell)

    def objective(c):
        full = dict(base)
        full.update(c)
        return comp_ev(space.project(full))

    n_iter = 6 if quick else 12
    best, best_v, trace, _ = bo.minimize(
        objective, sub,
        BOConfig(n_init=4 if quick else 6, n_iter=n_iter,
                 n_candidates=256, fit_steps=60, seed=0,
                 dynamic_boundary=False))
    default_v = comp_ev(space.project(base))
    speedup = default_v / best_v
    print(f"compiled default {default_v:.3f}s -> tuned {best_v:.3f}s "
          f"({speedup:.2f}x) after {comp_ev.calls} compiles")
    print("tuned knobs:", {kk: vv for kk, vv in best.items()})
    out = {"default_step_s": default_v, "tuned_step_s": best_v,
           "compiled_speedup": speedup, "tuned": best,
           "top_knobs": rk.top(k), "n_compiles": comp_ev.calls}
    save("fig5b_compiled_transfer", out)
    return out


def run(quick: bool = False, arch: str = "yi-6b", shape: str = "train_4k"):
    import jax  # noqa — probe whether this process already owns devices
    if len(jax.devices()) == 512:
        return _inner(quick, arch, shape)
    # jax initialized without the placeholder fleet: re-exec ourselves
    cmd = [sys.executable, "-m", "benchmarks.fig5b_compiled_transfer",
           "--arch", arch, "--shape", shape] + (["--quick"] if quick else [])
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    # the compiled fidelity lowers onto the forced host devices: CPU only
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(cmd, env=env, cwd=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    if proc.returncode != 0:
        raise RuntimeError("fig5b subprocess failed")
    from benchmarks.common import ARTIFACTS
    return json.loads((ARTIFACTS / "fig5b_compiled_transfer.json").read_text())


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--shape", default="train_4k")
    a = ap.parse_args()
    _inner(a.quick, a.arch, a.shape)
