"""Sharded candidate scoring + the kernel-autotune dogfood loop.

    PYTHONPATH=src python -m benchmarks.perf_multi_device [--tiny]

Two arms, two claims — both asserted even under ``--tiny`` (this is the
CI gate for PR 6):

* **scoring** — ``gp.select_batch_sharded`` splits the q-EI candidate
  pool row-wise over ``jax.devices()``; per-pick cross-device traffic is
  one masked all-reduce argmax plus three O(m + d) psum gathers, so the
  pool grows with the device count at ~constant wall-clock.  Device
  count is forced via ``XLA_FLAGS=--xla_force_host_platform_device_count``
  which must be set *before* jax imports, so each arm runs in a
  subprocess (re-invoking this module with ``--worker``);
  ``--xla_cpu_multi_thread_eigen=false`` stops single-device XLA from
  eating every core, which would mask device scaling on a CPU host.
  Acceptance: >= 1.6x scored-candidates/sec at 2 devices vs 1, and at an
  equal pool the sharded picks are bit-identical to ``select_batch``.
  Forced host devices are *threads sharing the machine's cores*, so the
  throughput gate only means something when the host actually grants
  >= 2 cores (the compiled program is verifiably parallel either way:
  num_partitions=2, per-shard [Ml] tensors, all-reduces only over
  scalars and [m]/[d] rows).  On a single-core host (CPU affinity, CI
  sandboxes) the ratio is reported but the gate is vacuous — the pick
  identity assertion, which is what correctness needs, always runs.

* **autotune** — the dogfood loop: :func:`repro.kernels.tune_kernel`
  tunes the gp_gram Pallas kernel's tiling through BO +
  ``Controller.run_async``, seeded with the shipped default.  The bench
  shape (n=136, d=8) sits off the 128 ladder, so the hand-picked square
  128 tile pads 136 -> 256 and runs a wasteful 2x2 grid; rectangular
  tiles under the same VMEM budget cover the rows in one stripe (~1.9x
  on this host).  Acceptance: the tuned config re-measured head-to-head
  is no slower than the hand-picked default (small tolerance for timer
  noise) — the tuner must at minimum *find* the default it was seeded
  with, and in practice beats it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _usable_cores() -> int:
    """Cores this process may actually run on (affinity-aware)."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:                   # non-Linux
        return os.cpu_count() or 1

# ---------------------------------------------------------------------------
# scoring arm: subprocess worker (device count is fixed at jax import)
# ---------------------------------------------------------------------------


def _worker(args) -> dict:
    """Time select_batch (1 device) / select_batch_sharded (N devices) on a
    pool of ``--pool`` candidates *per device*.  Runs inside the subprocess
    with XLA_FLAGS already applied; prints one ``RESULT {json}`` line."""
    import numpy as np

    import jax
    from repro.core import gp

    nd = jax.local_device_count()
    n, d, q = args.n, args.d, args.q
    rng = np.random.default_rng(0)
    x = rng.random((n, d))
    y = (np.sin(3 * x[:, 0]) + (x[:, 1] - 0.4) ** 2
         + 0.05 * rng.normal(size=n))
    pad_to = gp._bucket(n + q)
    st = gp.fit(x, y, steps=60, pad_to=pad_to)
    best_y = float(np.min(y))
    y_raw = np.zeros(int(st.x.shape[0]), np.float32)
    y_raw[:n] = y

    M = args.pool * nd                       # pool grows with device count
    cand = rng.random((M, d)).astype(np.float32)

    if nd == 1:
        fn = lambda: gp.select_batch(st, cand, y_raw, n, best_y, q)  # noqa
    else:
        fn = lambda: gp.select_batch_sharded(st, cand, y_raw, n,     # noqa
                                             best_y, q)
    idx = np.asarray(fn())                   # compile before timing

    same = True
    if nd > 1:
        # equal-pool identity: sharded picks == single-device picks, bit
        # for bit (the collective argmax has the same first-occurrence
        # tie-break as jnp.argmax)
        base = np.asarray(gp.select_batch(st, cand, y_raw, n, best_y, q))
        same = bool(np.array_equal(base, idx))

    best = math.inf                          # best-of-blocks: contention-
    for _ in range(4):                       # robust on shared CI boxes
        t0 = time.monotonic()
        for _ in range(args.repeats):
            np.asarray(fn())
        best = min(best, (time.monotonic() - t0) / args.repeats)

    print("RESULT " + json.dumps(
        {"devices": nd, "pool": M, "select_s": best,
         "cand_per_s": M / best, "same_picks": same}), flush=True)
    return 0


def _spawn_worker(nd: int, n: int, d: int, q: int, pool: int,
                  repeats: int) -> dict:
    env = dict(os.environ)
    # the worker measures forced host devices by design: CPU only, so it
    # never competes with this process for an attached accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={nd} "
                        "--xla_cpu_multi_thread_eigen=false")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(REPO / "src"), str(REPO),
                    env.get("PYTHONPATH", "")) if p)
    cmd = [sys.executable, "-m", "benchmarks.perf_multi_device", "--worker",
           "--n", str(n), "--d", str(d), "--q", str(q),
           "--pool", str(pool), "--repeats", str(repeats)]
    out = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                         text=True, timeout=900)
    if out.returncode != 0:
        raise RuntimeError(f"worker nd={nd} failed:\n{out.stdout}"
                           f"\n{out.stderr}")
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"worker nd={nd} printed no RESULT line:"
                       f"\n{out.stdout}\n{out.stderr}")


def bench_scoring(n: int, d: int, q: int, pool: int, repeats: int,
                  devices: int = 2) -> dict:
    # the >= 1.6x throughput gate is vacuous without real parallelism;
    # PERF_REQUIRE_CORES (set by CI) turns that silent skip into a loud
    # failure so a mis-provisioned runner can't fake a pass — checked
    # before the workers spend minutes measuring
    required = int(os.environ.get("PERF_REQUIRE_CORES", "0"))
    if _usable_cores() < required:
        raise RuntimeError(
            f"PERF_REQUIRE_CORES={required} but the host grants only "
            f"{_usable_cores()} core(s): the multi-device throughput gate "
            "would pass vacuously — run on a multi-core machine")
    one = _spawn_worker(1, n, d, q, pool, repeats)
    many = _spawn_worker(devices, n, d, q, pool, repeats)
    ratio = many["cand_per_s"] / one["cand_per_s"]
    cores = _usable_cores()
    print(f"  1 device : pool {one['pool']:6d}  "
          f"{one['select_s'] * 1e3:8.2f} ms/batch  "
          f"{one['cand_per_s']:10.0f} cand/s")
    print(f"  {many['devices']} devices: pool {many['pool']:6d}  "
          f"{many['select_s'] * 1e3:8.2f} ms/batch  "
          f"{many['cand_per_s']:10.0f} cand/s  "
          f"-> {ratio:.2f}x throughput "
          f"(equal-pool picks identical: {many['same_picks']})")
    if cores < devices:
        print(f"  [host grants {cores} core(s) for {devices} forced "
              "devices: throughput gate not enforceable here]")
    return {"one": one, "many": many, "throughput_ratio": ratio,
            "cores": cores,
            "same_picks": bool(many["same_picks"])}


# ---------------------------------------------------------------------------
# autotune arm: the dogfood loop, in-process
# ---------------------------------------------------------------------------


def bench_autotune(budget: int, repeats: int, head_repeats: int) -> dict:
    from repro.kernels.autotune import KernelEvaluator, tune_kernel

    out = tune_kernel("gp_gram", budget=budget, batch_size=2, seed=0,
                      repeats=repeats, warmup=1, fit_steps=60)
    print(f"  tuned   {out['best_config']}  "
          f"{out['best_value']:.3f} ms (search estimate)")
    print(f"  default {out['default_config']}  "
          f"{out['default_value']:.3f} ms (search estimate)")

    # head-to-head re-measure: same evaluator, same process, back to back
    # — the search-time estimates above were taken minutes apart
    ev = KernelEvaluator("gp_gram", repeats=head_repeats, warmup=2)
    tuned_ms = ev(out["best_config"])
    default_ms = ev(out["default_config"])
    speedup = default_ms / max(tuned_ms, 1e-12)
    print(f"  head-to-head: default {default_ms:.3f} ms, "
          f"tuned {tuned_ms:.3f} ms  -> {speedup:.2f}x")
    n_fail = sum(1 for r in out["db"].records if not r.ok)
    return {"best_config": out["best_config"],
            "default_config": out["default_config"],
            "tuned_ms": tuned_ms, "default_ms": default_ms,
            "speedup": speedup, "evals": len(out["trace"].values),
            "failed": n_fail}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke budgets (assertions stay on)")
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--n", type=int, default=48)
    ap.add_argument("--d", type=int, default=4)
    ap.add_argument("--q", type=int, default=4)
    ap.add_argument("--pool", type=int, default=4096)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)

    if args.worker:
        return _worker(args)

    from benchmarks.common import save

    if args.tiny:
        # n=96 keeps the per-shard solve (O(T²·Ml)) well above the
        # per-call dispatch + collective overhead, so the 2-device ratio
        # measures compute scaling, not fixed-cost amortization
        n, d, q, pool, repeats = 96, 4, 4, 4096, 5
        budget, tune_reps, head_reps = 12, 3, 8
    else:
        n, d, q, pool, repeats = 128, 8, 8, 8192, 8
        budget, tune_reps, head_reps = 24, 5, 12

    print("== sharded candidate scoring: 1 vs 2 forced host devices")
    scoring = bench_scoring(n, d, q, pool, repeats)

    print("== kernel-autotune dogfood: BO over gp_gram tiling")
    autotune = bench_autotune(budget, tune_reps, head_reps)

    save("perf_multi_device", {"scoring": scoring, "autotune": autotune})

    assert scoring["same_picks"], (
        "sharded picks diverged from select_batch at equal pool")
    if scoring["cores"] >= 2:
        assert scoring["throughput_ratio"] >= 1.6, (
            f"sharded scoring throughput {scoring['throughput_ratio']:.2f}x "
            "< 1.6x at 2 devices")
    assert autotune["tuned_ms"] <= autotune["default_ms"] * 1.15, (
        f"tuned config {autotune['tuned_ms']:.3f} ms slower than the "
        f"hand-picked default {autotune['default_ms']:.3f} ms")
    return 0


def run(quick: bool = False):
    """Entry for benchmarks.run."""
    main(["--tiny"] if quick else [])


if __name__ == "__main__":
    raise SystemExit(main())
