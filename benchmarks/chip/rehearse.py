#!/usr/bin/env python3
"""Compile every cell's jitted programs at their real sizes for one chip of
a described TPU v5e, with no chip attached, and print what the compiler
says of each: the bytes of ``memory_analysis()``, whether a Mosaic
kernel (``tpu_custom_call``) is in the program, and which kernels the
trace reduction will find in it (``trace.kernel_ops``).

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [workload ...]

Nothing runs, so this says nothing of results or times.  The train cells
compile the system's step and the reference's step; the tune cells the
proposer's fit, posterior build and q-EI selection at the cell's padded
GP size and candidate pool.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

harness.ensure_src_on_path()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _gib(x) -> str:
    return f"{x / 2**30:.2f} GiB" if x >= 2**30 else f"{x / 2**20:.2f} MiB"


def report(name, compiled):
    import trace as tracemod
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    print(f"{name}: arguments {_gib(ma.argument_size_in_bytes)}, outputs "
          f"{_gib(ma.output_size_in_bytes)}, aliased "
          f"{_gib(ma.alias_size_in_bytes)}, temp "
          f"{_gib(ma.temp_size_in_bytes)}; tpu_custom_call "
          f"{'tpu_custom_call' in text}; kernels "
          f"{sorted(set(tracemod.kernel_ops(text).values()))}", flush=True)


def rehearse_train(cell, spec):
    import drivers_train as T
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.train.train_loop import make_train_step
    import generate
    import reference as R

    t = cell.traffic
    cfg = T.model_config(cell)
    rc = T.run_config(cell, get_config(cell.config["arch"]))
    model = Model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    state = jax.eval_shape(lambda: T.initial_state(
        generate.init_params(jax.random.key(0), shapes), shapes, rc))
    state = jax.tree.map(lambda s: spec(s.shape, s.dtype), state)
    batch = {k: spec((t["batch"], t["seq"]), jnp.int32)
             for k in ("tokens", "labels")}
    lr = float(t["optimizer"]["learning_rate"])
    step = jax.jit(make_train_step(model, rc, lr_schedule=lambda s: lr),
                   donate_argnums=(0,))
    report(f"{cell.name} step ({rc.attention_impl}, remat "
           f"{rc.remat_policy})", step.lower(state, batch).compile())

    ref = cell.reference()
    model_d = cell.config["model"]
    mm = R.Matmul("f32")
    rstep = R.make_step(lambda p, a, b: ref.loss(p, a, b, model_d, mm),
                        t["optimizer"])
    p32 = jax.tree.map(lambda s: spec(s.shape, jnp.float32), shapes)
    with jax.default_matmul_precision("highest"):
        report(f"{cell.name} reference step",
               rstep.lower(p32, p32, p32, spec((), jnp.float32),
                           batch["tokens"], batch["labels"],
                           R.sample_indices(shapes)).compile())


def rehearse_tune(cell, spec):
    import numpy as np
    from repro.core import gp
    t = cell.traffic
    bo = t["bo"]
    d = int(t["gp_dims"])
    m = gp._bucket(bo["n_init"] + bo["n_iter"])
    pool = bo["n_candidates"] + bo["n_local"] + 5 * d
    q = int(t["batch_size"])
    params = gp.init_params(d)
    params = jax.tree.map(lambda a: spec(a.shape, a.dtype), params)
    x, y = spec((m, d)), spec((m,))
    report(f"{cell.name} _fit (m={m})",
           gp._fit.lower(params, x, y, "matern52",
                         steps=max(bo["fit_steps"] // 3, 20),
                         extra_noise=spec((m,))).compile())
    report(f"{cell.name} posterior build",
           gp._build_jit.lower(params, x, y, "matern52", spec((m,)),
                               use_pallas=True).compile())
    state = gp.GPState(params, x, y, spec((m, m)), spec((m,)), spec(()),
                       spec(()))
    report(f"{cell.name} select_batch (pool {pool}, q {q})",
           gp.select_batch.lower(state, spec((pool, d)), spec((m,)),
                                 spec((), np.int32), spec(()), q,
                                 use_pallas=True).compile())


def main(argv=None) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    # the step's kernels branch on the backend at trace time
    jax.default_backend = lambda: "tpu"
    sys.modules["drivers_train"] = harness.load_module(
        harness.HERE / "drivers" / "train.py", "drivers_train")
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    names = argv or [w["name"] for w in bench["workloads"]]
    for name in names:
        cell = harness.Cell(name, bench)
        if cell.traffic["driver"] == "train":
            rehearse_train(cell, spec)
        else:
            rehearse_tune(cell, spec)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
