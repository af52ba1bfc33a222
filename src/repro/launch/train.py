"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --steps 200 \
        --smoke --ckpt-dir /tmp/ckpt [--resume] [--knob microbatch=4 ...]

On this container it drives the reduced (--smoke) configs on the host
mesh; on a fleet the same driver runs the full config on the production
mesh (launch/mesh.py).  Integrates the whole runtime: RunConfig knobs,
sharded train step, stateless data stream, checkpoint/auto-resume, and
the step-time watchdog feeding the elastic policy.

After the first step it logs one line of compile counters
(``repro.compile_cache.watch_compiles``): the programs compiled so far,
their trace, lowering and compile seconds, and the persistent cache's
hits and misses.
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.compile_cache import use_compile_cache, watch_compiles
from repro.configs import get_config, get_smoke_config
from repro.launch.mesh import make_host_mesh
from repro.models.model import Model
from repro.runconfig import runconfig_from_knobs
from repro.train import elastic
from repro.train.checkpoint import CheckpointManager
from repro.train.data import SyntheticDataset
from repro.train.train_loop import init_state, make_train_step


def parse_knobs(pairs):
    out = {}
    for p in pairs or []:
        k, v = p.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v in ("true", "True"):
            v = True
        if v in ("false", "False"):
            v = False
        out[k] = v
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--knob", action="append", default=[],
                    help="RunConfig override, e.g. --knob microbatch=2")
    args = ap.parse_args(argv)
    use_compile_cache()
    compiles = watch_compiles()
    t_start = time.time()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    rc = runconfig_from_knobs(parse_knobs(args.knob))
    model = Model(cfg)
    mesh = make_host_mesh()

    cm = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    watchdog = elastic.StepWatchdog()

    with mesh:
        state = init_state(model, jax.random.key(args.seed), rc)
        start = 0
        if cm and args.resume and cm.latest_step() is not None:
            state, start = cm.restore(state)
            print(f"resumed from step {start}")
        # the state is donated: the step's output replaces it in place
        # instead of holding params and optimizer state twice
        step_fn = jax.jit(make_train_step(
            model, rc, lr_schedule=lambda s: args.lr), donate_argnums=(0,))
        data = SyntheticDataset(args.seed, args.global_batch, args.seq_len,
                                cfg.vocab_size, start_step=start)
        t_last = time.monotonic()
        for i in range(start, args.steps):
            batch = next(data)
            state, mets = step_fn(state, batch)
            now = time.monotonic()
            watchdog.observe(0, now - t_last)
            t_last = now
            if (i + 1) % 10 == 0 or i == start:
                print(f"step {i+1:5d} loss {float(mets['loss']):.4f} "
                      f"gnorm {float(mets['grad_norm']):.3f} "
                      f"lr {float(mets['lr']):.2e}")
            if i == start:
                c = compiles.snapshot(since=t_start)
                print(f"compiled {c.programs} programs in {c.seconds:.2f} s "
                      f"(persistent cache: {c.cache_hits} hits, "
                      f"{c.cache_misses} misses)")
            if cm and (i + 1) % args.ckpt_every == 0:
                cm.save(i + 1, state, blocking=False)
        if cm:
            cm.save(args.steps, state, blocking=True)
            print(f"final checkpoint at step {args.steps} -> {cm.root}")
    print("done")


if __name__ == "__main__":
    main()
