"""Serving driver: continuous-batching engine on a (smoke) config.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --requests 12
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs import get_config, get_smoke_config
from repro.models.model import Model
from repro.runconfig import runconfig_from_knobs
from repro.serve.engine import Engine
from repro.launch.train import parse_knobs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--s-max", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--knob", action="append", default=[])
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    rc = runconfig_from_knobs(parse_knobs(args.knob))
    model = Model(cfg)
    params = model.init(jax.random.key(args.seed))
    eng = Engine(model, params, rc, slots=args.slots, s_max=args.s_max)

    rng = np.random.default_rng(args.seed)
    t0 = time.monotonic()
    for i in range(args.requests):
        plen = int(rng.integers(4, 24))
        prompt = rng.integers(1, cfg.vocab_size, size=plen)
        eng.submit(prompt, max_new_tokens=args.max_new)
    done = eng.run()
    dt = time.monotonic() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"served {len(done)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s, {eng.step_count} engine steps)")
    for r in done[:4]:
        print(f"  rid {r.rid}: prompt {len(r.prompt)} -> {r.out_tokens[:8]}…")


if __name__ == "__main__":
    main()
