#!/usr/bin/env python3
"""Read the numbers that ``correct`` compares, on the chip at a cell's own
size: the program's sound readings over many seeds (the lower readings),
the control's and each planted fault's over a few (the upper readings).
The limits in ``limits/<cell>.json`` are set from these.

    python3 benchmarks/chip/calibrate.py --workload yi6b.train_4k \
        --seeds 12 --upper-seeds 3 [--seconds 4]

Train cells: the program runs its first steps from each seed, then the
reference follows; the control is the reference at fp8 in the program's
place; the faults "half_batch" and "altered" are planted in that
reference ("unchanged" reads 1 on the ``grad`` and ``update`` gaps by
construction).
Tune cells: each seed is a full run of the driver (``--seconds`` window);
the control is the reference's selection and Lasso path in float32 at
``high`` precision in the program's place, on the window's asks; the
faults are planted under the program.  All seeds run in this one process.
Writes ``<out>/<cell>.json`` and ``<out>/<cell>.limits.json`` (``--out``,
by default ``.bench_tmp/calibrate`` in the checkout).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

harness.ensure_src_on_path()


def _train(cell, args, log):
    import numpy as np
    T = cell.driver()
    out = {"program": [], "control": [], "half_batch": [], "altered": []}
    for i in range(args.seeds):
        seed = args.base_seed + i
        prog = T.Program(cell, seed)
        readings = prog.check_steps()
        prog.free()
        del prog
        ref = T.reference_readings(cell, seed)
        row = {"seed": seed, **T.gaps(readings, ref),
               "losses": readings["losses"], "ref_losses": ref["losses"]}
        out["program"].append(row)
        log(f"program seed {seed}: {row}")
        if i < args.upper_seeds:
            for name, kw in (("control", {"mode": "fp8"}),
                             ("half_batch", {"fault": "half_batch"}),
                             ("altered", {"fault": "altered"})):
                r = T.reference_readings(cell, seed, **kw)
                row = {"seed": seed, **T.gaps(r, ref), "losses": r["losses"]}
                out[name].append(row)
                log(f"{name} seed {seed}: {row}")
        if i == 0:
            out["leaf_gaps_first_seed"] = _leaf_table(T, readings, ref, cell)
    return out


def _leaf_table(T, prog, ref, cell):
    import jax
    import numpy as np
    import generate
    from repro.models.model import Model
    cfg = T.model_config(cell)
    specs = generate.leaf_specs(jax.eval_shape(
        lambda: Model(cfg).init(jax.random.key(0))))
    med = float(np.median(ref["grad"]))
    rows = []
    for (p, s, _), gp, gr, cp, cr in zip(specs, prog["grad"], ref["grad"],
                                         prog["change"], ref["change"]):
        rows.append([p, list(s), float(gp), float(gr),
                     float(abs(gp - gr) / max(gr, med)), float(cp),
                     float(cr)])
    return rows


def _tune(cell, args, log):
    import jax
    T = cell.driver()
    devices = jax.devices()[:1]
    out = {"program": [], "control": [], "altered": [], "unchanged": [],
           "half_batch": []}
    for i in range(args.seeds):
        seed = args.base_seed + i
        res = T.run(cell, seed=seed, seconds=args.seconds, trace=False,
                    t0=time.monotonic(), devices=devices, control=i <
                    args.upper_seeds)
        row = {"seed": seed, **{k: v["value"] for k, v in
                                res["checks"].items()},
               "asks": res["asks"], "picks_checked": res["picks_checked"],
               "e2e": res["end_to_end"]}
        out["program"].append(row)
        log(f"program seed {seed}: {row}")
        if "control" in res:
            out["control"].append({"seed": seed, **res["control"]})
            log(f"control seed {seed}: {res['control']}")
        if i < args.upper_seeds:
            for fault in ("altered", "unchanged", "half_batch"):
                r = T.run(cell, seed=seed, seconds=args.seconds, trace=False,
                          t0=time.monotonic(), devices=devices, fault=fault)
                row = {"seed": seed, **{k: v["value"] for k, v in
                                        r["checks"].items()}}
                out[fault].append(row)
                log(f"{fault} seed {seed}: {row}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--upper-seeds", type=int, default=3)
    ap.add_argument("--base-seed", type=int, default=2 ** 32 + 101)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--out", default=str(harness.SCRATCH / "calibrate"))
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU; nothing run", file=sys.stderr)
        return 1
    harness.use_compile_cache()
    cell = harness.Cell(args.workload)
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    def log(msg):
        print(msg, flush=True)

    kind = cell.traffic["driver"]
    out = _train(cell, args, log) if kind == "train" else \
        _tune(cell, args, log)
    (outdir / f"{cell.name}.json").write_text(json.dumps(out, indent=1,
                                                         default=float))
    for name, rows in out.items():
        if not rows or not isinstance(rows[0], dict):
            continue
        keys = [k for k in rows[0] if k.endswith("_gap")]
        log(f"{name}: " + ", ".join(
            f"{k} max {max(r[k] for r in rows)!r} min "
            f"{min(r[k] for r in rows)!r}" for k in keys))
    limits = limits_from(out)
    (outdir / f"{cell.name}.limits.json").write_text(json.dumps(limits,
                                                                indent=1))
    log(f"limits: {limits}")
    return 0


def limits_from(out: dict) -> dict:
    """Each number's limit from its readings: the lower reading is the
    largest of the program's seeds; the upper the least of the control's
    (where that is 3× the lower or more) and of each fault's (10×, or 3×
    for a state left unchanged, which reads 1 on the ``grad`` and
    ``update`` gaps: its first moment stays zero, its weights unmoved).  The
    limit lies between them, nearer the upper in ratio (lower^0.4 ·
    upper^0.6); a number with no upper reading gets none and is left
    out."""
    limits = {}
    for k in [k for k in out["program"][0] if k.endswith("_gap")]:
        lower = max(r[k] for r in out["program"])
        ups = []
        for name, rows in out.items():
            if name == "program" or not rows or not isinstance(rows[0],
                                                               dict):
                continue
            least = min(r[k] for r in rows)
            if least >= (3 if name == "control" else 10) * lower:
                ups.append(least)
        if k.startswith(("grad", "update")) and "unchanged" not in out \
                and lower <= 1 / 3:
            ups.append(1.0)
        if ups and lower > 0:
            limits[k] = float(f"{lower ** 0.4 * min(ups) ** 0.6:.2g}")
    return limits


if __name__ == "__main__":
    sys.exit(main())
