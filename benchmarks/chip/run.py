#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload yi6b.train_4k --seed 7 \
        --seconds 10 --trace 0

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
traffic mix; the traffic names the driver that builds the cell, warms
every shape it uses, measures for ``--seconds`` and then checks what the
timed path produced against the configuration's plain reference.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs the
same window under the profiler and prints its per-layer metrics, the
device's busy and window seconds, and a breakdown.  The last line of
stdout is one JSON object; the numbers compared for ``correct`` are the
last lines of stderr and the last key of that object.

There is no CPU fallback: with no TPU, or fewer chips than the cell asks
for, it exits non-zero before any work and prints no result.  A traced
run in which a per-layer metric of the cell finds nothing to read (a
kernel that was not tagged) exits 3 and prints no result either.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime's own logs go to a fixed path under /tmp unless told
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def _log(msg: str):
    print(msg, file=sys.stderr, flush=True)


class SilentMetric(RuntimeError):
    """A per-layer metric of the cell whose reader found nothing to read."""


def result_line(cell, res: dict, trace: bool) -> dict:
    if trace:
        ctx = res["trace"]
        metrics = {}
        for m in cell.per_layer():
            mod = harness.load_module(harness.HERE / "metrics" /
                                      f"{m['name']}.py")
            value = mod.read(ctx)
            if value is None:
                raise SilentMetric(
                    f"{m['name']} is listed for {cell.name} but its reader "
                    f"found nothing in the trace (kernels tagged: "
                    f"{sorted(ctx.get('kernels', {}))})")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        units = {m["name"]: m["unit"] for m in cell.bench["end_to_end"]}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in res["end_to_end"].items()
                   if k in cell.end_to_end()}
    out = {"correct": bool(res["correct"]), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics,
           "device": dict(res["device"])}
    if trace:
        out["device"]["busy_s"] = res["trace"]["busy_s"]
        out["device"]["window_s"] = res["trace"]["window_s"]
        out["breakdown"] = res["trace"]["breakdown"]
    out["checks"] = res["checks"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (harness.SRC / "repro").is_dir():
        _log(f"run: the system under test is missing ({harness.SRC})")
        return 2
    harness.ensure_src_on_path()
    try:
        cell = harness.Cell(args.workload)
    except (FileNotFoundError, KeyError) as e:
        _log(f"run: {e}")
        return 2

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _log(f"run: no TPU (JAX found {devices[0].platform}); nothing run")
        return 1
    chips = int(cell.entry["chips"])
    if len(devices) < chips:
        _log(f"run: {args.workload} needs {chips} chips, JAX found "
             f"{len(devices)}")
        return 1
    devices = devices[:chips]
    harness.use_compile_cache()
    _log(f"run: {args.workload} on {devices[0].device_kind} x {chips}, "
         f"seed {args.seed}, {args.seconds} s, trace {args.trace}")

    res = cell.driver().run(cell, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace), t0=T0, devices=devices)
    for text in harness.check_lines(res["checks"]):
        _log(text)
    try:
        line = result_line(cell, res, bool(args.trace))
    except SilentMetric as e:
        _log(f"run: {e}; no result")
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
