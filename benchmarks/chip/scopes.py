#!/usr/bin/env python3
"""Device time of a traced window by the step program's named scopes, and
compile spans on the trace's clock.

The program names its parts with ``jax.named_scope`` (``model.embed``,
``model.<mixer>``, ``model.mlp`` / ``model.moe``, ``model.head``,
``train.grad``, ``train.update``); the names reach the compiled program's
metadata as ``op_name`` paths such as
``jit(step_fn)/train.grad/transpose(jvp(model.head))/dot_general``.

* :func:`scope_ops` maps each instruction of a compiled module to
  ``(scope, direction)``: the innermost scope its ``op_name`` names, and
  ``bwd`` where the path holds ``transpose(`` or a ``bwd`` scope, ``fwd``
  otherwise.  An instruction takes its own metadata; a fusion that has
  none takes its fused root's, or where the root has none (a tuple of
  several outputs) that of the fused computation's last instruction.
* :func:`attribute` reads a trace that :mod:`trace` reduces and returns,
  in seconds, the self time of the module's events in the window per scope
  and direction (``scopes``), the module's self time no scope names
  (``unscoped_s``) and in all (``module_s``), the ten longest operations
  labelled ``<module>:<scope>/<direction>:<instruction>`` (``device_ops``),
  and the idle gaps labelled by the innermost span open in them, host
  spans given on the wall clock (compile spans, ``jax.compile``) placed on
  the trace's clock by an anchor (``idle_gaps``).

Run as a script on one chip, it builds a cell's train step as the train
driver does, times a traced and an untraced window of it, and prints one
JSON line: tokens/s of both windows, device milliseconds per step by scope,
the compile counters of set-up, and the labelled breakdown::

    python3 benchmarks/chip/scopes.py --workload yi6b.train_4k --seed 7 \
        --seconds 8
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, Optional, Tuple

import trace as tracemod

SCOPE = re.compile(r"(?:^|[/(])((?:model|train)\.[A-Za-z_]+)")
BWD = re.compile(r"transpose\(|(?:^|/)bwd(?:/|$)")
COMPILE_SPAN = "jax.compile"
_META = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


def scope_of(op_path: str) -> Optional[Tuple[str, str]]:
    """``(scope, direction)`` of one ``op_name`` path, None if it names no
    scope."""
    found = SCOPE.findall(op_path)
    if not found:
        return None
    return found[-1], "bwd" if BWD.search(op_path) else "fwd"


def module_name(hlo_text: str) -> str:
    """The module's name as the trace's "XLA Modules" line gives it."""
    m = _MODULE.match(hlo_text)
    return m.group(1) if m else ""


def scope_ops(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """Instruction name -> ``(scope, direction)`` for every instruction of
    ``hlo_text`` (a compiled module's ``as_text()``) that a scope names."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    last: Dict[str, str] = {}      # computation -> its root's, else its
    rooted = set()                 # last instruction's, op_name
    comp = None
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        if c and line.rstrip().endswith("{"):
            comp = c.group(1)
            continue
        m = tracemod._OP_NAME.match(line)
        if not m:
            continue
        name = m.group(1)
        meta = _META.search(line)
        if meta:
            own[name] = meta.group(1)
            if comp not in rooted:
                last[comp] = meta.group(1)
                if line.lstrip().startswith("ROOT "):
                    rooted.add(comp)
        elif " fusion(" in line:
            f = _CALLS.search(line)
            if f:
                calls[name] = f.group(1)
    for name, comp in calls.items():
        if comp in last:
            own[name] = last[comp]
    out = {}
    for name, path in own.items():
        s = scope_of(path)
        if s is not None:
            out[name] = s
    return out


def _open_span(mid: float, spans) -> str:
    open_ = [h for h in spans if h[0] <= mid <= h[1]]
    return min(open_, key=lambda h: h[1] - h[0])[2] if open_ \
        else "no bench span"


def attribute(path, scopes_by_op: Dict[str, Tuple[str, str]], module: str,
              extra_spans: Iterable[Tuple[float, float, str]] = (),
              anchor: Optional[float] = None,
              anchor_span: str = "bench.window") -> dict:
    """See the module docstring.  ``extra_spans`` are ``(start, end,
    name)`` in ``time.time()`` seconds; ``anchor`` is the wall time taken
    just inside the host span ``anchor_span``, whose start in the trace
    places them."""
    pd = tracemod.load(path)
    spans = tracemod.host_spans(pd)
    win = [s for s in spans if s[2] == "bench.window"]
    if not win:
        raise ValueError("no 'bench.window' span in the trace")
    ws, we = win[0][0], win[0][1]
    host = [s for s in spans if s[2] != "bench.window"]
    extra = list(extra_spans)
    if extra:
        at = [s for s in spans if s[2] == anchor_span]
        if anchor is None or not at:
            raise ValueError("extra spans need an anchor")
        shift = at[0][0] - anchor
        host += [(s + shift, e + shift, n) for s, e, n in extra]
    scopes: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"fwd": 0.0, "bwd": 0.0})
    ops: Dict[str, float] = defaultdict(float)
    module_s = unscoped = 0.0
    gaps = []
    devs = tracemod.device_events(pd)
    for evs in devs.values():
        inside = [ev for ev in evs if ev[0] >= ws and ev[1] <= we]
        prev = ws
        for s, e in tracemod._union([(s, e) for s, e, _, _ in inside]):
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if we > prev:
            gaps.append((prev, we))
        for (s, e, text, mod), own in zip(inside,
                                          tracemod._self_times(inside)):
            if mod != module:
                continue
            module_s += own
            sd = scopes_by_op.get(tracemod.op_name(text))
            if sd is None:
                unscoped += own
                ops[tracemod._label(text, f"{mod}:-")] += own
            else:
                scopes[sd[0]][sd[1]] += own
                ops[tracemod._label(text, f"{mod}:{sd[0]}/{sd[1]}")] += own
    n_dev = max(len(devs), 1)
    idle: Dict[str, list] = defaultdict(list)
    for s, e in gaps:
        idle[_open_span(0.5 * (s + e), host)].append((e - s, s - ws))
    top_idle = sorted(
        ((f"{k} ({len(v)} gaps, longest {max(v)[0]!r} s at "
          f"{max(v)[1]!r} s)", sum(d for d, _ in v))
         for k, v in idle.items()), key=lambda kv: -kv[1])[:10]
    return {"scopes": {k: {d: t / n_dev for d, t in v.items()}
                       for k, v in scopes.items()},
            "unscoped_s": unscoped / n_dev, "module_s": module_s / n_dev,
            "device_ops": [[k, v] for k, v in
                           sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[k, v] for k, v in top_idle]}


# ---------------------------------------------------------------------------
# one cell's train step, untraced and traced, on the chip
# ---------------------------------------------------------------------------

def profile(cell, seed: int, seconds: float, tdir, t0: float) -> dict:
    """Build the cell's step as ``drivers/train.py`` does, run its check
    steps, a traced window and an untraced one; ``t0`` is the wall time
    from which set-up's compiles count."""
    import shutil
    import time

    import jax

    import harness
    from repro.compile_cache import CompileLog, watch_compiles

    log = watch_compiles()
    driver = harness.load_module(harness.HERE / "drivers" / "train.py",
                                 "drivers_train")
    prog = driver.Program(cell, seed)
    prog.check_steps()
    t_setup = time.time()
    # traced first, as the driver traces its window right after set-up
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(str(tdir))
    with jax.profiler.TraceAnnotation("bench.anchor"):
        anchor = time.time()
    traced = prog.window(seconds)
    jax.profiler.stop_trace()
    t_end = time.time()
    plain = prog.window(seconds)
    setup = log.snapshot(since=t0, until=t_setup)
    inside = log.snapshot(since=anchor, until=t_end)
    hlo = prog.hlo
    prog.free()

    base = tracemod.reduce(tdir, tracemod.kernel_ops(hlo))
    found = attribute(tdir, scope_ops(hlo), module_name(hlo),
                      [(s.start, s.end, COMPILE_SPAN) for s in inside.spans],
                      anchor, anchor_span="bench.anchor")
    shutil.rmtree(tdir, ignore_errors=True)

    # the listeners' own cost: one call each, on a log of its own
    probe, n = CompileLog(), 100_000
    t = time.perf_counter()
    for _ in range(n):
        probe.on_span("/jax/core/compile/backend_compile_duration", 0.0,
                      1.0, fun_name="f")
    per_span = (time.perf_counter() - t) / n
    t = time.perf_counter()
    for _ in range(n):
        probe.on_event("/jax/unwatched")
    per_event = (time.perf_counter() - t) / n

    tokens = cell.traffic["batch"] * cell.traffic["seq"]
    steps = traced["steps"]
    ms = {k: {d: 1e3 * v / steps for d, v in fb.items()}
          for k, fb in found["scopes"].items()}
    return {
        "workload": cell.name, "seed": seed,
        "tokens_per_s": {"untraced": tokens * plain["steps"] /
                         plain["window_s"],
                         "traced": tokens * steps / traced["window_s"]},
        "steps": {"untraced": plain["steps"], "traced": steps},
        "window_s": base["window_s"], "busy_s": base["busy_s"],
        "module_s": found["module_s"], "unscoped_s": found["unscoped_s"],
        "unscoped_share": found["unscoped_s"] / found["module_s"]
        if found["module_s"] else None,
        "ms_per_step": {k: {**v, "total": v["fwd"] + v["bwd"]}
                        for k, v in sorted(ms.items())},
        "kernels_ms": {k: 1e3 * sum(v) / steps
                       for k, v in base["kernels"].items()},
        "compile": {"setup_s": setup.seconds, "setup_programs":
                    setup.programs, "cache_hits": setup.cache_hits,
                    "cache_misses": setup.cache_misses,
                    "in_window_programs": inside.programs,
                    "in_window_s": inside.seconds,
                    "listener_span_us": 1e6 * per_span,
                    "listener_event_us": 1e6 * per_event,
                    "setup_spans": len(setup.spans)},
        "memory_peak_bytes": harness.device_info(jax.devices()[:1])[
            "memory_peak_bytes"],
        "device_ops": found["device_ops"], "idle_gaps": found["idle_gaps"],
    }


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import sys
    import time

    t0 = time.time()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    harness.ensure_src_on_path()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("scopes: no TPU; nothing run", file=sys.stderr)
        return 1
    harness.use_compile_cache()
    cell = harness.Cell(args.workload)
    out = profile(cell, args.seed, args.seconds,
                  harness.SCRATCH / "trace" / f"{cell.name}.scopes", t0)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
