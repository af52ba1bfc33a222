"""Reduce one profiler trace (``.xplane.pb``) to what the per-layer metrics
read.

Device operations are the events of each TPU plane's "XLA Ops" line (on a
CPU trace, which the tests record, the host events that carry an
``hlo_op`` stat); each is attributed to the XLA module whose event on the
"XLA Modules" line encloses it (on the CPU, its ``hlo_module`` stat).
Kernel tags are the names of the files under ``flops/kernels/``.
Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``
events, named ``bench.*``.  The window is the ``bench.window`` span.

:func:`reduce` returns, all in seconds:

* ``window_s`` and ``busy_s`` — the window's length and the union of the
  intervals in which an operation ran on a device, averaged over devices;
* ``modules`` — device time per XLA module (``jit_step_fn``, ``jit__fit``,
  …, the program id stripped) and ``module_runs``, how often each ran;
* ``kernels`` — per kernel tag, the device durations of its events;
* ``spans`` — per host span name, the durations of its events;
* ``breakdown`` — the ten device operations that took most time of their
  own (less the operations nested in them) and the idle time by the
  innermost host span open in each gap.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import flops

_MODULE_ID = re.compile(r"\(\d+\)$")
_OP_NAME = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_name(text: str) -> str:
    """The HLO instruction name of a device event (TPU events carry the
    instruction's whole text, ``%fusion.47 = bf16[...] fusion(...)``)."""
    m = _OP_NAME.match(text)
    return m.group(1) if m else text.strip()


def kernel_ops(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> kernel tag, for every Mosaic custom call
    whose line holds one of a kernel module's ``MATCH`` substrings (its
    metadata names the kernel's jitted wrapper).  Where several match, the
    longest substring wins."""
    match = [(sub, tag) for tag, mod in flops.kernel_modules().items()
             for sub in mod.MATCH]
    out = {}
    for line in hlo_text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        m = _OP_NAME.match(line)
        if not m:
            continue
        hits = [(len(sub), tag) for sub, tag in match if sub in line]
        if hits:
            out[m.group(1)] = max(hits)[1]
    return out


def kernel_tag(name: str, ops: Optional[Dict[str, str]] = None):
    """The kernel tag of instruction ``name``: from ``ops`` when given, else
    from the name itself (a Mosaic call is named after the jitted wrapper
    around it, ``flash_attention.4``)."""
    tag = (ops or {}).get(name)
    if tag is None:
        stem = name.rsplit(".", 1)[0]
        tag = stem if stem in flops.kernel_modules() else None
    return tag


def _self_times(evs) -> List[float]:
    """Each event's duration less that of the events nested in it (a
    ``while`` and the operations of its body are all on one line)."""
    own = [e - s for s, e, *_ in evs]
    stack: List[int] = []
    for i in sorted(range(len(evs)), key=lambda i: (evs[i][0], -evs[i][1])):
        s, e = evs[i][0], evs[i][1]
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def _label(text: str, module: str, width: int = 160) -> str:
    short = _LAYOUT.sub("", text)[:width]
    return f"{module}:{short}" if module else short


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _module_name(name: str) -> str:
    return _MODULE_ID.sub("", name).strip()


def load(path) -> "object":
    from jax.profiler import ProfileData
    path = Path(path)
    if path.is_dir():
        found = glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = Path(sorted(found)[-1])
    return ProfileData.from_file(str(path))


def device_events(pd):
    """Per device: list of (start_s, end_s, op name, module name)."""
    devices = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                            * 1e-9, e.name) for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                             * 1e-9, _module_name(e.name))
                            for e in line.events]
            mods.sort()
            out, j = [], 0
            for s, e, name in sorted(ops):
                while j + 1 < len(mods) and mods[j + 1][0] <= s:
                    j += 1
                mod = mods[j][2] if mods and mods[j][0] <= s <= mods[j][1] \
                    else ""
                out.append((s, e, name, mod))
            devices[plane.name] = out
    if devices:
        return devices
    # a CPU trace: XLA's operations run on host threads, tagged by stats
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                st = _stats(e)
                if "hlo_op" in st:
                    out.append((e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9,
                                str(st["hlo_op"]),
                                _module_name(str(st.get("hlo_module", "")))))
    return {"cpu": sorted(out)}


def host_spans(pd, prefix: str = "bench.") -> List[Tuple[float, float, str]]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9, e.name))
    return sorted(out)


def reduce(path, kernels_by_op: Optional[Dict[str, str]] = None,
           window_span: str = "bench.window") -> dict:
    """See the module docstring; ``kernels_by_op`` tags instructions by
    name (:func:`kernel_ops`), the rest are tagged by :func:`kernel_tag`."""
    pd = load(path)
    spans = host_spans(pd)
    win = [s for s in spans if s[2] == window_span]
    if not win:
        raise ValueError(f"no {window_span!r} span in the trace")
    ws, we = win[0][0], win[0][1]
    devs = device_events(pd)
    busy = []
    modules: Dict[str, float] = defaultdict(float)
    module_runs: Dict[str, int] = defaultdict(int)
    ops: Dict[str, float] = defaultdict(float)
    kernels: Dict[str, List[float]] = defaultdict(list)
    gaps: List[Tuple[float, float]] = []
    for evs in devs.values():
        inside = [(s, e, n, m) for s, e, n, m in evs if s >= ws and e <= we]
        iv = _union([(s, e) for s, e, _, _ in inside])
        busy.append(sum(e - s for s, e in iv))
        prev = ws
        for s, e in iv:
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if we > prev:
            gaps.append((prev, we))
        for (s, e, n, m), own in zip(inside, _self_times(inside)):
            ops[_label(n, m)] += own
            tag = kernel_tag(op_name(n), kernels_by_op)
            if tag is not None:
                kernels[tag].append(e - s)
    n_dev = max(len(devs), 1)
    mod_events = _module_events(pd, ws, we)
    for s, e, name in mod_events:
        modules[name] += e - s
        module_runs[name] += 1
    if not mod_events:
        for evs in devs.values():
            for s, e, n, m in evs:
                if s >= ws and e <= we and m:
                    modules[m] += e - s
    idle: Dict[str, List[float]] = defaultdict(list)
    host = [s for s in spans if s[2] != window_span]
    for s, e in gaps:
        mid = 0.5 * (s + e)
        open_ = [h for h in host if h[0] <= mid <= h[1]]
        label = min(open_, key=lambda h: h[1] - h[0])[2] if open_ \
            else "no bench span"
        idle[label].append(e - s)
    span_d: Dict[str, List[float]] = defaultdict(list)
    for s, e, name in host:
        if s >= ws and e <= we:
            span_d[name].append(e - s)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    top_idle = sorted(((f"{k} ({len(v)} gaps, longest {max(v)!r} s)",
                        sum(v)) for k, v in idle.items()),
                      key=lambda kv: -kv[1])[:10]
    return {"window_s": we - ws, "busy_s": sum(busy) / n_dev,
            "devices": n_dev, "modules": dict(modules),
            "module_runs": dict(module_runs), "kernels": dict(kernels),
            "spans": dict(span_d),
            "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                          "idle_gaps": [[k, v] for k, v in top_idle]}}


def _module_events(pd, ws, we):
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for e in line.events:
                s = e.start_ns * 1e-9
                t = s + e.duration_ns * 1e-9
                if s >= ws and t <= we:
                    out.append((s, t, _module_name(e.name)))
    return out
