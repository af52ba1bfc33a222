"""What every cell shares: finding its files by name, seeds, the device,
the compile cache, and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Its files are
found by the names that entry gives:

* ``configs/<config>.json``          model configuration as run;
* ``configs/<config>.reference.py``  its plain float32 reference;
* ``traffic/<traffic>.json``         the traffic mix, naming its driver;
* ``drivers/<driver>.py``            builds, warms, times and checks;
* ``limits/<workload>.json``         the limit of each number compared;
* ``metrics/<metric>.py``            one reader per per-layer metric;
* ``flops/kernels/<tag>.py``         one per kernel with a roofline share:
                                     what tags its calls in the trace, and
                                     its operations and bytes per call;
* ``flops/layers/<kind>.py``         one per layer kind of a pattern: its
                                     term of the model FLOPs.

Adding a cell, a metric, a kernel or a layer kind adds files; no file here
changes.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SRC = ROOT / "src"
CACHE_DIR = ROOT / ".jax_cache"
SCRATCH = ROOT / ".bench_tmp"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str = None):
    """Import a file of the benchmark by its path (names may hold dots
    and dashes, which ``import`` does not take)."""
    name = name or "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, name: str, bench: dict = None, entry: dict = None,
                 limits: dict = None):
        """``entry`` and ``limits`` stand in for the cell's entry in
        ``BENCHMARK.json`` and its limits file (the tests' smoke sizes)."""
        bench = bench or load_json(ROOT / "BENCHMARK.json")
        if entry is None:
            entries = {w["name"]: w for w in bench["workloads"]}
            if name not in entries:
                raise KeyError(f"no workload {name!r} in BENCHMARK.json")
            entry = entries[name]
        self.name = name
        self.entry = entry
        self.bench = bench
        self.config_name = self.entry["config"]
        self.config = load_json(HERE / "configs" / f"{self.config_name}.json")
        self.traffic = load_json(HERE / "traffic" /
                                 f"{self.entry['traffic']}.json")
        self.limits = limits or load_json(HERE / "limits" / f"{name}.json")

    def reference(self):
        return load_module(HERE / "configs" /
                           f"{self.config_name}.reference.py")

    def driver(self):
        return load_module(HERE / "drivers" / f"{self.traffic['driver']}.py")

    def end_to_end(self):
        """Names of the end-to-end metrics this cell reports."""
        return [m["name"] for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self):
        """Per-layer metrics of this cell: those that list it, or that list
        no cells and move an end-to-end metric it reports."""
        e2e = set(self.end_to_end())
        out = []
        for m in self.bench["per_layer"]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif m["moves"] in e2e:
                out.append(m)
        return out


def seed_words(seed: int, *more: int) -> int:
    """A 31-bit seed for JAX's and NumPy's generators from any whole
    numbers: ``--seed`` may exceed 32 bits."""
    import numpy as np
    ent = [int(seed) & 0xFFFFFFFF, int(seed) >> 32] + [int(m) for m in more]
    return int(np.random.SeedSequence(ent).generate_state(1)[0]) & 0x7FFFFFFF


def peak(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peak figures for device kind {kind!r} in "
                       f"peaks.json; add them with their source")
    return table[kind]


def use_compile_cache() -> str:
    """JAX's persistent cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` points), every program cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devices) -> dict:
    d = devices[0]
    stats = d.memory_stats() or {}
    peak_bytes = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for x in devices)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak_bytes),
            "memory_limit_bytes": int(stats.get("bytes_limit", 0))}


def check_lines(checks: dict) -> list:
    return [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
            for k, v in checks.items()]


def ensure_src_on_path():
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def judge(values: dict, limits: dict):
    """Each number that ``limits`` names beside its limit; correct when
    every one is finite and within it."""
    checks = {k: {"value": values[k], "limit": v} for k, v in limits.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    return checks, correct
