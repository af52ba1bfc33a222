"""JAX's persistent compilation cache, placed from outside or at a fixed
path, and a log of the compiles the process runs.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``,
``examples/tune_sapphire.py``, ``python -m repro.service``) call
:func:`use_compile_cache` before their first compile; importing the
library never does.  The directory is part of the cache's key, so it never
moves between runs:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
  here sets another directory;
* otherwise: ``<repo>/.jax_cache`` (listed in ``.gitignore``).

:func:`watch_compiles` registers listeners on ``jax.monitoring`` once per
process and returns the one :class:`CompileLog` they fill: the wall-clock
span of each trace, lowering and backend compile (a read of the persistent
cache is timed as a backend compile), and the persistent cache's hits and
misses.  The listeners run only while JAX compiles; nothing is written
out unless a caller asks for a :meth:`CompileLog.snapshot`.
"""

from __future__ import annotations

import math
import os
import threading
import time
from pathlib import Path
from typing import List, NamedTuple, Tuple

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# jax.monitoring's names for the three stages of a compile, timed as spans
COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileSpan(NamedTuple):
    start: float        # time.time() seconds, as JAX records them
    end: float
    stage: str          # "trace" | "lower" | "compile"
    fun_name: str


class CompileSnapshot(NamedTuple):
    """What the log holds between two wall-clock times."""
    programs: int       # backend compiles, persistent-cache reads included
    seconds: float      # union of the spans: nested traces count once
    cache_hits: int
    cache_misses: int
    spans: Tuple[CompileSpan, ...]


def _union_seconds(spans) -> float:
    total, end = 0.0, -math.inf
    for s in sorted(spans):
        if s.end <= end:
            continue
        total += s.end - max(s.start, end)
        end = s.end
    return total


class CompileLog:
    """In-memory record of the process's compiles (see the module
    docstring).  Listeners may fire on any thread that compiles."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spans: List[CompileSpan] = []
        self._events: List[Tuple[float, str]] = []

    def on_span(self, event: str, start: float, end: float, **kw):
        stage = COMPILE_STAGES.get(event)
        if stage is not None:
            with self._lock:
                self._spans.append(CompileSpan(start, end, stage,
                                               str(kw.get("fun_name", ""))))

    def on_event(self, event: str, **kw):
        name = CACHE_EVENTS.get(event)
        if name is not None:
            with self._lock:
                self._events.append((time.time(), name))

    def snapshot(self, since: float = -math.inf,
                 until: float = math.inf) -> CompileSnapshot:
        """The spans that started in ``[since, until)`` and the cache
        events recorded then; times are ``time.time()`` seconds."""
        with self._lock:
            spans = tuple(s for s in self._spans if since <= s.start < until)
            events = [n for t, n in self._events if since <= t < until]
        return CompileSnapshot(
            programs=sum(s.stage == "compile" for s in spans),
            seconds=_union_seconds(spans),
            cache_hits=events.count("cache_hits"),
            cache_misses=events.count("cache_misses"), spans=spans)


_LOG = None
_LOG_LOCK = threading.Lock()


def watch_compiles() -> CompileLog:
    """Register the compile listeners (once per process) and return the
    log they fill.  Compiles before the first call are not in it."""
    global _LOG
    with _LOG_LOCK:
        if _LOG is None:
            from jax import monitoring
            log = CompileLog()
            monitoring.register_event_time_span_listener(log.on_span)
            monitoring.register_event_listener(log.on_event)
            _LOG = log
        return _LOG
