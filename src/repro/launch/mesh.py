"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run must set
XLA_FLAGS before the first jax device query, and smoke tests must see the
real single CPU device.

  single-pod : (16, 16)        axes ("data", "model")      — 256 chips
  multi-pod  : (2, 16, 16)     axes ("pod", "data", "model") — 512 chips
"""

from __future__ import annotations

import math

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """The deployment mesh over the dry-run's forced host (CPU) devices —
    built from ``jax.devices("cpu")`` so that it starts on a machine
    whose default backend is an attached accelerator, too."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         devices=jax.devices("cpu")[:math.prod(shape)])


def make_host_mesh():
    """Whatever this host actually has (smoke tests / examples): 1 device."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"))
