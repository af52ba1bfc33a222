"""Sharded q-EI candidate scoring (gp.select_batch_sharded).

Guards the PR-6 tentpole contracts:

* the pool mesh helpers (``repro.parallel.sharding``) — deterministic
  device order (part of the pick-reproducibility contract) and the
  spare-device rule for background refits;
* ``gp.select_batch_sharded`` picks **bit-identically** to
  ``gp.select_batch`` on the same pool — on a 1-device ``shard_map``
  mesh, across fantasy x acquisition, odd pool sizes (exercising the
  pad-to-multiple rows, pre-marked taken) and the Pallas cross-Gram;
* the same identity under *real* multi-device partitioning — a
  subprocess forces 2 CPU devices via ``XLA_FLAGS`` (it must be set
  before jax imports, hence the re-exec);
* ``BOConfig.shard_candidates`` never changes a trace: on a 1-device
  host the gate falls back to plain ``select_batch``, and the strategy's
  picks match the gate-off run config for config.
"""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.core import gp
from repro.core.space import Knob, Space
from repro.core.strategy import BOConfig, BOStrategy
from repro.parallel.sharding import (POOL_AXIS, pool_devices, pool_mesh,
                                     spare_device)

REPO = Path(__file__).resolve().parent.parent


def _problem(n=26, d=3, q=3, seed=0, steps=30):
    rng = np.random.default_rng(seed)
    x = rng.random((n, d))
    y = (np.sin(3 * x[:, 0]) + (x[:, 1] - 0.4) ** 2
         + 0.1 * rng.normal(size=n))
    st = gp.fit(x, y, steps=steps, pad_to=gp._bucket(n + q))
    y_raw = np.zeros(int(st.x.shape[0]), np.float32)
    y_raw[:n] = y
    return st, y_raw, n, float(np.min(y))


class TestPoolMesh:
    def test_pool_devices_deterministic_prefix(self):
        devs = pool_devices()
        assert devs == tuple(jax.devices())
        assert pool_devices(1) == (jax.devices()[0],)
        assert pool_devices(99) == devs          # clamped to the host

    def test_pool_mesh_axis(self):
        mesh = pool_mesh(1)
        assert mesh.axis_names == (POOL_AXIS,)
        assert mesh.shape[POOL_AXIS] == 1

    def test_spare_device_single_host(self):
        # tests run on the host's single device: background work shares it
        if len(jax.devices()) == 1:
            assert spare_device() is None
        else:
            d = spare_device()
            assert d is not None and d != jax.devices()[0]


class TestSingleDeviceIdentity:
    """nd=1 sharded path == select_batch.  The mesh
    machinery (padding, collective argmax, masked psum gathers) is fully
    exercised; only the cross-device traffic is degenerate."""

    @pytest.mark.parametrize("fantasy", ["liar", "believer"])
    @pytest.mark.parametrize("acq", ["ei", "ucb"])
    def test_matches_select_batch(self, fantasy, acq):
        st, y_raw, n, best_y = _problem(seed=1)
        cand = np.random.default_rng(2).random((37, 3)).astype(np.float32)
        base = np.asarray(gp.select_batch(
            st, cand, y_raw, n, best_y, 3, fantasy=fantasy,
            acquisition=acq))
        picks = np.asarray(gp.select_batch_sharded(
            st, cand, y_raw, n, best_y, 3, fantasy=fantasy,
            acquisition=acq))
        assert np.array_equal(base, picks), f"{fantasy}/{acq}"

    def test_q1_and_even_pool(self):
        st, y_raw, n, best_y = _problem(n=20, q=1, seed=3)
        cand = np.random.default_rng(4).random((64, 3)).astype(np.float32)
        base = np.asarray(gp.select_batch(st, cand, y_raw, n, best_y, 1))
        picks = np.asarray(gp.select_batch_sharded(
            st, cand, y_raw, n, best_y, 1))
        assert np.array_equal(base, picks)

    def test_pad_rows_never_picked(self):
        """Explicit 1-device tuple + odd pool: the pad row (unit-cube
        midpoint, often a genuinely good candidate) is pre-marked taken
        and must never appear in the picks."""
        st, y_raw, n, best_y = _problem(seed=5)
        cand = np.random.default_rng(6).random((41, 3)).astype(np.float32)
        picks = np.asarray(gp.select_batch_sharded(
            st, cand, y_raw, n, best_y, 4,
            devices=(jax.devices()[0],)))
        assert np.all(picks < 41)
        base = np.asarray(gp.select_batch(st, cand, y_raw, n, best_y, 4))
        assert np.array_equal(base, picks)

    def test_use_pallas_cross_gram(self):
        st, y_raw, n, best_y = _problem(seed=7)
        cand = np.random.default_rng(8).random((33, 3)).astype(np.float32)
        base = np.asarray(gp.select_batch(
            st, cand, y_raw, n, best_y, 3, use_pallas=True))
        picks = np.asarray(gp.select_batch_sharded(
            st, cand, y_raw, n, best_y, 3, use_pallas=True))
        assert np.array_equal(base, picks)


_TWO_DEVICE_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import numpy as np
import jax
from repro.core import gp

assert jax.local_device_count() == 2, jax.devices()
n, d, q = 18, 3, 3
rng = np.random.default_rng(0)
x = rng.random((n, d))
y = np.sin(3 * x[:, 0]) + (x[:, 1] - 0.4) ** 2 + 0.1 * rng.normal(size=n)
st = gp.fit(x, y, steps=15, pad_to=gp._bucket(n + q))
y_raw = np.zeros(int(st.x.shape[0]), np.float32)
y_raw[:n] = y
best_y = float(np.min(y))
cand = rng.random((41, d)).astype(np.float32)   # odd: one pad row/shard
base = np.asarray(gp.select_batch(st, cand, y_raw, n, best_y, q))
picks = np.asarray(gp.select_batch_sharded(st, cand, y_raw, n, best_y, q))
assert np.array_equal(base, picks), (base, picks)
print("IDENTICAL", base.tolist())
"""


class TestForcedTwoDevices:
    def test_picks_identical_across_two_devices(self):
        """The shard_map mesh partitions the pool over 2 forced CPU
        devices and still reproduces select_batch bit for bit."""
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (str(REPO / "src"),
                        env.get("PYTHONPATH", "")) if p)
        out = subprocess.run(
            [sys.executable, "-c", _TWO_DEVICE_SCRIPT], env=env,
            capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, f"{out.stdout}\n{out.stderr}"
        assert "IDENTICAL" in out.stdout


class TestStrategyGate:
    def _space(self, d=3):
        return Space(tuple(Knob(f"x{i}", "float", 0.5, lo=0.0, hi=1.0)
                           for i in range(d)))

    def _run(self, shard_candidates):
        cfg = BOConfig(n_init=5, n_iter=6, batch_size=2, n_candidates=48,
                       n_local=16, fit_steps=15, seed=11,
                       shard_candidates=shard_candidates)
        strat = BOStrategy(self._space(), cfg)
        rng = np.random.default_rng(12)
        while not strat.finished:
            probes = strat.ask()
            if not probes:
                break
            vals = [float(np.sum((np.array([c[f"x{i}"] for i in range(3)])
                                  - 0.3) ** 2)
                          + 0.01 * rng.standard_normal())
                    for c in probes]
            # deterministic objective noise per config order: both runs
            # see identical values because picks must be identical
            strat.tell(probes, vals)
        return strat.trace

    def test_gate_never_changes_trace(self):
        """shard_candidates=True on this host (single device: fallback;
        multi-device: bit-identical sharded picks) reproduces the
        gate-off trace config for config."""
        t_off = self._run(False)
        t_on = self._run(True)
        assert t_off.configs == t_on.configs
        assert t_off.values == t_on.values

    def test_shard_devices_gate(self):
        cfg = BOConfig(shard_candidates=True)
        strat = BOStrategy(self._space(), cfg)
        devs = strat._shard_devices()
        if len(jax.devices()) == 1:
            assert devs is None              # nothing to shard over
        else:
            assert len(devs) == len(jax.devices())
        strat.cfg = BOConfig(shard_candidates=False)
        assert strat._shard_devices() is None
