"""Train driver for a configuration that fixes its weights: the train
driver (``drivers/train.py``) with the weights drawn from the
configuration file's ``weight_seed`` and only the token batches from the
run's seed.  Every run of the cell then times one model, as a deployment
trains one checkpoint, on batches of its own.

A sparse-expert model needs it.  Which experts a token picks follows the
router's and the embedding's weights, so weights drawn anew from each
run's seed send each run another share of the rows to the experts the chip
holds, and the dropless layer's time follows that share: the runs would
measure which router their seed drew.  The check is unchanged: the
reference follows the program from the same fixed weights and the run's
batches.

Beside that, the driver reads the step's ``moe_held_rows`` in every step
of the window and counts the grouped products of ``megablox_gmm`` at the
rows the window really held (``flops/kernels/megablox_gmm.step_count``),
where set-up counted what even routing sends; the traced context carries
the window's mean as ``moe_held_rows``.  Everything else is the train
driver's own.
"""

from __future__ import annotations

from pathlib import Path

import jax
import numpy as np

import flops as flopsmod
import harness

# A private copy of the train driver: its weights key is replaced here,
# and other users of drivers/train.py in the same process keep theirs.
_T = harness.load_module(Path(__file__).with_name("train.py"),
                         "bench_driver_train_for_fixed_weights")
_run_weights_key = _T.weights_key
_config = {}
GMM = "megablox_gmm"


def config_key(cell):
    """The key the configuration's ``weight_seed`` gives, where the train
    driver takes the run's."""
    return jax.random.key(harness.seed_words(int(cell.config["weight_seed"])))


def _use(key):
    """Make ``key`` the one the train driver's code draws weights from:
    each entry point below sets it before it runs that code."""
    _config["key"] = key
    return key


# Derived at each call, as the train driver derives it: the draws then wait
# on a computation queued behind the step in flight, and are not placed
# beside that step's temporaries (a key made once ahead ran the chip out of
# memory in the check's draws).
_T.weights_key = lambda key: _run_weights_key(_config["key"])


class Program(_T.Program):
    """The train driver's program, from the configuration's weights, with
    the held rows of every step read."""

    def __init__(self, cell, seed: int, fault: str = None):
        self.config_key = _use(config_key(cell))
        super().__init__(cell, seed, fault=fault)
        self.held = []
        if self.cfg.has_moe:
            step = self.run_step

            def run_step(state, batch):
                state, mets = step(state, batch)
                self.held.append(mets["moe_held_rows"])
                return state, mets
            self.run_step = run_step

    def check_steps(self) -> dict:
        _use(self.config_key)
        return super().check_steps()

    def window(self, seconds: float) -> dict:
        self.held = []
        out = super().window(seconds)
        if self.held:
            rows = float(np.sum(jax.device_get(self.held))) / out["steps"]
            calls = dict(self.counts["kernel_calls"])
            if GMM in calls:
                calls[GMM] = flopsmod.kernel_modules()[GMM].step_count(
                    self.cfg, rows)
            self.counts = {**self.counts, "kernel_calls": calls,
                           "moe_held_rows": rows}
        return out


def reference_readings(cell, seed: int, mode: str = "f32",
                       fault: str = None) -> dict:
    _use(config_key(cell))
    return _T.reference_readings(cell, seed, mode=mode, fault=fault)


_T.Program = Program


def __getattr__(name):
    """Everything else is the train driver's (``run``, ``gaps``,
    ``model_config``, ...); its ``run`` builds this module's
    :class:`Program`."""
    return getattr(_T, name)
