"""Each driver, called directly at a smoke size on the CPU, runs a window
and checks what its timed path produced: a sound run comes out correct,
the control and every fault planted under the timed path come out not
correct.  The smoke size has limits of its own, set from its own readings
(the cell's limits come from chip runs at the real size)."""

import copy
import json
import math
import shutil
import subprocess
import sys
import time
import types

import jax
import pytest

import harness
from conftest import JAMBA_SMOKE, TinyCell, load_driver

# Smoke-size limits of every number the train driver can compare, each set
# between the program's reading at SEED on the CPU and the least of the fp8
# control's and the faults' (bfloat16 program vs float32 reference):
#   yi-6b:  loss 7.8e-5 (control 3.8e-4), loss3 2.0e-4 (1.9e-3), grad 1.1e-3
#           (1.2e-2), grad median 5.3e-4 (5.3e-3), grad direction 7.3e-3
#           (8.4e-2), update 1.0e-3 (3.2e-3; half batch 0.18), update median
#           3.1e-4 (2.4e-3);
#   xlstm-1.3b at width 128: loss 5.6e-4 (3.1e-3), loss3 1.6e-3 (1.3e-2),
#           grad 0.14 (0.30), grad median 0.027 (0.075), grad direction 0.36
#           (0.82), update 0.051 (0.078), update median 9.4e-3 (0.018).
# The cell's limits file names the numbers compared; the tests compare the
# same ones.
SMOKE = {"yi6b.train_4k": {"loss_gap": 2e-4, "loss3_gap": 8e-4,
                           "grad_gap": 5e-3, "grad_median_gap": 2e-3,
                           "grad_dir_gap": 0.03, "update_gap": 2.5e-3,
                           "update_median_gap": 1e-3},
         "xlstm.train_4k": {"loss_gap": 1.5e-3, "loss3_gap": 6e-3,
                            "grad_gap": 0.25, "grad_median_gap": 0.05,
                            "grad_dir_gap": 0.6, "update_gap": 0.07,
                            "update_median_gap": 0.014}}
TRAIN_CELLS = sorted(SMOKE)


def smoke_limits(name):
    compared = harness.load_json(harness.HERE / "limits" / f"{name}.json")
    return {k: SMOKE[name][k] for k in compared}


TUNE_LIMITS = {"pick_gap": 1e-4, "told_gap": 0.0, "lasso_gap": 1e-2}
SEED = 2 ** 33 + 5


def _run(driver, cell, seed=SEED, fault=None, trace=False):
    return driver.run(cell, seed=seed, seconds=0.5, trace=trace,
                      t0=time.monotonic(), devices=jax.devices(),
                      fault=fault)


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_train_cell_is_correct(name, cpu_peak):
    import run as runmod
    cell = TinyCell(name, smoke_limits(name))
    res = _run(load_driver("train"), cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    line = runmod.result_line(cell, res, False)
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(line)[-1] == "checks"
    json.dumps(line)


def test_train_traced_run_reads_its_metrics(cpu_peak):
    import run as runmod
    cell = TinyCell("yi6b.train_4k", smoke_limits("yi6b.train_4k"))
    res = _run(load_driver("train"), cell, trace=True)
    # a CPU run has no Mosaic kernel to tag: the roofline the cell lists
    # finds nothing, and the run refuses to print a line without it
    with pytest.raises(runmod.SilentMetric, match="flash_attention"):
        runmod.result_line(cell, res, True)
    calls = max(res["trace"]["steps"], 1)
    res["trace"]["kernels"]["flash_attention"] = [1.0] * calls
    line = runmod.result_line(cell, res, True)
    assert set(line["metrics"]) == {m["name"] for m in cell.per_layer()}
    assert 0 < line["metrics"]["flash_attention_roofline"]["value"] < 100
    assert line["device"]["busy_s"] > 0
    assert line["breakdown"]["device_ops"]


@pytest.mark.parametrize("name", TRAIN_CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_train_fault_is_caught(name, fault, cpu_peak):
    res = _run(load_driver("train"), TinyCell(name, smoke_limits(name)),
               fault=fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_train_control_is_rejected(name):
    """The reference in bf16's place at fp8 (the control) fails a limit of
    a number the cell compares."""
    T = load_driver("train")
    limits = smoke_limits(name)
    cell = TinyCell(name, limits)
    ref = T.reference_readings(cell, SEED)
    control = T.reference_readings(cell, SEED, mode="fp8")
    _, correct = harness.judge(T.gaps(control, ref), limits)
    assert not correct


def test_tune_cell_is_correct(cpu_peak):
    import run as runmod
    cell = TinyCell("yi6b.tune_256", TUNE_LIMITS)
    res = _run(load_driver("tune"), cell)
    assert res["correct"], res["checks"]
    assert res["picks_checked"] > 0
    line = runmod.result_line(cell, res, False)
    assert set(line["metrics"]) == {"tune_evals_per_s", "tune_ask_ms_p95",
                                    "setup_s"}


@pytest.mark.parametrize("fault", ["altered", "unchanged", "half_batch"])
def test_tune_fault_is_caught(fault, cpu_peak):
    res = _run(load_driver("tune"), TinyCell("yi6b.tune_256", TUNE_LIMITS),
               fault=fault)
    assert not res["correct"], res["checks"]
    assert any(not math.isfinite(c["value"]) or c["value"] > c["limit"]
               for c in res["checks"].values())


def test_run_refuses_a_cpu():
    import run as runmod
    assert runmod.main(["--workload", "yi6b.train_4k", "--seed", "1",
                        "--seconds", "1"]) == 1


def test_run_refuses_without_the_system(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "yi6b.train_4k", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


# -- every stated width is checked ------------------------------------------

@pytest.mark.parametrize("key,value", [("n_experts", 16),
                                       ("n_routed_experts", 4),
                                       ("mlp", ["moe"] * 8)])
def test_stated_widths_are_checked(key, value):
    """The file's ``model`` states what runs: a key the system runs with
    another value, or has no setting for, fails naming it (the file as
    it stands passes, ``test_added_files.py``)."""
    conf = copy.deepcopy(JAMBA_SMOKE)
    conf["model"][key] = value
    with pytest.raises(ValueError, match=key):
        load_driver("train").model_config(types.SimpleNamespace(config=conf))
