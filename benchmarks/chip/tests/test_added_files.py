"""A configuration with layer kinds no cell runs yet (mamba and moe), a new
kernel and its roofline reader enter a copy of the benchmark as added
files only: the copy's harness tags the kernel's calls, counts its
operations and the model's FLOPs, and reads the new metric, and no file
the copy had changes."""

import copy
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import harness
from conftest import CPU_PEAK, JAMBA_SMOKE, weight_flops

CELL = "jamba.train_smoke"
TAG = "ssd_chunk_demo"

KERNEL = '''"""A made-up SSD chunk kernel: the forward's state update and
readout over the mamba layers' heads."""

MATCH = ("ssd_chunk_demo",)


def call(cfg, rc, traffic):
    if "mamba" not in {s.kind for s in cfg.pattern}:
        return None
    from repro.models import ssm
    di, nh, n = ssm.dims(cfg)
    tokens = traffic["batch"] * traffic["seq"]
    return 4.0 * tokens * nh * n * (di // nh), 2.0 * tokens * 3 * di * 2
'''

READER = '''"""Roofline share of the made-up SSD chunk kernel, in percent."""

import flops


def read(ctx):
    durations = ctx["kernels"].get("ssd_chunk_demo")
    if not durations or "ssd_chunk_demo" not in ctx["kernel_calls"]:
        return None
    least = flops.roofline_seconds(*ctx["kernel_calls"]["ssd_chunk_demo"],
                                   ctx["peak"])
    return 100.0 * least * len(durations) / sum(durations)
'''

HLO = ('  %custom-call.3 = bf16[2,64,128]{2,1,0} custom-call(%a), '
       'custom_call_target="tpu_custom_call", metadata={op_name='
       '"jit(step_fn)/jit(ssd_chunk_demo)/pallas_call"}\n'
       '  %fusion.1 = f32[8]{0} fusion(%b), kind=kLoop\n')

# Run in the copy, as a checkout runs its harness: the copy's modules on
# the path, the step's counts and a trace context built as drivers/train
# builds them, and the result line that run.py would print.
SCRIPT = '''import json, sys
sys.path.insert(0, "benchmarks/chip")
import harness
import jax
import run as runmod
import trace as tracemod
from repro.configs import get_config
from repro.models.model import Model

cell = harness.Cell(%(cell)r)
T = cell.driver()
cfg = T.model_config(cell)
rc = T.run_config(cell, get_config(cell.config["arch"]))
shapes = jax.eval_shape(lambda: Model(cfg).init(jax.random.key(0)))
counts = T.counts(cell, cfg, rc, shapes)
steps = 4
summary = {"window_s": 1.0, "busy_s": 0.75, "devices": 1,
           "kernels": {%(tag)r: [2e-5] * (7 * steps)},
           "breakdown": {"device_ops": [], "idle_gaps": []}}
ctx = T.trace_context(counts, summary, steps, %(peak)r)
res = {"correct": True, "attempted": steps, "failed": 0, "checks": {},
       "device": {"platform": "cpu"}, "trace": ctx}
line = runmod.result_line(cell, res, True)
print(json.dumps({"here": str(harness.HERE), "counts": counts,
                  "ops": tracemod.kernel_ops(%(hlo)r),
                  "metrics": line["metrics"]}))
'''


def _hashes(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()
                                                     ).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def _write(path, text):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def _add_cell(tmp_path):
    """The files a configuration PR would add, and its entries in
    ``BENCHMARK.json``."""
    chip = tmp_path / "benchmarks" / "chip"
    _write(chip / "configs" / "jamba-smoke.json", json.dumps(JAMBA_SMOKE))
    traffic = harness.load_json(harness.HERE / "traffic" /
                                "train_4k.flash.json")
    traffic.update({"batch": 2, "seq": 64, "pool": 2})
    _write(chip / "traffic" / "train_smoke.jamba.json", json.dumps(traffic))
    _write(chip / "limits" / f"{CELL}.json", json.dumps({"update_gap": 0.1}))
    _write(chip / "flops" / "kernels" / f"{TAG}.py", KERNEL)
    _write(chip / "metrics" / f"{TAG}_roofline.py", READER)
    bench = copy.deepcopy(harness.load_json(harness.ROOT / "BENCHMARK.json"))
    bench["configs"].append({
        "name": JAMBA_SMOKE["name"], "source": JAMBA_SMOKE["source"],
        "file": "benchmarks/chip/configs/jamba-smoke.json",
        "reduced": ["n_experts"], "why": "mamba and moe"})
    bench["workloads"].append({"name": CELL, "config": JAMBA_SMOKE["name"],
                               "traffic": "train_smoke.jamba", "chips": 1,
                               "why": "smoke size"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("train_tokens_per_s", "mfu.train",
                         "idle_share.train"):
            m["workloads"].append(CELL)
    bench["per_layer"].append({"name": f"{TAG}_roofline", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "kernels",
                               "moves": "train_tokens_per_s",
                               "workloads": [CELL]})
    _write(tmp_path / "BENCHMARK.json", json.dumps(bench))


def test_configuration_and_kernel_enter_by_added_files(tmp_path):
    from repro.configs import get_smoke_config
    from repro.models import ssm
    from repro.models.model import Model
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(harness.HERE, chip, ignore=shutil.ignore_patterns(
        "__pycache__", ".pytest_cache"))
    before = _hashes(chip)
    _add_cell(tmp_path)
    script = SCRIPT % {"cell": CELL, "tag": TAG, "peak": CPU_PEAK,
                       "hlo": HLO}
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONDONTWRITEBYTECODE": "1",
           "PYTHONPATH": str(harness.SRC)}
    out = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    after = _hashes(chip)
    assert got["here"] == str(chip)
    assert {k: after[k] for k in before} == before

    assert got["ops"] == {"custom-call.3": TAG}
    cfg = get_smoke_config("jamba-1.5-large-398b")
    di, nh, n = ssm.dims(cfg)
    b, s = 2, 64
    assert got["counts"]["kernel_calls"][TAG] == \
        [4.0 * b * s * nh * n * (di // nh), 2.0 * b * s * 3 * di * 2]
    # 7 mamba layers at 4·N·P a head and token, the experts at 2 of the 16
    # published, one attention layer of 4 heads of 16
    routed, other = weight_flops(jax.eval_shape(
        lambda: Model(cfg).init(jax.random.key(0))))
    want = 3 * (b * s * (other + routed * 2 / 16 + 7 * 4 * n * di) +
                4 * b * 4 * 16 * s * (s + 1) / 2)
    assert got["counts"]["flops_per_step"] == pytest.approx(want, rel=1e-12)
    metrics = got["metrics"]
    assert set(metrics) == {"idle_share.train", "mfu.train",
                            f"{TAG}_roofline"}
    assert 0 < metrics[f"{TAG}_roofline"]["value"] < 100
    assert metrics["idle_share.train"]["value"] == 25.0
