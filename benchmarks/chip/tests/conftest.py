"""Shared set-up of the benchmark's own tests (run on the CPU):

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

They drive the harness's pieces directly at smoke sizes; ``run.py`` itself
refuses any device but a TPU."""

import copy
import sys
from pathlib import Path

import jax
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

harness.ensure_src_on_path()

CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}

TINY_MODELS = {
    "yi6b.train_4k": {
        "model": {"n_layers": 1, "d_model": 64, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                  "vocab_size": 256},
        "overrides": {"n_layers": 1, "d_model": 64, "n_heads": 4,
                      "n_kv_heads": 2, "d_ff": 128, "vocab_size": 256}},
    "xlstm.train_4k": {
        "model": {"n_layers": 8, "d_model": 128, "n_heads": 4,
                  "n_kv_heads": 4, "vocab_size": 256},
        "overrides": {"n_layers": 8, "d_model": 128, "n_heads": 4,
                      "n_kv_heads": 4, "vocab_size": 256}},
}
TINY_TRAIN = {"seq": 128, "batch": 2, "pool": 4, "doc_len": 32}
TINY_TUNE = {"n_rank_samples": 40, "check_asks": 4, "check_rankings": 2,
             "bo": {"n_init": 6, "n_iter": 16, "n_candidates": 128,
                    "n_local": 32, "fit_steps": 30, "use_pallas": True}}

# A configuration file for a model with both layer kinds no cell runs yet,
# mamba and moe: jamba's smoke shape (``repro.configs``' SMOKE), 4 of a
# published 16 experts held.
JAMBA_SMOKE = {
    "name": "jamba-smoke", "arch": "jamba-1.5-large-398b",
    "source": "https://arxiv.org/abs/2408.12570",
    "model": {"n_layers": 8, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 16, "d_ff": 128, "vocab_size": 256,
              "n_experts": 4, "n_experts_per_tok": 2, "moe_d_ff": 128,
              "n_shared_experts": 0, "ssm_state_dim": 8, "ssm_expand": 2,
              "ssm_conv_width": 4, "tie_embeddings": False,
              "pattern": ["mamba"] * 4 + ["attn"] + ["mamba"] * 3,
              "mlp": ["dense", "moe"] * 4,
              "sliding_window": [None] * 8},
    "overrides": {"n_layers": 8, "d_model": 64, "n_heads": 4,
                  "n_kv_heads": 2, "d_ff": 128, "moe_d_ff": 128,
                  "vocab_size": 256, "n_experts": 4, "n_experts_per_tok": 2,
                  "ssm_state_dim": 8},
    "reduced": {"n_experts": {"published": 16, "run": 4,
                              "why": "a chip's share of the experts"}},
}


# The tuning-session cell waits, out of BENCHMARK.json, on a fault of the
# program (PERF.md, Open questions); its entry and end-to-end metrics as
# they will be listed, so that its driver stays tested.
TUNE_ENTRY = {"name": "yi6b.tune_256", "config": "yi-6b.pod256",
              "traffic": "tune_256", "chips": 1}
TUNE_E2E = [{"name": "tune_evals_per_s", "unit": "evals/s",
             "workloads": ["yi6b.tune_256"]},
            {"name": "tune_ask_ms_p95", "unit": "ms",
             "workloads": ["yi6b.tune_256"]}]


class TinyCell(harness.Cell):
    """A cell of BENCHMARK.json, or the tuning-session cell, at a smoke
    size."""

    def __init__(self, name, limits=None):
        bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
        entry = None
        if name == TUNE_ENTRY["name"]:
            entry = TUNE_ENTRY
            bench = {**bench, "end_to_end": bench["end_to_end"] + TUNE_E2E}
        super().__init__(name, bench=bench, entry=entry, limits=limits)
        self.config = copy.deepcopy(self.config)
        self.traffic = copy.deepcopy(self.traffic)
        if name in TINY_MODELS:
            self.config["model"].update(TINY_MODELS[name]["model"])
            self.config["overrides"] = TINY_MODELS[name]["overrides"]
            self.traffic.update(TINY_TRAIN)
            if "mlstm_chunk" in self.traffic["knobs"]:
                self.traffic["knobs"]["mlstm_chunk"] = 64
        else:
            self.traffic.update(TINY_TUNE)


@pytest.fixture
def cpu_peak(monkeypatch):
    monkeypatch.setattr(harness, "peak", lambda kind: CPU_PEAK)


def load_driver(name):
    mod = f"drivers_{name}"
    if mod not in sys.modules:
        sys.modules[mod] = harness.load_module(
            HERE / "drivers" / f"{name}.py", mod)
    return sys.modules[mod]


def weight_flops(shapes):
    """2 · elements of the leaves of rank ≥ 2 outside the embedding, as
    (routed experts', the others')."""
    routed = other = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        where = jax.tree_util.keystr(path)
        if len(leaf.shape) < 2 or where.startswith("['embed']"):
            continue
        if "['moe']['experts']" in where:
            routed += 2.0 * leaf.size
        else:
            other += 2.0 * leaf.size
    return routed, other
