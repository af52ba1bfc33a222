"""lfm2moe.train_4k: its configuration file passes ``drivers/train.py``'s
check of every stated width, its counts are pinned at the real size, and
at a smoke size on the CPU its driver (``drivers/train_fixed_weights.py``)
runs it (the grouped-matmul kernel in interpret mode) and checks it
against the reference: a sound run comes out correct, the control and
every planted fault do not.  The driver draws the weights from the
configuration's ``weight_seed`` and the batches from the run's seed, and
counts the grouped products at the rows the window held."""

import copy
import time

import jax
import numpy as np
import pytest

import harness
from conftest import CPU_PEAK, TINY_TRAIN, load_driver

CELL = "lfm2moe.train_4k"
DRIVER = "train_fixed_weights"
GMM = "megablox_gmm"

# At the real size (CPU count from the shapes, no allocation): 382.3 M
# model FLOPs a token forward, 3x for the step of 4 x 4096 tokens; the
# flash forward's count per call at head_dim 64; the whole step's grouped
# products, 36 of 2·R·d·f at R = 16,384 rows (4·4096·4·8/32).
PINNED = {"flops_per_step": 18790842630144.0,
          "kernel_calls": {
              "flash_attention": (274945015808.0, 167772160.0),
              GMM: (4329327034368.0, 6643777536.0)}}

# The smoke shape: the cut's five layers at width 64, 2 of 8 experts held.
TINY = {"n_layers": 5, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "moe_d_ff": 32, "vocab_size": 256,
        "n_experts": 2, "router_experts": 8}

# Smoke-size limits, each between the program's readings on the CPU
# (bfloat16 program vs float32 reference; SEED and SEED + 1) and the least
# of the fp8 control's and the half-sequence fault's at SEED:
#   loss3 6.7e-5, 9.9e-4 (control 3.2e-3, half 1.6e-2); grad direction
#   0.070, 0.089 (control 0.47, half 0.72); update 6.3e-3, 1.0e-2 (control
#   0.024, half 0.033; a state left unchanged reads 1).
SMOKE_LIMITS = {"loss3_gap": 2e-3, "grad_dir_gap": 0.2, "update_gap": 0.05}
SEED = 2 ** 33 + 17


class TinyCell(harness.Cell):
    def __init__(self, limits=None):
        super().__init__(CELL, limits=limits)
        self.config = copy.deepcopy(self.config)
        self.traffic = copy.deepcopy(self.traffic)
        self.config["model"].update(TINY)
        self.config["overrides"].update(TINY)
        self.config["reduced"]["n_experts"]["published"] = 8
        self.traffic.update(TINY_TRAIN)


def _counts(cell):
    from repro.configs import get_config
    from repro.models.model import Model
    T = load_driver(DRIVER)
    cfg = T.model_config(cell)
    rc = T.run_config(cell, get_config(cell.config["arch"]))
    shapes = jax.eval_shape(lambda: Model(cfg).init(jax.random.key(0)))
    return cfg, rc, T.counts(cell, cfg, rc, shapes)


def test_counts_are_pinned():
    cfg, rc, counts = _counts(harness.Cell(CELL))
    assert counts == PINNED
    assert (rc.moe_impl, rc.microbatch, rc.attention_impl) == \
        ("grouped", 4, "flash")
    # 36 products of 2·R·d·f: three a matrix, three matrices, four layers
    rows = 4 * 4096 * 4 * 8 / 32
    assert counts["kernel_calls"][GMM][0] == \
        36 * 2.0 * rows * 2048 * 1792


def test_file_states_what_runs():
    """The file's ``model`` passes ``drivers/train.model_config``'s check
    of every stated key, and its copy of the catalog's config gives the
    same model."""
    cell = harness.Cell(CELL)
    cfg = load_driver("train").model_config(cell)
    c = cell.config
    kinds = {"conv": "conv", "full_attention": "attn"}
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["num_experts"],
            c["num_experts_per_tok"], c["num_hidden_layers"],
            c["vocab_size"], c["conv_L_cache"], c["norm_eps"],
            c["rope_theta"]) == \
        (cfg.d_model, cfg.d_ff, cfg.moe_d_ff, cfg.n_heads, cfg.n_kv_heads,
         cfg.n_experts, cfg.n_experts_per_tok, cfg.n_layers, cfg.vocab_size,
         cfg.conv_width, cfg.norm_eps, cfg.rope_theta)
    assert [kinds[t] for t in c["layer_types"]] == \
        [s.kind for s in cfg.pattern]
    assert [s.mlp for s in cfg.pattern][:c["num_dense_layers"]] == ["dense"]
    assert cfg.router_width == c["reduced"]["n_experts"]["published"] == 32
    assert (cfg.tie_embeddings, cfg.expert_bias, cfg.router_score,
            cfg.router_aux_coef) == (True, True, "sigmoid", 0.0)


def _run(cell, fault=None, trace=False, seed=SEED):
    return load_driver(DRIVER).run(
        cell, seed=seed, seconds=0.5, trace=trace, t0=time.monotonic(),
        devices=jax.devices(), fault=fault)


@pytest.fixture
def cpu_peak(monkeypatch):
    monkeypatch.setattr(harness, "peak", lambda kind: CPU_PEAK)


def test_smoke_cell_is_correct_and_reads_its_metrics(cpu_peak):
    import run as runmod
    cell = TinyCell(SMOKE_LIMITS)
    res = _run(cell, trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    # a CPU run has no Mosaic kernel to tag: give the grouped matmul's
    # calls and the flash kernels' (a forward and two backward calls of the
    # one attention layer) a duration, as the chip's trace would
    steps = res["trace"]["steps"]
    res["trace"]["kernels"][GMM] = [1e-3] * (36 * steps)
    res["trace"]["kernels"]["flash_attention"] = [1e-3] * (3 * steps)
    line = runmod.result_line(cell, res, True)
    assert set(line["metrics"]) == {"mfu.train", "idle_share.train",
                                    "grouped_matmul_roofline",
                                    "flash_attention_roofline"}
    for name in ("grouped_matmul_roofline", "flash_attention_roofline"):
        assert 0 < line["metrics"][name]["value"] < 100


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_smoke_fault_is_caught(fault, cpu_peak):
    res = _run(TinyCell(SMOKE_LIMITS), fault=fault)
    assert not res["correct"], res["checks"]


def test_smoke_control_is_rejected():
    T = load_driver(DRIVER)
    cell = TinyCell(SMOKE_LIMITS)
    ref = T.reference_readings(cell, SEED)
    control = T.reference_readings(cell, SEED, mode="fp8")
    _, correct = harness.judge(T.gaps(control, ref), SMOKE_LIMITS)
    assert not correct


def _master(prog):
    return [np.asarray(x) for x in
            jax.device_get(jax.tree.leaves(prog.state.opt_state.master))]


def test_weights_follow_the_configuration_and_batches_the_run():
    """Two run seeds: the same weights and other batches; another
    ``weight_seed``: other weights.  The reference draws the program's."""
    T = load_driver(DRIVER)
    cell = TinyCell(SMOKE_LIMITS)
    a, b = T.Program(cell, SEED), T.Program(cell, SEED + 1)
    assert all(np.array_equal(x, y) for x, y in zip(_master(a), _master(b)))
    assert not np.array_equal(a.batches[0]["tokens"], b.batches[0]["tokens"])
    other = TinyCell(SMOKE_LIMITS)
    other.config["weight_seed"] = cell.config["weight_seed"] + 1
    c = T.Program(other, SEED)
    assert not all(np.array_equal(x, y)
                   for x, y in zip(_master(a), _master(c)))
    readings = b.check_steps()
    _, correct = harness.judge(T.gaps(readings, T.reference_readings(
        cell, SEED + 1)), SMOKE_LIMITS)
    assert correct
    for p in (a, b, c):
        p.free()


def test_grouped_products_counted_at_the_held_rows(cpu_peak):
    """The traced context counts the grouped products at the window's mean
    held rows, which the routing sets: not the even count of set-up."""
    res = _run(TinyCell(SMOKE_LIMITS), trace=True)
    ctx = res["trace"]
    cfg = load_driver(DRIVER).model_config(TinyCell())
    gmm = load_driver(DRIVER).flopsmod.kernel_modules()[GMM]
    rows = ctx["moe_held_rows"]
    tokens = TINY_TRAIN["batch"] * TINY_TRAIN["seq"]
    assert 0 < rows <= 4 * tokens * 4
    assert ctx["kernel_calls"][GMM] == gmm.step_count(cfg, rows)
    even = gmm.step_count(cfg, 4 * tokens * 4 * 2 / 8)
    assert ctx["kernel_calls"][GMM] != even
