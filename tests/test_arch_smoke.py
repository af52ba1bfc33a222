"""Per-architecture smoke tests: reduced same-family configs on CPU.

Every assigned architecture must (a) build, (b) run one forward/loss,
(c) run one TRAIN step, (d) prefill + decode one token — all with finite
outputs and the expected shapes.  The FULL configs are exercised only by
the dry-run (no allocation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models.config import applicable_shapes
from repro.models.model import Model
from repro.runconfig import RunConfig
from repro.train.train_loop import init_state, make_train_step


def _batch(cfg, B=2, S=16):
    b = {"tokens": jnp.ones((B, S), jnp.int32) * 3,
         "labels": jnp.ones((B, S), jnp.int32) * 5}
    if cfg.is_encoder_decoder:
        b["frames"] = jnp.zeros((B, cfg.encoder_seq, cfg.d_model),
                                jnp.bfloat16)
    if cfg.mrope_sections is not None:
        b["positions"] = jnp.zeros((3, B, S), jnp.int32)
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_loss_finite(arch):
    cfg = get_smoke_config(arch)
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    loss, mets = m.loss(params, _batch(cfg), RunConfig())
    assert np.isfinite(float(loss))
    assert float(loss) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step(arch):
    cfg = get_smoke_config(arch)
    m = Model(cfg)
    rc = RunConfig(microbatch=1)        # exercise accumulation too
    state = init_state(m, jax.random.key(0), rc)
    step = jax.jit(make_train_step(m, rc, lr_schedule=lambda s: 1e-3))
    b = _batch(cfg, B=2, S=16)
    state2, mets = step(state, b)
    assert np.isfinite(float(mets["loss"]))
    assert int(state2.step) == 1
    # parameters actually moved
    d0 = jax.tree.leaves(state.params)[0]
    d1 = jax.tree.leaves(state2.params)[0]
    assert not np.allclose(np.asarray(d0, np.float32),
                           np.asarray(d1, np.float32))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode(arch):
    cfg = get_smoke_config(arch)
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    rc = RunConfig()
    inputs = {"tokens": jnp.ones((2, 8), jnp.int32)}
    if cfg.is_encoder_decoder:
        inputs["frames"] = jnp.zeros((2, cfg.encoder_seq, cfg.d_model),
                                     jnp.bfloat16)
    logits, st = m.prefill(params, inputs, 16, rc)
    assert logits.shape == (2, 1, cfg.vocab_size)
    logits2, st2 = m.decode_step(params, jnp.ones((2, 1), jnp.int32), st, rc)
    assert logits2.shape == (2, 1, cfg.vocab_size)
    assert not bool(jnp.isnan(logits2).any())
    assert int(st2.pos[0]) == int(st.pos[0]) + 1


@pytest.mark.parametrize("arch", ["yi-6b", "xlstm-1.3b", "lfm2-8b-a1b"])
def test_decode_matches_forward(arch):
    """Teacher-forced decode == full forward logits (cache correctness; for
    lfm2-8b-a1b the conv state and the attention cache side by side)."""
    cfg = get_smoke_config(arch)
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    rc = RunConfig()
    toks = jax.random.randint(jax.random.key(1), (1, 8), 1, cfg.vocab_size)
    full, _ = __import__("repro.models.transformer", fromlist=["forward"]) \
        .forward(params, toks, cfg, rc)
    from repro.models import transformer
    state = transformer.init_decode_state(1, 16, cfg, rc)
    outs = []
    for t in range(8):
        logits, state = transformer.decode_step(params, toks[:, t:t + 1],
                                                state, cfg, rc)
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full, np.float32),
                               atol=0.15, rtol=0.05)   # bf16 params


def test_long_context_applicability():
    """long_500k runs only for sub-quadratic stacks (DESIGN.md §6)."""
    runs = {a: [c.name for c in applicable_shapes(get_config(a))]
            for a in ARCH_IDS}
    assert "long_500k" in runs["xlstm_1_3b"]
    assert "long_500k" in runs["jamba_1_5_large_398b"]
    for dense in ("yi_6b", "mistral_nemo_12b", "grok_1_314b", "whisper_tiny"):
        assert "long_500k" not in runs[dense]


def test_exact_assigned_dimensions():
    """Configs carry the exact assignment numbers."""
    spec = {
        "xlstm_1_3b": (48, 2048, 4, 4, 50304),
        "qwen2_vl_72b": (80, 8192, 64, 8, 152064),
        "mistral_nemo_12b": (40, 5120, 32, 8, 131072),
        "codeqwen1_5_7b": (32, 4096, 32, 32, 92416),
        "yi_6b": (32, 4096, 32, 4, 64000),
        "qwen1_5_4b": (40, 2560, 20, 20, 151936),
        "grok_1_314b": (64, 6144, 48, 8, 131072),
        "qwen2_moe_a2_7b": (24, 2048, 16, 16, 151936),
        "jamba_1_5_large_398b": (72, 8192, 64, 8, 65536),
        "whisper_tiny": (4, 384, 6, 6, 51865),
        "lfm2_8b_a1b": (24, 2048, 32, 8, 65536),
    }
    for a, (L, d, H, kv, V) in spec.items():
        c = get_config(a)
        assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads,
                c.vocab_size) == (L, d, H, kv, V), a
    assert get_config("grok_1_314b").n_experts == 8
    assert get_config("qwen2_moe_a2_7b").n_experts == 60
    assert get_config("qwen2_moe_a2_7b").n_experts_per_tok == 4
    assert get_config("jamba_1_5_large_398b").n_experts == 16
    lfm2 = get_config("lfm2_8b_a1b")
    assert (lfm2.n_experts, lfm2.router_width, lfm2.n_experts_per_tok,
            lfm2.moe_d_ff, lfm2.d_ff, lfm2.conv_width) == (32, 32, 4, 1792,
                                                           7168, 3)
    assert [s.kind for s in lfm2.pattern].count("attn") == 6
    assert [i for i, s in enumerate(lfm2.pattern) if s.kind == "attn"] == \
        [2, 6, 10, 14, 18, 21]
    assert [s.mlp for s in lfm2.pattern] == ["dense"] * 2 + ["moe"] * 22
