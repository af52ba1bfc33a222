"""The peak table and the operation counts of ``flops``: each count's
full-tile form agrees with ``repro.launch.roofline``'s count of the HLO
that XLA compiles for the same call on the CPU."""

import jax
import jax.numpy as jnp
import pytest

import flops
import harness
from conftest import TinyCell, load_driver


def hlo_flops(fn, *args) -> float:
    from repro.launch.roofline import analyze_hlo
    return analyze_hlo(jax.jit(fn).lower(*args).compile().as_text()).flops


def test_unknown_device_kind_raises():
    assert harness.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peak("cpu")


def test_flash_attention_count_matches_hlo():
    from repro.models.attention import reference_attention
    b, s, h, kh, d = 1, 64, 4, 2, 16
    q = jnp.ones((b, s, h, d))
    k = jnp.ones((b, s, kh, d))
    got = hlo_flops(lambda q, k, v: reference_attention(
        q, k, v, causal=True, window=None, softcap=None), q, k, k)
    full, _ = flops.flash_attention(b, s, h, kh, d, causal=False)
    assert got == pytest.approx(full, rel=0.01)
    causal, _ = flops.flash_attention(b, s, h, kh, d)
    assert causal == pytest.approx(full * (s + 1) / (2 * s))


def test_mlstm_chunk_count_matches_hlo():
    from repro.kernels.mlstm_chunk.ref import mlstm_chunked
    b, s, h, p, c = 1, 128, 2, 32, 32
    x = jnp.ones((b, s, h, p))
    g = jnp.ones((b, s, h))
    got = hlo_flops(lambda q, k, v, li, lf: mlstm_chunked(
        q, k, v, li, lf, chunk=c), x, x, x, g, g)
    full, _ = flops.mlstm_chunk(b, s, h, p, c, causal=False)
    assert got == pytest.approx(full, rel=0.05)
    assert flops.mlstm_chunk(b, s, h, p, c)[0] < full


def test_gp_gram_count_matches_hlo():
    from repro.core.gp import matern52
    n, m, d = 64, 96, 16
    got = hlo_flops(lambda a, b: matern52(a, b, jnp.ones((d,)), 1.0),
                    jnp.ones((n, d)), jnp.ones((m, d)))
    assert got == pytest.approx(flops.gp_gram(n, m, d)[0], rel=0.01)


def test_train_step_count_matches_hlo():
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.train.train_loop import init_state, make_train_step
    T = load_driver("train")
    cell = TinyCell("yi6b.train_4k")
    cfg = T.model_config(cell)
    rc = T.run_config(cell, get_config("yi-6b")).replace(
        attention_impl="reference")
    model = Model(cfg)
    state = jax.eval_shape(lambda: init_state(model, jax.random.key(0), rc))
    b, s = cell.traffic["batch"], cell.traffic["seq"]
    batch = {k: jax.ShapeDtypeStruct((b, s), jnp.int32)
             for k in ("tokens", "labels")}
    got = hlo_flops(make_train_step(model, rc, lambda t: 1e-5), state, batch)
    want = flops.train_step(
        state.params, batch=b, seq=s, attn_layers=cfg.n_layers,
        heads=cfg.n_heads, head_dim=cfg.resolved_head_dim, causal=False)
    assert got == pytest.approx(want, rel=0.05)


def test_slstm_recurrent_weight_counts_its_block_diagonal_shape():
    """The sLSTM recurrence is 4 gates × 4 blocks of (d/4)², not 4·d²."""
    from repro.models.model import Model
    T = load_driver("train")
    cell = TinyCell("xlstm.train_4k")
    cfg = T.model_config(cell)
    shapes = jax.eval_shape(lambda: Model(cfg).init(jax.random.key(0)))
    rec = shapes["layers"][7]["slstm"]["w_rec"]
    d = cfg.d_model
    assert rec.shape == (1, 4, 4, d // 4, d // 4)
    with_rec = flops.train_step(shapes, batch=1, seq=1)
    no_rec = dict(shapes)
    no_rec["layers"] = [dict(p) for p in shapes["layers"]]
    no_rec["layers"][7] = {**shapes["layers"][7],
                           "slstm": {k: v for k, v in
                                     shapes["layers"][7]["slstm"].items()
                                     if k != "w_rec"}}
    without = flops.train_step(no_rec, batch=1, seq=1)
    assert with_rec - without == pytest.approx(3 * 2 * 4 * d * d // 4)
