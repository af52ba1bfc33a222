"""The peak table and the operation counts of ``flops``: each count's
full-tile form agrees with ``repro.launch.roofline``'s count of the HLO
that XLA compiles for the same call on the CPU."""

import jax
import jax.numpy as jnp
import pytest

import flops
import harness
from conftest import TinyCell, load_driver, weight_flops


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
    want = flops.train_step(state.params, cfg, batch=b, seq=s,
                            causal=False)
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
    with_rec = flops.train_step(shapes, cfg, batch=1, seq=1)
    no_rec = dict(shapes)
    no_rec["layers"] = [dict(p) for p in shapes["layers"]]
    no_rec["layers"][7] = {**shapes["layers"][7],
                           "slstm": {k: v for k, v in
                                     shapes["layers"][7]["slstm"].items()
                                     if k != "w_rec"}}
    without = flops.train_step(no_rec, cfg, batch=1, seq=1)
    assert with_rec - without == pytest.approx(3 * 2 * 4 * d * d // 4)


# -- the cells' counts, pinned -----------------------------------------------

# Each cell's model FLOPs a step and per-call kernel counts at its own
# configuration, as the benchmark has read them since its first PR: a
# change to how the counts are found may not move them.
PINNED = {
    "yi6b.train_4k": {
        "flops_per_step": 11107087417344.0,
        "kernel_calls": {"flash_attention": (137472507904.0, 75497472.0)}},
    "xlstm.train_4k": {
        "flops_per_step": 17761586970624.0,
        "kernel_calls": {"mlstm_chunk": (81654710272.0, 134348800.0)}},
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_cell_counts_are_pinned(name):
    from repro.configs import get_config
    from repro.models.model import Model
    T = load_driver("train")
    cell = harness.Cell(name)
    cfg = T.model_config(cell)
    rc = T.run_config(cell, get_config(cell.config["arch"]))
    shapes = jax.eval_shape(lambda: Model(cfg).init(jax.random.key(0)))
    assert T.counts(cell, cfg, rc, shapes) == PINNED[name]


# -- the model FLOPs of each layer kind, by hand -----------------------------

def _shapes(cfg):
    from repro.models.model import Model
    return jax.eval_shape(lambda: Model(cfg).init(jax.random.key(0)))


@pytest.mark.parametrize("published", [None, 60])
def test_routed_experts_count_k_over_published(published):
    """qwen2-moe's smoke shape holds 8 experts, 2 a token: its expert
    weights count at 2/8 of their dense count, or at 2/60 where 60 are
    published and 8 held."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("qwen2-moe-a2.7b")
    assert (cfg.n_experts, cfg.n_experts_per_tok) == (8, 2)
    shapes = _shapes(cfg)
    routed, _ = weight_flops(shapes)
    assert routed > 0
    b, s = 2, 32
    got = flops.train_step(shapes, cfg, batch=b, seq=s,
                           experts_published=published)
    none = flops.train_step(shapes, cfg.scaled(n_experts_per_tok=0),
                            batch=b, seq=s, experts_published=published)
    assert got - none == pytest.approx(
        3 * b * s * routed * 2 / (published or 8), rel=1e-12)


def test_mamba_and_routed_terms_match_hand_formula():
    """jamba's smoke shape: 7 mamba layers, 1 attention layer, experts on
    every other layer (2 a token of 4 held, 16 published), d_model 64."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("jamba-1.5-large-398b")
    shapes = _shapes(cfg)
    b, s = 2, 64
    # SSD: expand 2 gives d_inner 128 in heads of P = 64, so 2 heads, N = 8
    mamba = 4 * 8 * 64 * 2
    spec = cfg.pattern[0]
    assert spec.kind == "mamba"
    assert flops.layer_module("mamba").sequence_flops(
        cfg, spec, b, s, True) == b * s * mamba
    routed, other = weight_flops(shapes)
    attn = 4 * b * 4 * 16 * s * (s + 1) / 2
    want = 3 * (b * s * (other + routed * 2 / 16 + 7 * mamba) + attn)
    got = flops.train_step(shapes, cfg, batch=b, seq=s, experts_published=16)
    assert got == pytest.approx(want, rel=1e-12)


def test_tied_head_counts_its_product_once():
    """A tied head has no leaf of its own: its product, 2·V·d a token, is
    added once, so tied and untied count alike; the gather counts
    nothing."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("yi-6b")
    tied = cfg.scaled(tie_embeddings=True)
    t_shapes = _shapes(tied)
    assert "head" not in t_shapes
    b, s = 2, 16
    got = flops.train_step(t_shapes, tied, batch=b, seq=s)
    assert got == flops.train_step(_shapes(cfg), cfg, batch=b, seq=s)
    assert got - flops.train_step(t_shapes, cfg, batch=b, seq=s) == \
        pytest.approx(3 * b * s * 2 * cfg.vocab_size * cfg.d_model)


def test_layer_kind_without_module_fails_naming_it():
    from repro.configs import get_smoke_config
    from repro.models.config import LayerSpec
    cfg = get_smoke_config("yi-6b")
    shapes = _shapes(cfg)
    with pytest.raises(ValueError, match="'hyena'"):
        flops.train_step(shapes, cfg.scaled(pattern=(LayerSpec("hyena"),)),
                         batch=1, seq=8)


@pytest.mark.parametrize("window,want", [(None, 8 * 9 / 2), (8, 8 * 9 / 2),
                                         (3, 3 * 4 / 2 + 5 * 3)])
def test_attention_term_counts_the_window(window, want):
    """A query sees at most ``window`` keys, itself included."""
    from repro.configs import get_smoke_config
    from repro.models.config import LayerSpec
    cfg = get_smoke_config("yi-6b")
    spec = LayerSpec("attn", sliding_window=window)
    got = flops.layer_module("attn").sequence_flops(cfg, spec, 1, 8, True)
    assert got == 4 * cfg.n_heads * cfg.resolved_head_dim * want



@pytest.mark.parametrize("windows", [(None,), (3,), (3, None)])
def test_flash_kernel_counts_the_visible_pairs(windows):
    """A flash call counts the pairs its layer's window leaves visible;
    where windows differ, the mean over the attention layers."""
    import types
    from repro.configs import get_smoke_config
    from repro.models.config import LayerSpec
    cfg = get_smoke_config("yi-6b")
    cfg = cfg.scaled(pattern=tuple(LayerSpec("attn", sliding_window=w)
                                   for w in windows))
    kernel = flops.kernel_modules()["flash_attention"]
    rc = types.SimpleNamespace(attention_impl="flash")
    got, nbytes = kernel.call(cfg, rc, {"batch": 1, "seq": 8})
    h, d = cfg.n_heads, cfg.resolved_head_dim
    seen = {None: 8 * 9 / 2, 3: 3 * 4 / 2 + 5 * 3}
    assert got == pytest.approx(
        4 * h * d * sum(seen[w] for w in windows) / len(windows))
    assert nbytes == flops.flash_attention(1, 8, h, cfg.n_kv_heads, d)[1]
    assert kernel.call(cfg, types.SimpleNamespace(attention_impl="xla"),
                       {"batch": 1, "seq": 8}) is None
