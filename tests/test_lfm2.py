"""LFM2-8B-A1B at smoke size on the CPU: the system against the plain
float32 reference the chip benchmark checks it with
(``benchmarks/chip/configs/lfm2-8b-a1b.reference.py``), the expert layer's
shares against the uncut layer, the grouped-matmul kernel (interpret mode)
against its jnp oracle, and the gated short convolution against a direct
formula.

Everything is float32 here, parameters and activations, so the tolerances
are round-off of float32 sums in another order (≈1e-5 relative): a
mechanism left out or computed differently fails them by orders of
magnitude."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels.grouped_matmul.ops import grouped_matmul
from repro.kernels.grouped_matmul.ref import grouped_matmul_ref
from repro.models import attention, conv, moe, transformer
from repro.models.common import norm_apply
from repro.models.model import Model
from repro.models.rotary import apply_rope
from repro.runconfig import RunConfig

REF_PATH = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip" /
            "configs" / "lfm2-8b-a1b.reference.py")
F32 = {"param_dtype": "float32", "activation_dtype": "float32"}


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("lfm2_reference", REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _model_dict(cfg):
    return {"n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.resolved_head_dim, "norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta,
            "pattern": [s.kind for s in cfg.pattern],
            "mlp": [s.mlp for s in cfg.pattern],
            "n_experts": cfg.n_experts, "expert_offset": cfg.expert_offset,
            "n_experts_per_tok": cfg.n_experts_per_tok}


def _params(cfg, seed=0):
    """float32 weights from the model's init, with a nonzero expert bias so
    that the selection differs from top-k of the scores alone."""
    params = Model(cfg).init(jax.random.key(seed), jnp.float32)
    for i, spec in enumerate(cfg.pattern):
        if spec.mlp == "moe":
            r = params["layers"][i]["moe"]["router"]
            r["bias"] = 0.3 * jax.random.normal(jax.random.key(100 + i),
                                                r["bias"].shape)
    return params


def _tokens(cfg, b=2, s=32, seed=1):
    k1, k2 = jax.random.split(jax.random.key(seed))
    return (jax.random.randint(k1, (b, s), 0, cfg.vocab_size),
            jax.random.randint(k2, (b, s), 0, cfg.vocab_size))


def _rc(impl, cfg):
    # a capacity of every token: the dropping layer drops nothing here
    return RunConfig(moe_impl=impl, moe_capacity_factor=cfg.router_width
                     / cfg.n_experts_per_tok, **F32)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


@pytest.mark.parametrize("impl", ["grouped", "dense", "dropping"])
def test_matches_reference(ref, impl):
    """Logits, loss and the gradient of every leaf, against the float32
    reference, for each ``moe_impl``."""
    cfg = get_smoke_config("lfm2-8b-a1b")
    rc = _rc(impl, cfg)
    params = _params(cfg)
    tokens, labels = _tokens(cfg)
    model, mm = _model_dict(cfg), ref.R.Matmul("f32")
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lambda p: transformer.forward(p, tokens, cfg, rc)
                            )(params)
        want = ref.logits(params, tokens, model, mm)
        assert _rel(logits, want) < 1e-5

        def sys_loss(p):
            return transformer.loss_fn(p, {"tokens": tokens,
                                           "labels": labels}, cfg, rc)[0]
        loss, grads = jax.jit(jax.value_and_grad(sys_loss))(params)
        rloss, rgrads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, tokens, labels, model, mm)))(params)
    assert abs(float(loss) - float(rloss)) < 1e-5 * abs(float(rloss))
    flat = jax.tree_util.tree_flatten_with_path(rgrads)[0]
    got = jax.tree.leaves(grads)
    assert len(got) == len(flat)
    for (path, want_g), g in zip(flat, got):
        name = jax.tree_util.keystr(path)
        if name.endswith("['bias']"):       # a buffer: no gradient
            assert not np.any(np.asarray(g)) and not np.any(
                np.asarray(want_g)), name
            continue
        assert _rel(g, want_g) < 1e-4, name


@pytest.mark.parametrize("impl", ["grouped", "dense", "dropping"])
def test_expert_shares_sum_to_the_uncut_layer(ref, impl):
    """Four shares of the experts (offsets 0, E/4, E/2, 3E/4), each routing
    over all E and computing its own experts' part, add up to the uncut
    reference layer."""
    cfg = get_smoke_config("lfm2-8b-a1b")
    E = cfg.router_width
    p = _params(cfg)["layers"][1]["moe"]
    p = jax.tree.map(lambda t: t[0], p)
    x = jax.random.normal(jax.random.key(3), (2, 16, cfg.d_model))
    model = _model_dict(cfg)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(p, x, model, ref.R.Matmul("f32"))
        total = jnp.zeros_like(x)
        for off in range(0, E, E // 4):
            share = cfg.scaled(n_experts=E // 4, expert_offset=off)
            sp = {"router": p["router"],
                  "experts": jax.tree.map(lambda t: t[off: off + E // 4],
                                          p["experts"])}
            y, stats = moe.apply(sp, x, share, _rc(impl, share))
            total = total + y
            assert 0 <= float(stats["moe_held_rows"]) <= 2 * 16 * 4
    assert _rel(total, want) < 1e-5


def test_held_rows_count_the_routed_pairs():
    cfg = get_smoke_config("lfm2-8b-a1b").scaled(n_experts=2,
                                                expert_offset=4)
    p = jax.tree.map(lambda t: t[0], _params(cfg)["layers"][1]["moe"])
    x = jax.random.normal(jax.random.key(4), (1, 64, cfg.d_model))
    _, stats = moe.apply(p, x, cfg, _rc("grouped", cfg))
    topi, _, _ = moe._routing(p, x.reshape(64, -1), cfg)
    sizes = np.bincount(np.asarray(topi).ravel(), minlength=8)[4:6]
    assert float(stats["moe_held_rows"]) == sizes.sum()
    assert float(stats["moe_max_group"]) == sizes.max()


GMM_CASES = {
    # (group sizes of the held groups, then rows of none)
    "uneven": ([40, 3, 100, 17], 96),
    "empty_group": ([64, 0, 0, 80], 112),
    "none_held": ([0, 0, 0, 0], 256),
    "all_held": ([1, 250, 2, 3], 0),
}


@pytest.mark.parametrize("case", sorted(GMM_CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_matches_ref(case, dtype):
    held, rest = GMM_CASES[case]
    sizes = jnp.asarray(held + [rest], jnp.int32)
    m, k, n, G = int(sizes.sum()), 64, 96, len(held)
    ka, kb, kc = jax.random.split(jax.random.key(len(case)), 3)
    a = jax.random.normal(ka, (m, k)).astype(dtype)
    b = jax.random.normal(kb, (G, k, n)).astype(dtype)
    probe = jax.random.normal(kc, (m, n))

    def f(fn):
        return lambda a, b: jnp.sum(fn(a, b, sizes).astype(jnp.float32)
                                    * probe)
    out, want = grouped_matmul(a, b, sizes), grouped_matmul_ref(a, b, sizes)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert _rel(out.astype(jnp.float32), want) < tol
    assert not np.any(np.asarray(out, np.float32)[m - rest:])
    got = jax.grad(f(grouped_matmul), argnums=(0, 1))(a, b)
    exp = jax.grad(f(grouped_matmul_ref), argnums=(0, 1))(a, b)
    for g, e in zip(got, exp):
        if np.any(np.asarray(e)):
            assert _rel(g.astype(jnp.float32), e) < tol
        else:
            assert not np.any(np.asarray(g, np.float32))


def test_conv_matches_direct_formula():
    cfg = get_smoke_config("lfm2-8b-a1b")
    p = conv.init(jax.random.key(5), cfg, jnp.float32)
    h = jax.random.normal(jax.random.key(6), (2, 12, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = conv.apply(p, h, cfg, RunConfig(**F32))
        proj = np.asarray(h @ p["in_proj"]["w"], np.float64)
    d, K = cfg.d_model, np.asarray(p["conv_w"], np.float64)
    b, c, u = proj[..., :d], proj[..., d: 2 * d], proj[..., 2 * d:]
    v = b * u
    w = np.zeros_like(v)
    for t in range(v.shape[1]):
        for i in range(3):               # w_t = Σ K_i ⊙ v_{t-2+i}
            if t - 2 + i >= 0:
                w[:, t] += K[i] * v[:, t - 2 + i]
    want = (c * w) @ np.asarray(p["out_proj"]["w"], np.float64)
    assert _rel(got, want) < 1e-5


@pytest.mark.parametrize("qk_norm", [False, True])
def test_attention_qk_norm(qk_norm):
    """q and k are RMS-normed over each head where ``qk_norm`` asks, and
    attention is what it was without it otherwise."""
    cfg = get_smoke_config("yi-6b").scaled(qk_norm=qk_norm)
    p = attention.init(jax.random.key(7), cfg, jnp.float32)
    assert ("q_norm" in p) == qk_norm
    if qk_norm:
        p["q_norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(
            jax.random.key(8), p["q_norm"]["scale"].shape)
    x = jax.random.normal(jax.random.key(9), (1, 16, cfg.d_model))
    pos = jnp.arange(16)[None]
    hd = cfg.resolved_head_dim
    rc = RunConfig(**F32)
    q = (x @ p["q"]["w"]).reshape(1, 16, cfg.n_heads, hd)
    k = (x @ p["k"]["w"]).reshape(1, 16, cfg.n_kv_heads, hd)
    v = (x @ p["v"]["w"]).reshape(1, 16, cfg.n_kv_heads, hd)
    if qk_norm:
        q = norm_apply(p["q_norm"], q, eps=cfg.norm_eps)
        k = norm_apply(p["k_norm"], k, eps=cfg.norm_eps)
    q, k = apply_rope(q, pos, cfg.rope_theta), apply_rope(k, pos,
                                                          cfg.rope_theta)
    o = attention.reference_attention(q, k, v, causal=True, window=None,
                                      softcap=None)
    want = o.reshape(1, 16, -1) @ p["o"]["w"]
    got = attention.apply(p, x, pos, cfg, rc)
    assert _rel(got, want) < 1e-6


def test_cost_model_prices_each_moe_impl():
    """``moe_impl`` is live in the cost model: grouped at the routed work,
    dropping at its capacity factor, dense at router_width / k times it."""
    from repro.configs import get_config
    from repro.core import costmodel
    from repro.models.config import SHAPES_BY_NAME
    cfg = get_config("lfm2-8b-a1b")
    cell = SHAPES_BY_NAME["train_4k"]
    flops = {impl: costmodel.estimate(
        cfg, cell, costmodel.SINGLE_POD,
        {"moe_impl": impl, "moe_capacity_factor": 1.5}).flops
        for impl in ("grouped", "dropping", "dense")}
    routed = 3.0 * costmodel.routed_expert_flops(cfg, 256 * 4096)
    assert flops["dropping"] - flops["grouped"] == pytest.approx(
        0.5 * routed, rel=1e-9)
    assert flops["dense"] - flops["grouped"] == pytest.approx(
        (32 / 4 - 1) * routed, rel=1e-9)
