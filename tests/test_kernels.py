"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import reference_attention
from repro.kernels.gp_gram.ops import matern52_cross, matern52_gram
from repro.kernels.gp_gram.ref import matern52_cross_ref, matern52_gram_ref
from repro.kernels.mlstm_chunk.ops import mlstm_chunk
from repro.kernels.mlstm_chunk.ref import mlstm_sequential


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


FLASH_CASES = [
    # (B, Sq, Sk, H, Kh, D, causal, window, softcap, bq, bk)
    (2, 256, 256, 4, 2, 64, True, None, None, 128, 128),
    (1, 128, 384, 8, 8, 128, True, None, 30.0, 128, 128),
    (2, 200, 200, 4, 1, 64, True, 64, None, 128, 128),
    (1, 512, 512, 2, 2, 128, False, None, None, 256, 128),
    (1, 96, 96, 6, 6, 64, True, None, None, 128, 128),     # whisper-ish
    (2, 64, 64, 4, 4, 32, True, 16, 10.0, 64, 64),
]


@pytest.mark.parametrize("case", FLASH_CASES, ids=lambda c: f"B{c[0]}S{c[1]}x{c[2]}H{c[3]}-{c[4]}D{c[5]}")
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_reference(case, dtype):
    B, Sq, Sk, H, Kh, D, causal, window, softcap, bq, bk = case
    k1, k2, k3 = jax.random.split(jax.random.key(Sq + H), 3)
    q = _rand(k1, (B, Sq, H, D), dtype)
    k = _rand(k2, (B, Sk, Kh, D), dtype)
    v = _rand(k3, (B, Sk, Kh, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, block_q=bq, block_k=bk)
    ref = reference_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_flash_block_size_invariance():
    """Output must not depend on the (tuned) block sizes."""
    k1, k2, k3 = jax.random.split(jax.random.key(7), 3)
    q = _rand(k1, (1, 256, 4, 64), jnp.float32)
    k = _rand(k2, (1, 256, 2, 64), jnp.float32)
    v = _rand(k3, (1, 256, 2, 64), jnp.float32)
    outs = [flash_attention(q, k, v, block_q=bq, block_k=bk)
            for bq, bk in [(64, 64), (128, 256), (256, 128)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-5)


def _flash_grads(attn, q, k, v, w, **kw):
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v, **kw).astype(jnp.float32)
                       * w.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("case", FLASH_CASES,
                         ids=lambda c: f"B{c[0]}S{c[1]}x{c[2]}H{c[3]}-{c[4]}"
                                       f"W{c[7]}cap{c[8]}")
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grad_matches_reference(case, dtype):
    """The kernel's custom VJP (the Pallas dK/dV and dQ kernels) equals
    the gradient of the materializing f32 oracle at the same inputs.

    f32: within 1e-4.  bf16: within 1e-2 of each gradient's largest
    reference magnitude — the gradients are returned in bf16 (2^-9
    relative rounding) and P and dS enter the MXU as bf16; the worst
    case reads 0.5 % in interpret mode."""
    B, Sq, Sk, H, Kh, D, causal, window, softcap, bq, bk = case
    k1, k2, k3, k4 = jax.random.split(jax.random.key(Sq + 3 * H), 4)
    q = _rand(k1, (B, Sq, H, D), dtype)
    k = _rand(k2, (B, Sk, Kh, D), dtype)
    v = _rand(k3, (B, Sk, Kh, D), dtype)
    w = _rand(k4, (B, Sq, H, D), dtype)
    kw = dict(causal=causal, window=window, softcap=softcap)
    got = _flash_grads(flash_attention, q, k, v, w, block_q=bq, block_k=bk,
                       **kw)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want = _flash_grads(reference_attention, *f32, w, **kw)
    for g, r in zip(got, want):
        assert g.dtype == dtype
        r = np.asarray(r)
        tol = 1e-4 if dtype == jnp.float32 else 1e-2 * np.abs(r).max()
        np.testing.assert_allclose(np.asarray(g, np.float32), r, atol=tol)


_GRAD_TILES = [(64, 64), (128, 256), (256, 128)]


@pytest.mark.parametrize("tiles", _GRAD_TILES, ids=lambda t: f"{t[0]}x{t[1]}")
def test_flash_grad_block_size_invariance(tiles):
    """The gradient must not depend on the (tuned) block sizes: each
    tiling, with its skipped tiles and clamped block indices, matches the
    one-block gradient."""
    k1, k2, k3, k4 = jax.random.split(jax.random.key(7), 4)
    q = _rand(k1, (1, 256, 4, 64), jnp.float32)
    k = _rand(k2, (1, 256, 2, 64), jnp.float32)
    v = _rand(k3, (1, 256, 2, 64), jnp.float32)
    w = _rand(k4, (1, 256, 4, 64), jnp.float32)
    bq, bk = tiles
    got = _flash_grads(flash_attention, q, k, v, w, block_q=bq, block_k=bk)
    one = _flash_grads(flash_attention, q, k, v, w, block_q=256,
                       block_k=256)
    for g, r in zip(got, one):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-5)


@pytest.mark.parametrize("tiles", [(64, 64), (64, 128), (128, 64)],
                         ids=lambda t: f"{t[0]}x{t[1]}")
def test_flash_grad_window_skips_tiles(tiles):
    """A window of 64 over 512 positions leaves most tiles wholly outside
    it, so the window sides of the backward's clamps (the first KV block
    a query block sees, the last query block a KV block is seen by)
    redirect skipped steps; the visible ones must still read their own
    blocks.  f32 against the oracle within 1e-4."""
    k1, k2, k3, k4 = jax.random.split(jax.random.key(11), 4)
    q = _rand(k1, (1, 512, 4, 32), jnp.float32)
    k = _rand(k2, (1, 512, 2, 32), jnp.float32)
    v = _rand(k3, (1, 512, 2, 32), jnp.float32)
    w = _rand(k4, (1, 512, 4, 32), jnp.float32)
    bq, bk = tiles
    got = _flash_grads(flash_attention, q, k, v, w, window=64, block_q=bq,
                       block_k=bk)
    want = _flash_grads(reference_attention, q, k, v, w, window=64)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-4)


MLSTM_CASES = [
    (2, 128, 2, 32, 32), (1, 256, 4, 64, 64), (2, 64, 1, 16, 16),
    (1, 512, 2, 32, 128), (1, 128, 2, 32, 128),
]


@pytest.mark.parametrize("case", MLSTM_CASES,
                         ids=lambda c: f"B{c[0]}S{c[1]}H{c[2]}P{c[3]}C{c[4]}")
def test_mlstm_chunk_matches_sequential(case):
    B, S, H, P, chunk = case
    ks = jax.random.split(jax.random.key(S * H + P), 5)
    q = _rand(ks[0], (B, S, H, P), jnp.float32) * 0.5
    k = _rand(ks[1], (B, S, H, P), jnp.float32) * 0.5 / (P ** 0.5)
    v = _rand(ks[2], (B, S, H, P), jnp.float32) * 0.5
    logi = jax.random.normal(ks[3], (B, S, H), jnp.float32)
    logf = -jax.nn.softplus(-jax.random.normal(ks[4], (B, S, H)) * 2.0)
    out = mlstm_chunk(q, k, v, logi, logf, chunk=chunk)
    ref = mlstm_sequential(q, k, v, logi, logf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)


def test_mlstm_chunk_grad_matches_sequential():
    """The kernel's custom VJP (the jnp chunked form's) equals the
    gradient of the sequential oracle, gates included."""
    B, S, H, P, chunk = 1, 128, 2, 32, 32
    ks = jax.random.split(jax.random.key(5), 6)
    q = _rand(ks[0], (B, S, H, P), jnp.float32) * 0.5
    k = _rand(ks[1], (B, S, H, P), jnp.float32) * 0.5 / (P ** 0.5)
    v = _rand(ks[2], (B, S, H, P), jnp.float32) * 0.5
    logi = jax.random.normal(ks[3], (B, S, H), jnp.float32)
    logf = -jax.nn.softplus(-jax.random.normal(ks[4], (B, S, H)) * 2.0)
    w = _rand(ks[5], (B, S, H, P), jnp.float32)
    args = (q, k, v, logi, logf)
    got = jax.grad(lambda *a: jnp.sum(mlstm_chunk(*a, chunk=chunk) * w),
                   argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(mlstm_sequential(*a) * w),
                    argnums=range(5))(*args)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-4)


def test_mlstm_chunk_size_invariance():
    B, S, H, P = 1, 256, 2, 32
    ks = jax.random.split(jax.random.key(11), 5)
    q = _rand(ks[0], (B, S, H, P), jnp.float32)
    k = _rand(ks[1], (B, S, H, P), jnp.float32) / (P ** 0.5)
    v = _rand(ks[2], (B, S, H, P), jnp.float32)
    logi = jax.random.normal(ks[3], (B, S, H))
    logf = -jax.nn.softplus(-jax.random.normal(ks[4], (B, S, H)))
    outs = [mlstm_chunk(q, k, v, logi, logf, chunk=c) for c in (32, 64, 256)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=2e-4)   # f32 reassociation across chunk sizes


def test_mlstm_kernel_matches_model_path():
    """Kernel numerics == the model's jnp chunked scan (models/xlstm.py)."""
    from repro.configs import get_smoke_config
    from repro.models import xlstm
    from repro.runconfig import RunConfig
    cfg = get_smoke_config("xlstm-1.3b")
    rc = RunConfig(mlstm_chunk=16)
    p = xlstm.mlstm_init(jax.random.key(0), cfg, jnp.float32)
    u = jax.random.normal(jax.random.key(1), (2, 64, cfg.d_model), jnp.float32)
    ref_out = xlstm.mlstm_apply(p, u, cfg, rc)          # jnp chunked path
    q, k, v, logi, logf, z = xlstm._mlstm_qkvg(p, u, cfg)
    h = mlstm_chunk(q, k, v, logi, logf, chunk=16)
    di, nh, P = xlstm.mlstm_dims(cfg)
    from repro.models.common import dense_apply, norm_apply
    hh = h.reshape(2, 64, di)
    hh = norm_apply(p["out_norm"],
                    hh.astype(u.dtype)
                    * jax.nn.silu(z.astype(jnp.float32)).astype(u.dtype),
                    kind="rmsnorm", eps=cfg.norm_eps)
    out = dense_apply(p["down"], hh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=5e-4)


GRAM_CASES = [(40, 17, 5), (130, 200, 16), (8, 8, 2), (300, 1, 24),
              (128, 128, 8)]


@pytest.mark.parametrize("case", GRAM_CASES,
                         ids=lambda c: f"n{c[0]}m{c[1]}d{c[2]}")
def test_gp_gram_matches_reference(case):
    n, m, d = case
    ka, kb, kl = jax.random.split(jax.random.key(n + m), 3)
    xa = jax.random.uniform(ka, (n, d))
    xb = jax.random.uniform(kb, (m, d))
    ls = jax.random.uniform(kl, (d,), minval=0.1, maxval=1.0)
    np.testing.assert_allclose(
        np.asarray(matern52_gram(xa, ls, 1.7)),
        np.asarray(matern52_gram_ref(xa, ls, 1.7)), atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(matern52_cross(xa, xb, ls, 0.9)),
        np.asarray(matern52_cross_ref(xa, xb, ls, 0.9)), atol=2e-4)


def test_gp_gram_psd():
    """Property: Gram + jitter is positive definite (Cholesky succeeds)."""
    x = jax.random.uniform(jax.random.key(5), (64, 6))
    g = matern52_gram(x, jnp.full((6,), 0.3), 1.0)
    chol = np.linalg.cholesky(np.asarray(g) + 1e-5 * np.eye(64))
    assert np.all(np.isfinite(chol))


# ---------------------------------------------------------------------------
# autotune knobs: rectangular tiles, knob spaces, the dogfood evaluator
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block_n,block_m", [(64, 256), (256, 64), (32, 32)])
def test_gp_gram_rectangular_tiles_match_reference(block_n, block_m):
    """Tiling is a pure scheduling knob: non-square tiles at shapes off
    every block ladder (n=136, m=77) reproduce the reference bit-for-bit
    within f32 tolerance."""
    ka, kb, kl = jax.random.split(jax.random.key(9), 3)
    xa = jax.random.uniform(ka, (136, 9))
    xb = jax.random.uniform(kb, (77, 9))
    ls = jax.random.uniform(kl, (9,), minval=0.1, maxval=1.0)
    np.testing.assert_allclose(
        np.asarray(matern52_gram(xa, ls, 1.3, block=block_n,
                                 block_m=block_m)),
        np.asarray(matern52_gram_ref(xa, ls, 1.3)), atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(matern52_cross(xa, xb, ls, 0.8, block=block_n,
                                  block_m=block_m)),
        np.asarray(matern52_cross_ref(xa, xb, ls, 0.8)), atol=2e-4)


class TestKernelSpaces:
    def test_tunable_registry(self):
        from repro.kernels.autotune import kernel_space, tunable_kernels
        assert tunable_kernels() == ("flash_attention", "gp_gram",
                                     "mlstm_chunk")
        for k in tunable_kernels():
            sp = kernel_space(k)
            dflt = sp.project(sp.default_config())
            assert sp.validate(dflt) == []
        with pytest.raises(KeyError):
            kernel_space("nope")

    def test_pow2_snap_and_product_constraint(self):
        """Projection first snaps every knob to its pow2 ladder, then the
        ProductLeq halves the larger factor until the tile budget holds —
        and returns the ladder's own int objects, not floats."""
        from repro.kernels.autotune import kernel_space
        sp = kernel_space("gp_gram")
        p = sp.project({"block_n": 500, "block_m": 500,
                        "num_warps": 3, "pipeline": 2})
        assert p["block_n"] * p["block_m"] <= 256 * 256
        assert all(isinstance(p[k], int) and not isinstance(p[k], bool)
                   for k in ("block_n", "block_m", "num_warps"))
        assert p["num_warps"] in (2, 4)          # nearest pow2 of 3
        assert sp.validate(p) == []

    def test_pow2_knob_helper(self):
        from repro.core.space import pow2_knob
        k = pow2_knob("b", 128, 16, 512)
        assert k.choices == (16, 32, 64, 128, 256, 512)
        assert k.clip(200) == 256
        assert k.clip(24) in (16, 32)            # nearest, tie -> smaller
        with pytest.raises(AssertionError):
            pow2_knob("b", 100, 16, 512)         # default off the ladder


class TestKernelEvaluator:
    def test_times_valid_config(self):
        from repro.kernels.autotune import KernelEvaluator
        ev = KernelEvaluator("gp_gram", shape={"n": 24, "d": 3},
                            repeats=1, warmup=1)
        ms = ev(ev.spec.default_config())
        assert ms > 0.0

    def test_invalid_config_fails_through_service(self):
        """A config off the space raises in the evaluator; the service
        layer converts it into a *failed* EvalResult — the contract that
        lets the async controller price it as infeasible instead of
        dying."""
        from repro.core.service import EvalRequest, as_service
        from repro.kernels.autotune import KernelEvaluator
        ev = KernelEvaluator("gp_gram", shape={"n": 24, "d": 3},
                            repeats=1, warmup=1)
        bad = dict(ev.spec.default_config())
        bad["block_n"] = 48                      # off the pow2 ladder
        with as_service(ev) as svc:
            ticket = svc.submit([EvalRequest(config=bad)])[0]
            res = svc.gather([ticket])[0]
        assert not res.ok
        assert "invalid config" in res.error

    def test_screen_fidelity_reduces_repeats(self):
        from repro.core.service import EvalRequest
        from repro.kernels.autotune import KernelEvaluator
        calls = []
        ev = KernelEvaluator("gp_gram", shape={"n": 24, "d": 3},
                            repeats=4, warmup=1, screen_repeats=1)
        build = ev._build

        def counting_build(cfg):
            run = build(cfg)
            def wrapped():
                calls.append(1)
                return run()
            return wrapped

        ev._build = counting_build
        cfg = ev.spec.default_config()
        ev(cfg, request=EvalRequest(config=cfg, fidelity="screen"))
        screen_calls = len(calls)
        calls.clear()
        ev(cfg, request=EvalRequest(config=cfg))
        assert screen_calls < len(calls)
