"""The main path's kernels compile for a TPU v5e chip that is described,
not attached.

Each test lowers a kernel (or its VJP) at the width the system runs it
and compiles it with the TPU compiler for one chip of a ``v5e:2x2``
topology; the compiled program must hold the Mosaic kernel
(``tpu_custom_call``), so nothing fell back to interpret mode.  Nothing
runs: this guards what the chip's compiler refuses (block tiling, scoped
VMEM), not results or times.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and
pytest-xdist workers import every test file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def spec(topo, no_compile_cache):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_gp_gram_compiles(spec):
    from repro.kernels.gp_gram.ops import matern52_cross, matern52_gram
    hlo = _compiled_text(
        lambda x, ls: matern52_gram(x, ls, 1.0, interpret=False),
        spec((512, 12)), spec((12,)))
    assert "tpu_custom_call" in hlo
    hlo = _compiled_text(
        lambda a, b, ls: matern52_cross(a, b, ls, 1.0, interpret=False),
        spec((4096, 12)), spec((256, 12)), spec((12,)))
    assert "tpu_custom_call" in hlo


def test_gp_gram_autotune_space_compiles(spec):
    """Every tile the kernel autotuner may probe compiles at its bench
    shape: a refused tile would be a failed probe on the chip."""
    import itertools
    from repro.kernels.autotune import kernel_space
    from repro.kernels.gp_gram.ops import matern52_gram
    sp = kernel_space("gp_gram")
    base = sp.project(sp.default_config())
    tiles = [(bn, bm) for bn, bm in itertools.product(
        sp.knob("block_n").choices, sp.knob("block_m").choices)
        if not sp.validate({**base, "block_n": bn, "block_m": bm})]
    assert tiles
    for bn, bm in tiles:
        hlo = _compiled_text(
            lambda x, ls: matern52_gram(x, ls, 1.0, block=bn, block_m=bm,
                                        interpret=False),
            spec((136, 8)), spec((8,)))
        assert "tpu_custom_call" in hlo, (bn, bm)


YI6B_ATTENTION = ((1, 4096, 32, 128), (1, 4096, 4, 128))   # q, k/v


def test_flash_forward_compiles_at_yi6b_widths(spec):
    from repro.kernels.flash_attention.ops import flash_attention
    qs, ks = YI6B_ATTENTION
    hlo = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        spec(qs, jnp.bfloat16), spec(ks, jnp.bfloat16),
        spec(ks, jnp.bfloat16))
    assert "tpu_custom_call" in hlo


def test_flash_backward_compiles_at_yi6b_widths(spec):
    from repro.kernels.flash_attention.ops import flash_attention
    qs, ks = YI6B_ATTENTION

    def loss(q, k, v):
        return flash_attention(q, k, v, interpret=False) \
            .astype(jnp.float32).sum()
    hlo = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                         spec(qs, jnp.bfloat16), spec(ks, jnp.bfloat16),
                         spec(ks, jnp.bfloat16))
    # the forward (with its log-sum-exp) and the dK/dV and dQ kernels;
    # no loop of a jnp backward is left
    assert hlo.count('custom_call_target="tpu_custom_call"') >= 3
    assert not re.search(r"\bwhile\(", hlo)


def _mlstm_args(spec):
    # xlstm-1.3b: d_model 2048, mlstm_expand 2 -> di 4096 over 4 heads
    B, S, H, P = 1, 4096, 4, 1024
    return (spec((B, S, H, P), jnp.bfloat16),) * 3 + (spec((B, S, H)),) * 2


@pytest.mark.parametrize("chunk", [256, 512])
def test_mlstm_chunk_compiles_at_xlstm_widths(spec, chunk):
    from repro.kernels.mlstm_chunk.ops import mlstm_chunk
    hlo = _compiled_text(
        lambda *a: mlstm_chunk(*a, chunk=chunk, interpret=False),
        *_mlstm_args(spec))
    assert "tpu_custom_call" in hlo


def test_mlstm_chunk_backward_compiles_at_xlstm_widths(spec):
    from repro.kernels.mlstm_chunk.ops import mlstm_chunk

    def loss(*a):
        return mlstm_chunk(*a, chunk=256, interpret=False) \
            .astype(jnp.float32).sum()
    hlo = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)),
                         *_mlstm_args(spec))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", ["flash_attention", "mlstm_chunk"])
def test_kernel_backward_keeps_its_scope(spec, kernel):
    """Under a caller's ``jax.named_scope``, every operation of the
    kernel's gradient compiled for the chip names that scope, as backward
    (its path holds ``transpose(``): a profile attributes the custom VJP
    to the layer that called the kernel."""
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.mlstm_chunk.ops import mlstm_chunk
    q, kv = spec((1, 256, 2, 128)), spec((1, 256, 1, 128))
    args, scope = (q, kv, kv), "model.attn"
    if kernel == "mlstm_chunk":
        args, scope = (q, q, q, spec((1, 256, 2)), spec((1, 256, 2))), \
            "model.mlstm"

    def loss(*a):
        with jax.named_scope(scope):
            if kernel == "flash_attention":
                out = flash_attention(*a, interpret=False, block_q=128,
                                      block_k=128)
            else:
                out = mlstm_chunk(*a, chunk=128, interpret=False)
        return out.sum()
    hlo = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *args)
    paths = [p for p in re.findall(r'op_name="([^"]*)"', hlo)
             if p.startswith("jit(")]
    assert paths
    # The flash VJP saves its forward's output and log-sum-exp, so its
    # gradient also runs the forward kernel and the layout changes before
    # it: forward, under the scope.  The mLSTM VJP recomputes from its
    # inputs.  Every other operation, each backward kernel included, is
    # backward.
    bwd = f"transpose(jvp({scope}))"
    fwd = {p for p in paths if bwd not in p}
    if kernel == "flash_attention":
        assert fwd and all(p.startswith(f"jit(loss)/jvp({scope})/"
                                        "jit(flash_attention)/")
                           for p in fwd), sorted(fwd)[:5]
    else:
        assert not fwd, sorted(fwd)[:5]
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in hlo.splitlines() if "tpu_custom_call" in line]
    if kernel == "flash_attention":      # the forward; dK/dV and dQ
        assert sum(bwd not in p for p in calls) == 1
        assert sum(bwd in p for p in calls) == 2
    else:                                # a jnp backward, no kernel
        assert not calls


# lfm2-8b-a1b's cell: batch 4 x seq 4096, top-4, so 65,536 sorted rows;
# d_model 2048, expert width 1792, 8 experts held
LFM2_EXPERTS = ((4 * 4096 * 4, 2048), (8, 2048, 1792), (8, 1792, 2048))


def test_grouped_matmul_compiles_at_lfm2_widths(spec):
    """Gate and down products and their gradients: the forward, the rows'
    and the weights' kernels, three Mosaic calls a product."""
    from repro.kernels.grouped_matmul.ops import grouped_matmul
    (m, d), up, down = LFM2_EXPERTS

    def loss(x, w1, w2, sizes):
        h = grouped_matmul(x, w1, sizes, interpret=False)
        return grouped_matmul(h, w2, sizes, interpret=False) \
            .astype(jnp.float32).sum()
    hlo = _compiled_text(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                         spec((m, d), jnp.bfloat16), spec(up, jnp.bfloat16),
                         spec(down, jnp.bfloat16), spec((9,), jnp.int32))
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 6
    assert all("grouped_matmul" in line for line in calls)


def test_lfm2_step_names_its_kernels(spec, monkeypatch):
    """lfm2-8b-a1b's smoke train step compiled for the chip: every product
    and kernel lies under a ``model.*`` scope, and each grouped-matmul
    call under ``model.moe``'s ``experts``, where the benchmark's kernel
    file (``flops/kernels/megablox_gmm.py``) tags it."""
    import sys
    from pathlib import Path
    from repro.configs import get_smoke_config
    from repro.models.model import Model
    from repro.runconfig import runconfig_from_knobs
    from repro.train.train_loop import init_state, make_train_step
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] /
                           "benchmarks" / "chip"))
    import trace as tracemod
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_smoke_config("lfm2-8b-a1b")
    rc = runconfig_from_knobs({"attention_impl": "flash",
                               "flash_block_q": 128, "flash_block_k": 128,
                               "moe_impl": "grouped"})
    model = Model(cfg)
    state = jax.eval_shape(lambda: init_state(model, jax.random.key(0), rc))
    state = jax.tree.map(lambda s: spec(s.shape, s.dtype), state)
    batch = {k: spec((2, 256), jnp.int32) for k in ("tokens", "labels")}
    hlo = jax.jit(make_train_step(model, rc)).lower(state, batch) \
        .compile().as_text()
    heavy = [line for line in hlo.splitlines()
             if " dot(" in line or "tpu_custom_call" in line]
    names = [re.search(r'op_name="([^"]*)"', line) for line in heavy]
    assert heavy and all(n and "model." in n.group(1) for n in names)
    tags = tracemod.kernel_ops(hlo)
    gmm = {k for k, v in tags.items() if v == "megablox_gmm"}
    # three products a layer (gate, up, down), each a forward and two
    # backward calls, over the four MoE layers
    assert len(gmm) == 3 * 3 * 4
    for line in hlo.splitlines():
        m = tracemod._OP_NAME.match(line)
        if m and m.group(1) in gmm:
            assert "model.moe/experts/" in line, line[:300]
    assert set(tags.values()) == {"megablox_gmm", "flash_attention"}
