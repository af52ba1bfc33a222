"""The step program's named scopes and the compile counters.

The train step names its parts with ``jax.named_scope``; the names live
only in the compiled program's metadata (``op_name``), where a profile's
reduction reads them.  These tests compile the smoke configurations'
train steps on the CPU and check that every matrix product and custom
call is named, in the direction it runs, and that the scopes change
nothing else in the optimized program.  The compile counters of
``repro.compile_cache`` are checked against compiles made here.
"""

import contextlib
import re
import time
from collections import defaultdict

import jax
import jax.numpy as jnp
import pytest

from repro.compile_cache import watch_compiles
from repro.configs import get_smoke_config
from repro.models.config import MLP_DENSE, MLP_MOE
from repro.models.model import Model
from repro.runconfig import runconfig_from_knobs
from repro.train.train_loop import init_state, make_train_step

SCOPE = re.compile(r"(?:^|[/(])((?:model|train)\.[A-Za-z_]+)")
OP_NAME = re.compile(r'op_name="([^"]*)"')
HEAVY = (" dot(", " convolution(", " custom-call(")
# each architecture with the attention and expert paths its benchmark cell
# takes
CASES = {"yi-6b": {"attention_impl": "flash"}, "xlstm-1.3b": {},
         "lfm2-8b-a1b": {"attention_impl": "flash", "moe_impl": "grouped"}}
MLP_SCOPES = {MLP_DENSE: "model.mlp", MLP_MOE: "model.moe"}


def _scope(op_path):
    found = SCOPE.findall(op_path)
    if not found:
        return None
    return found[-1], "bwd" if "transpose(" in op_path else "fwd"


def _step_text(arch, knobs):
    cfg = get_smoke_config(arch)
    rc = runconfig_from_knobs(knobs)
    model = Model(cfg)
    state = jax.eval_shape(lambda: init_state(model, jax.random.key(0), rc))
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
             for k in ("tokens", "labels")}
    text = jax.jit(make_train_step(model, rc)).lower(state, batch) \
        .compile().as_text()
    return cfg, text


def _heavy_scopes(text):
    """(scope, direction) -> count, over the dots, convolutions and custom
    calls of a compiled module; unnamed ones under None."""
    out = defaultdict(int)
    for line in text.splitlines():
        if any(op in line for op in HEAVY):
            m = OP_NAME.search(line)
            out[_scope(m.group(1)) if m else None] += 1
    return out


def _all_scopes(text):
    return {_scope(p) for p in OP_NAME.findall(text)} - {None}


DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _instructions(text):
    """The optimized module's instructions, their metadata and the tables
    of source locations left out, and each name (``%transpose.3082``)
    replaced by its order of first appearance: the lowering numbers some
    instructions differently when scopes are on."""
    out, table, names = [], False, {}

    def rename(m):
        return names.setdefault(m.group(0), f"%{len(names)}")
    for line in text.splitlines():
        if line in DEBUG_TABLES:
            table = True
        elif table and not line.strip():
            table = False
        elif not table:
            line = re.sub(r", metadata=\{[^}]*\}", "", line)
            out.append(re.sub(r"%[\w.\-]+", rename, line))
    return out


@pytest.fixture(scope="module", params=sorted(CASES))
def compiled(request):
    arch = request.param
    cfg, text = _step_text(arch, CASES[arch])
    return arch, cfg, text


def test_every_product_is_named(compiled):
    _, cfg, text = compiled
    heavy = _heavy_scopes(text)
    assert heavy, "no dot, convolution or custom call in the step"
    assert None not in heavy, f"{heavy[None]} unnamed products"
    assert all(s.startswith("model.") for s, _ in heavy), dict(heavy)


def test_scopes_in_both_directions(compiled):
    _, cfg, text = compiled
    found = _all_scopes(text)
    used = {"model.embed", "model.head"}
    used |= {f"model.{s.kind}" for s in cfg.pattern}
    used |= {MLP_SCOPES[s.mlp] for s in cfg.pattern if s.mlp in MLP_SCOPES}
    for scope in sorted(used):
        assert (scope, "fwd") in found, scope
        assert (scope, "bwd") in found, scope
    assert ("train.update", "fwd") in found
    assert ("train.update", "bwd") not in found
    assert ("train.grad", "bwd") in found


def test_scopes_change_only_metadata(compiled, monkeypatch):
    arch, _, text = compiled
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    _, bare = _step_text(arch, CASES[arch])
    assert not _all_scopes(bare)
    ours, theirs = _instructions(text), _instructions(bare)
    assert len(ours) > 100 and not any("op_name" in x for x in ours)
    assert ours == theirs


def _flash_loss(q, k, v):
    from repro.kernels.flash_attention.ops import flash_attention
    with jax.named_scope("model.attn"):
        return flash_attention(q, k, v, block_q=32, block_k=32).sum()


def _mlstm_loss(q, k, v):
    from repro.kernels.mlstm_chunk.ops import mlstm_chunk
    gate = jnp.ones(q.shape[:3], q.dtype)
    with jax.named_scope("model.mlstm"):
        return mlstm_chunk(q, k, v, gate, -gate, chunk=16).sum()


@pytest.mark.parametrize("loss,scope,kv_heads,saves_forward", [
    (_flash_loss, "model.attn", 1, True),
    (_mlstm_loss, "model.mlstm", 2, False)])
def test_kernel_vjp_is_named_backward(loss, scope, kv_heads, saves_forward):
    """The kernels' custom VJPs (run in interpret mode here) keep the
    caller's scope, and their backward rules read as backward: every
    operation the gradient's program names lies under the scope.  The
    mLSTM VJP recomputes from its inputs, so all of its gradient is
    backward; the flash VJP saves its forward's output and log-sum-exp,
    so its gradient also runs the forward kernel, which reads as forward
    and lies inside the kernel's wrapper.  (The CPU compiler drops the
    metadata of the products it rewrites in the interpreted flash
    kernels: those name nothing.  For the chip, see
    tests/test_tpu_compile.py.)"""
    q = jnp.ones((1, 64, 2, 16))
    kv = jnp.ones((1, 64, kv_heads, 16))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv) \
        .compile().as_text()
    paths = [p for p in OP_NAME.findall(text) if p.startswith("jit(")]
    assert paths
    want = {(scope, "bwd")} | ({(scope, "fwd")} if saves_forward else set())
    assert {_scope(p) for p in paths} == want
    assert all("jit(flash_attention)" in p for p in paths
               if _scope(p) == (scope, "fwd"))
    assert _heavy_scopes(text).keys() - {None} <= want


def test_counters_count_a_backend_compile():
    log = watch_compiles()
    assert watch_compiles() is log
    t = time.time()
    jax.jit(lambda x: jnp.sin(x) * 3.0 + x)(jnp.ones(7)).block_until_ready()
    snap = log.snapshot(since=t)
    assert snap.programs >= 1
    assert {s.stage for s in snap.spans} == {"trace", "lower", "compile"}
    assert any(s.stage == "compile" and "<lambda>" in s.fun_name
               for s in snap.spans)
    assert 0.0 < snap.seconds <= time.time() - t
    assert all(t <= s.start <= s.end for s in snap.spans)
    assert log.snapshot(since=time.time() + 1.0).programs == 0


def test_counters_count_a_persistent_cache_hit(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    log = watch_compiles()
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_enable_compilation_cache")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()

        def fresh():
            return jax.jit(lambda x: jnp.cos(x) * 5.0 - x)
        x = jnp.ones(11)
        t = time.time()
        fresh()(x).block_until_ready()
        first = log.snapshot(since=t)
        assert first.cache_misses >= 1 and first.cache_hits == 0
        t = time.time()
        fresh()(x).block_until_ready()
        second = log.snapshot(since=t)
        assert second.cache_hits >= 1 and second.cache_misses == 0
        assert second.programs >= 1
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_launch_train_logs_the_counters(tmp_path):
    """The training entry point logs its compiles after the first step."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    repo = Path(__file__).resolve().parent.parent
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "PYTHONPATH": os.pathsep.join(
               p for p in (str(repo / "src"), os.environ.get("PYTHONPATH"))
               if p)}
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "yi-6b",
         "--smoke", "--steps", "2", "--seq-len", "32", "--global-batch", "2"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    m = re.search(r"^compiled (\d+) programs in ([\d.]+) s \(persistent "
                  r"cache: (\d+) hits, (\d+) misses\)$", out.stdout, re.M)
    assert m, out.stdout
    assert int(m.group(1)) >= 1 and float(m.group(2)) > 0
    assert out.stdout.count("compiled ") == 1
