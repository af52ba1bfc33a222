"""Device time by named scope and compile spans on the trace's clock, on
small traces recorded on the CPU; the profile of a cell at a smoke size."""

import time

import jax
import jax.numpy as jnp
import pytest

import scopes
import trace as tracemod
from conftest import TinyCell

HLO = """HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %tanh.1 = f32[8]{0} tanh(%param_0), metadata={op_name="jit(step_fn)/train.grad/jvp(model.mlp)/tanh"}
}

%fused_computation.2 (param_0.1: f32[8]) -> (f32[8], f32[8]) {
  %param_0.1 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step_fn)/train.update/mul"}
  ROOT %tuple.1 = (f32[8]{0}, f32[8]{0}) tuple(%mul.1, %param_0.1)
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0), metadata={op_name="state"}
  %dot.3 = f32[8]{0} dot(%p, %p), metadata={op_name="jit(step_fn)/train.grad/transpose(jvp(model.head))/dot_general"}
  %fusion.1 = f32[8]{0} fusion(%dot.3), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = (f32[8]{0}, f32[8]{0}) fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %custom-call.4 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/model.attn/bwd/pallas_call"}
  ROOT %copy.5 = f32[8]{0} copy(%fusion.1)
}
"""


def test_scope_ops_names_scope_and_direction():
    assert scopes.module_name(HLO) == "jit_step_fn"
    assert scopes.scope_ops(HLO) == {
        "dot.3": ("model.head", "bwd"),
        "fusion.1": ("model.mlp", "fwd"),
        "tanh.1": ("model.mlp", "fwd"),
        "fusion.2": ("train.update", "fwd"),
        "mul.1": ("train.update", "fwd"),
        "custom-call.4": ("model.attn", "bwd")}
    assert scopes.scope_of("jit(f)/train.grad/jvp()/while/body/"
                           "closed_call/model.mlp/dot_general") == \
        ("model.mlp", "fwd")
    assert scopes.scope_of("jit(f)/add") is None


def _scoped(w, x):
    with jax.named_scope("model.mlp"):
        h = jnp.tanh(x @ w)
    with jax.named_scope("model.head"):
        return jnp.tanh(h @ w).sum()


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A scoped gradient run three times in the window, then a function
    compiled inside it, its compile spans taken from the counters."""
    from repro.compile_cache import watch_compiles
    log = watch_compiles()
    d = tmp_path_factory.mktemp("scopes")
    grad = jax.jit(jax.grad(_scoped))
    w = x = jnp.ones((384, 384))
    grad(w, x).block_until_ready()
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation("bench.window"):
        anchor = time.time()
        for _ in range(3):
            grad(w, x).block_until_ready()
        late = jax.jit(lambda a: jnp.cos(a) @ a + 1.0)
        late(w).block_until_ready()
    jax.profiler.stop_trace()
    spans = log.snapshot(since=anchor).spans
    return d, grad.lower(w, x).compile().as_text(), spans, anchor


def test_scopes_sum_to_the_module(recorded):
    d, hlo, _, _ = recorded
    out = scopes.attribute(d, scopes.scope_ops(hlo), scopes.module_name(hlo))
    s = out["scopes"]
    for name in ("model.mlp", "model.head"):
        assert s[name]["fwd"] > 0 and s[name]["bwd"] > 0, s
    total = sum(v["fwd"] + v["bwd"] for v in s.values())
    assert out["module_s"] > 0
    assert total + out["unscoped_s"] == pytest.approx(out["module_s"])
    assert out["unscoped_s"] < 0.5 * out["module_s"]
    assert all(k.startswith(("jit__scoped:model.", "jit__scoped:-:"))
               for k, _ in out["device_ops"])


def test_compile_in_the_window_labels_its_gap(recorded):
    d, hlo, spans, anchor = recorded
    assert spans, "no compile counted in the window"
    extra = [(s.start, s.end, scopes.COMPILE_SPAN) for s in spans]
    out = scopes.attribute(d, scopes.scope_ops(hlo),
                           scopes.module_name(hlo), extra, anchor)
    idle = dict(out["idle_gaps"])
    compiled = [v for k, v in idle.items() if k.startswith("jax.compile")]
    assert compiled and compiled[0] == max(idle.values())
    with pytest.raises(ValueError):
        scopes.attribute(d, {}, "", extra)


def test_gaps_agree_with_the_reduction(recorded):
    """The idle gaps the attribution finds are those of ``trace.reduce``,
    whose readings stay as they were."""
    d, hlo, _, _ = recorded
    base = tracemod.reduce(d)
    out = scopes.attribute(d, scopes.scope_ops(hlo), scopes.module_name(hlo))
    idle = sum(v for _, v in out["idle_gaps"])
    assert idle == pytest.approx(base["window_s"] - base["busy_s"], rel=1e-6,
                                 abs=1e-9)


@pytest.mark.parametrize("name,mixers", [
    ("yi6b.train_4k", {"model.attn", "model.mlp"}),
    ("xlstm.train_4k", {"model.mlstm", "model.slstm"})])
def test_profile_at_smoke_size(name, mixers, tmp_path, cpu_peak):
    cell = TinyCell(name)
    out = scopes.profile(cell, seed=2 ** 33 + 5, seconds=0.3,
                         tdir=tmp_path / "trace", t0=time.time())
    ms = out["ms_per_step"]
    for scope in mixers | {"model.embed", "model.head", "train.update"}:
        assert ms[scope]["total"] > 0, (scope, ms)
    assert ms["train.update"]["bwd"] == 0
    assert 0 <= out["unscoped_share"] < 0.5
    assert out["compile"]["setup_programs"] >= 1
    assert out["compile"]["setup_s"] > 0
    assert out["compile"]["in_window_programs"] == 0
    assert out["tokens_per_s"]["untraced"] > 0
    assert out["steps"]["traced"] >= 1
