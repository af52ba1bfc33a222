"""The trace reduction on a small trace recorded on the CPU: busy time as
a union, idle gaps labelled by the host span open in them, device time
grouped by module, kernels tagged, host spans collected."""

import time

import jax
import jax.numpy as jnp
import pytest

import trace as tracemod


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")

    @jax.jit
    def work(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((384, 384))
    work(x).block_until_ready()
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.compute"):
                work(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.2)
    jax.profiler.stop_trace()
    return d


def test_reduce_window_busy_and_gaps(recorded):
    out = tracemod.reduce(recorded)
    assert 0.2 <= out["window_s"] < 5.0
    assert 0.0 < out["busy_s"] < out["window_s"] - 0.15
    labels = dict(out["breakdown"]["idle_gaps"])
    sleep = [v for k, v in labels.items() if k.startswith("bench.sleep")]
    assert sleep and sleep[0] >= 0.19
    assert max(labels.values()) == sleep[0]
    assert len(out["spans"]["bench.compute"]) == 3
    assert out["modules"].get("jit_work", 0) > 0
    assert out["breakdown"]["device_ops"]


def test_kernel_tagging(recorded):
    """Instructions tagged by name are collected as kernel events."""
    ops = {tracemod.op_name(k.split(":", 1)[1]): "work"
           for k, _ in tracemod.reduce(recorded)["breakdown"]["device_ops"]}
    out = tracemod.reduce(recorded, ops)
    assert len(out["kernels"]["work"]) >= 3
    assert sum(out["kernels"]["work"]) <= out["busy_s"] * 1.5


def test_union_and_kernel_ops():
    assert tracemod._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    hlo = ('  %custom-call.7 = bf16[8,128]{1,0} custom-call(%a), '
           'custom_call_target="tpu_custom_call", metadata={op_name='
           '"jit(step_fn)/jit(flash_attention)/pallas_call"}\n'
           '  %fusion.1 = f32[8]{0} fusion(%b), kind=kLoop\n')
    assert tracemod.kernel_ops(hlo) == {"custom-call.7": "flash_attention"}
    assert tracemod.op_name("%flash_attention.4 = bf16[32,4096,128]{2,1,0} "
                            "custom-call(%a)") == "flash_attention.4"
    assert tracemod.kernel_tag("flash_attention.4") == "flash_attention"
    assert tracemod.kernel_tag("fusion.47") is None


def _custom_call(wrapper: str) -> str:
    return ('  %custom-call.3 = bf16[8,128]{1,0} custom-call(%a), '
            'custom_call_target="tpu_custom_call", metadata={op_name='
            f'"jit(step_fn)/jit({wrapper})/pallas_call"}}')


@pytest.mark.parametrize("wrapper,tag", [
    ("flash_attention", "flash_attention"),
    ("flash_attention_dkdv", "flash_attention"),
    ("flash_attention_dq", "flash_attention"),
    ("mlstm_chunk", "mlstm_chunk"),
    ("matern52_gram", "matern52_gram"),
    ("matern52_cross", "matern52_cross"),
    ("grouped_matmul", None)])
def test_kernel_ops_take_tags_from_kernel_modules(wrapper, tag):
    """Each file under ``flops/kernels/`` tags the Mosaic calls whose line
    holds one of its substrings; a call no module names stays untagged."""
    want = {"custom-call.3": tag} if tag else {}
    assert tracemod.kernel_ops(_custom_call(wrapper)) == want
    assert tracemod.kernel_tag(f"{wrapper}.4") == (
        wrapper if wrapper == tag else None)


def test_self_times_leave_out_nested_operations():
    evs = [(0.0, 10.0, "while"), (1.0, 3.0, "a"), (4.0, 9.0, "b"),
           (5.0, 6.0, "c"), (11.0, 12.0, "d")]
    assert tracemod._self_times(evs) == [3.0, 2.0, 4.0, 1.0, 1.0]
