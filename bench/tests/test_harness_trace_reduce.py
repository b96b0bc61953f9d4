"""The trace reduction, on a trace recorded on the CPU in the test and on made-up events."""

import time

import jax
import jax.numpy as jnp

from bench import trace_reduce as tr


def test_merge_and_innermost():
    assert tr._merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]
    events = [("outer", 0, 100), ("inner", 10, 20), ("late", 50, 60)]
    assert tr._innermost_at(events, [5, 15, 30, 55, 150]) == [
        "outer", "inner", "outer", "late", None]


def test_op_labels_carry_module_and_target():
    name = ('%custom-call.4 = f32[10,78,78] custom-call(f32[10,78,78] %x), '
            'custom_call_target="tpu_custom_call"')
    assert tr._op_label(name) == "%custom-call.4 (tpu_custom_call)"
    ops = [(name, 15, 18), ("%fusion.1 = f32[4] fusion(..)", 40, 41)]
    modules = [("jit_gram(123)", 10, 20), ("jit__eval(9)", 30, 50)]
    assert [o[0] for o in tr._in_modules(ops, modules)] == [
        "jit_gram/%custom-call.4 (tpu_custom_call)", "jit__eval/%fusion.1"]


def test_reduce_a_cpu_trace(tmp_path):
    f = jax.jit(lambda a: jnp.tanh(a @ a.T).sum())
    a = jnp.ones((256, 256), jnp.float32)
    f(a).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.compute"):
            f(a).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.host_wait"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    red = tr.reduce_trace(tr.find_xplane(tmp_path))
    assert red["devices"] == 1
    assert 0.06 <= red["window_s"] < 5.0
    assert 0.0 < red["busy_s"] < red["window_s"]
    assert red["ops"] and all(v > 0 for v in red["ops"].values())
    idle = dict(red["idle_gaps"])
    # the sleeps are device idle time, attributed to the span the host was in
    assert sum(v for k, v in idle.items() if k.startswith("bench.host_wait")) >= 0.05
    assert red["collective_s"] == 0.0
