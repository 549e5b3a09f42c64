"""Trace reduction: exact on hand-built intervals, and end to end on a
small trace recorded here on the CPU (its XLA client thread stands in
for a device plane)."""
import numpy as np
import pytest

from harness import tracing
from harness.window import SPAN_PREFIX


def test_merge_and_gaps_by_hand():
    s = np.array([5.0, 0.0, 2.0, 20.0, 30.0])
    e = np.array([8.0, 3.0, 4.0, 25.0, 40.0])
    ms, me = tracing.merge(s, e, 1.0, 35.0)
    assert ms.tolist() == [1.0, 5.0, 20.0, 30.0]
    assert me.tolist() == [4.0, 8.0, 25.0, 35.0]
    gs, ge = tracing.gaps(ms, me, 1.0, 35.0)
    assert list(zip(gs, ge)) == [(4.0, 5.0), (8.0, 20.0), (25.0, 30.0)]


def test_reduce_by_hand():
    ns = 1e9
    ops = (["a", "b", "a"], np.array([0.0, 2.0, 6.0]) * ns,
           np.array([1.0, 3.0, 7.0]) * ns)
    trace = tracing.Trace(
        devices=[ops], modules=[(["jit_a(1)", "jit_b(2)", "jit_a(1)"],
                                 ops[1], ops[2])],
        spans=[(SPAN_PREFIX + "round", 0.0, 10.0 * ns),
               (SPAN_PREFIX + "eval_compose", 0.0, 4.0 * ns),
               (SPAN_PREFIX + "run_round", 4.0 * ns, 10.0 * ns)])
    r = tracing.reduce(trace)
    assert r.window_s == pytest.approx(10.0)
    assert r.busy_s == pytest.approx(3.0)
    assert r.module_seconds == pytest.approx({"jit_a": 2.0, "jit_b": 1.0})
    assert r.seconds_of(lambda n: n == "jit_b") == pytest.approx(1.0)
    # idle: [1,2] [3,6] -> eval_compose 1 + 1 (3..4 of 3..6 is less than
    # 4..6), run_round 3..6 mostly: 4..6 > 3..4, so run_round; [7,10]
    assert r.idle_by_span == pytest.approx({"eval_compose": 1.0,
                                            "run_round": 6.0})
    bd = r.breakdown()
    assert bd["device_ops"] == [["jit_a", 2.0], ["jit_b", 1.0]]
    assert bd["idle_gaps"][0] == ["run_round", 6.0]


def test_two_devices_average():
    ns = 1e9
    dev = lambda a, b: (["x"], np.array([a * ns]), np.array([b * ns]))
    trace = tracing.Trace(devices=[dev(0, 4), dev(0, 2)],
                          spans=[(SPAN_PREFIX + "round", 0.0, 8 * ns)])
    r = tracing.reduce(trace)
    assert r.busy_s == pytest.approx(3.0)
    assert r.idle_by_span == pytest.approx({"other": 5.0})


def test_cpu_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + "round"):
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + "run_round"):
                for _ in range(3):
                    f(x).block_until_ready()
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + "tail"):
                sum(range(100000))
    jax.profiler.stop_trace()
    path = tracing.xplane_file(str(tmp_path))
    trace = tracing.load(path, plane_prefix="/host:CPU",
                         ops_line="tf_XLAPjRtCpuClient",
                         modules_line="tf_XLAPjRtCpuClient")
    assert [n for n, _, _ in trace.spans].count(SPAN_PREFIX + "round") == 2
    r = tracing.reduce(trace)
    assert r.n_devices == 1
    assert 0.0 < r.busy_s < r.window_s
    assert any("dot" in name for name in r.module_seconds)
    idle = sum(r.idle_by_span.values())
    assert idle == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    assert "tail" in r.idle_by_span


def test_wire_roofline_reader_by_hand():
    """The reader's least time over the wire programs' device time."""
    import json
    import os
    from types import SimpleNamespace

    import jax

    from harness import counts
    from harness.main import load_reader
    from reference import decoder

    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(bench, "configs", "charlm-shakespeare.json")) as f:
        cfg = json.load(f)["model"]
    from repro.configs.base import ModelConfig
    from repro.models import build
    shapes = jax.eval_shape(build(ModelConfig(**cfg)).init,
                            jax.random.PRNGKey(0))
    reduced = tracing.Reduced(
        window_s=2.0, busy_s=1.0, n_devices=1, idle_by_span={},
        module_seconds={"jit_quantize_blocks": 0.004,
                        "jit_dequantize_blocks": 0.006,
                        "jit__one_client": 0.5})
    cohorts = [[(5, 17, 26, 1, 3)] * 6, [(5, 17, 26, 2, 3)] * 6]
    run = SimpleNamespace(
        trace=reduced, peaks={"hbm_bytes_per_s": 819e9}, cohorts=cohorts,
        shapes=shapes, mask=lambda k: decoder.trainable_mask(shapes, cfg, k))
    n = counts.trainable_elements(shapes, decoder.trainable_mask(shapes, cfg, 5))
    need = 6 * (counts.wire_bytes(n, 1) + counts.wire_bytes(n, 2))
    got = load_reader("wire_roofline")(run)
    assert got == pytest.approx(100.0 * need / 819e9 / 0.010)
    # rounds at q = 0 only: nothing to read
    run.cohorts = [[(6, 40, 32, 0, 1)] * 6]
    assert load_reader("wire_roofline")(run) is None
    # rounds at q > 0 but no wire program in the trace: an error
    run.cohorts = cohorts
    reduced.module_seconds = {"jit__one_client": 0.5}
    with pytest.raises(RuntimeError, match="jit__one_client"):
        load_reader("wire_roofline")(run)
