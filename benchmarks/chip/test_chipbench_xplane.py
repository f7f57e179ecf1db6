"""The trace reduction, on a synthetic trace with a hand-computed
answer, and the loader on a trace recorded here."""

import jax
import jax.numpy as jnp
import pytest

from chipbench import xplane

SHIFT = 0.002   # the device clock runs 2 ms early


def synthetic():
    # host spans (s); device work truly at prefill [0.12, 0.28] and
    # decode [0.36, 0.48], recorded 2 ms early on the device clock
    spans = {"window": [(0.0, 1.0)], "tick": [(0.1, 0.5)],
             "prefill": [(0.119, 0.281)], "decode": [(0.359, 0.481)],
             "idle": [(0.6, 0.9)]}
    dev = [(0.12 - SHIFT, 0.16, "fusion.1"), (0.36 - SHIFT, 0.12, "fusion.2")]
    return xplane.Trace(ops=[dev], modules=[(s, d) for s, d, _ in dev],
                        spans=spans)


def test_reduce_by_hand():
    r = xplane.reduce(synthetic())
    assert r.shift_s == pytest.approx(SHIFT)
    assert r.window_s == pytest.approx(1.0)
    assert r.busy_s == pytest.approx(0.28)
    assert r.device_s["prefill"] == pytest.approx(0.16)
    assert r.device_s["decode"] == pytest.approx(0.12)
    assert r.device_s["tick"] == pytest.approx(0.28)
    assert r.device_s["idle"] == pytest.approx(0.0)
    assert [n for n, _ in r.device_ops] == ["prefill/fusion.1",
                                            "decode/fusion.2"]
    assert [s for _, s in r.device_ops] == pytest.approx([0.16, 0.12])
    # gaps: [0.48, 1.0] while idle, [0, 0.12] before any tick, and
    # [0.28, 0.36] in the tick between its prefill and its decode
    assert [n for n, _ in r.idle_gaps] == ["idle", "window", "tick"]
    assert [s for _, s in r.idle_gaps] == pytest.approx([0.52, 0.12, 0.08])


def test_busy_is_a_union_and_averaged_over_chips():
    t = synthetic()
    overlapping = t.ops[0] + [(0.2 - SHIFT, 0.1, "copy")]
    t2 = xplane.Trace([overlapping, []], t.modules, t.spans)
    r = xplane.reduce(t2)
    assert r.busy_s == pytest.approx((0.28 + 0.02) / 2)


def test_helpers():
    assert xplane.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert xplane.overlap([(0, 2), (3, 4)], [(1, 3.5)]) == pytest.approx(1.5)
    assert xplane.op_name("%fusion.3 = bf16[2]{0} fusion(x)") == "fusion.3"


def test_load_reads_the_harness_spans(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.decode"):
            jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    t = xplane.load(str(tmp_path))
    assert len(t.spans["window"]) == 1 and len(t.spans["decode"]) == 1
    (w0, w1), = t.spans["window"]
    (d0, d1), = t.spans["decode"]
    assert w0 <= d0 <= d1 <= w1
