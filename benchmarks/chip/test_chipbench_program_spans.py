"""The reduction of the program's ``repro.*`` spans, on a synthetic trace
with a hand-computed answer, and the loader on a trace recorded here."""

import gc

import jax
import jax.numpy as jnp
import pytest

from chipbench import program_spans as ps
from chipbench import xplane
from repro.obs import spans

SHIFT = 0.002   # the device clock runs 2 ms early


def synthetic():
    # device work truly at prefill [0.12, 0.28] and decode [0.36, 0.48];
    # a tick holds a prefill (admit with a swap-in, then the wait on its
    # token) and a decode step; a collection on another thread while the
    # harness idles
    bench = {"window": [(0.0, 1.0)], "tick": [(0.1, 0.5)],
             "prefill": [(0.119, 0.281)], "decode": [(0.299, 0.491)],
             "idle": [(0.6, 0.9)]}
    dev = [(0.12 - SHIFT, 0.16, "fusion.1"), (0.36 - SHIFT, 0.12, "fusion.2")]
    trace = xplane.Trace(ops=[dev], modules=[(s, d) for s, d, _ in dev],
                         spans=bench)
    main = [("repro.sched.tick", 0.1, 0.5),
            ("repro.engine.prefill", 0.119, 0.281),
            ("repro.kv.admit", 0.119, 0.13),
            ("repro.kv.swap_in", 0.12, 0.125),
            ("repro.engine.sample", 0.2, 0.281),
            ("repro.engine.decode", 0.3, 0.49),
            ("repro.engine.decode_inputs", 0.3, 0.35),
            ("repro.model.decode", 0.35, 0.36),
            ("repro.engine.sample", 0.36, 0.48),
            ("repro.kv.flush", 0.48, 0.49)]
    events = [(n, s, e, "main") for n, s, e in main]
    events.append(("repro.host.gc", 0.7, 0.75, "other"))
    return trace, events


def test_self_times_by_hand():
    trace, events = synthetic()
    got, nested = ps.stats(events, (0.0, 1.0))
    want = {"repro.sched.tick": (1, 0.4, 0.4 - 0.162 - 0.19),
            "repro.engine.prefill": (1, 0.162, 0.162 - 0.011 - 0.081),
            "repro.kv.admit": (1, 0.011, 0.006),
            "repro.kv.swap_in": (1, 0.005, 0.005),
            "repro.engine.decode": (1, 0.19, 0.0),
            "repro.engine.decode_inputs": (1, 0.05, 0.05),
            "repro.model.decode": (1, 0.01, 0.01),
            "repro.engine.sample": (2, 0.201, 0.201),
            "repro.kv.flush": (1, 0.01, 0.01),
            "repro.host.gc": (1, 0.05, 0.05)}
    assert set(got) == set(want)
    for name, (n, total, self_s) in want.items():
        assert got[name].count == n, name
        assert got[name].total_s == pytest.approx(total), name
        assert got[name].self_s == pytest.approx(self_s, abs=1e-12), name
    assert nested[("repro.engine.decode", "repro.engine.sample")] == \
        pytest.approx(0.12)
    assert nested[("repro.engine.prefill", "repro.engine.sample")] == \
        pytest.approx(0.081)
    # a window that cuts the tick leaves it, and only it, out
    got, _ = ps.stats(events, (0.11, 1.0))
    assert "repro.sched.tick" not in got
    assert got["repro.engine.decode"].count == 1


def test_idle_gaps_are_named_and_summed_by_innermost_span():
    trace, events = synthetic()
    red = xplane.reduce(trace)
    assert red.shift_s == pytest.approx(SHIFT)
    out = ps.reduce(trace, red, events)
    # the gaps xplane finds, in its order, named by kind and span
    assert [s for _, s in out.idle_gaps] == \
        pytest.approx([s for _, s in red.idle_gaps])
    assert [n for n, _ in out.idle_gaps] == [
        "idle/host.gc", "window", "decode/engine.decode_inputs"]
    # [0, 0.12]: tick from 0.1, admit from 0.119; [0.28, 0.36]: the
    # prefill's sample, the tick, decode inputs, decode dispatch;
    # [0.48, 1.0]: flush, tick, the collection
    want = {"none": 0.1 + 0.45, "sched.tick": 0.019 + 0.019 + 0.01,
            "kv.admit": 0.001, "engine.sample": 0.001,
            "engine.decode_inputs": 0.05, "model.decode": 0.01,
            "kv.flush": 0.01, "host.gc": 0.05}
    assert set(out.idle_by_span) == set(want)
    for k, v in want.items():
        assert out.idle_by_span[k] == pytest.approx(v), k
    assert sum(out.idle_by_span.values()) == \
        pytest.approx(red.window_s - red.busy_s)


def test_innermost_prefers_the_latest_start_and_then_the_shorter():
    events = [("repro.a", 0.0, 1.0, "t"), ("repro.b", 0.2, 0.6, "t"),
              ("repro.c", 0.2, 0.4, "t"), ("repro.gc", 0.5, 0.7, "u")]
    assert ps.innermost(events) == [
        (0.0, 0.2, "repro.a"), (0.2, 0.4, "repro.c"), (0.4, 0.5, "repro.b"),
        (0.5, 0.7, "repro.gc"), (0.7, 1.0, "repro.a")]
    assert ps.parents(events) == [-1, 0, 1, -1]


def test_load_reads_program_spans_nested_in_the_harness_spans(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    spans.enable(jax.profiler.TraceAnnotation)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.decode"):
                with spans.span("repro.engine.decode", n=3):
                    with spans.span("repro.engine.sample"):
                        jnp.ones((8, 8)).sum().block_until_ready()
                    gc.collect()
    finally:
        spans.disable()
        jax.profiler.stop_trace()
    events = ps.load(str(tmp_path))
    names = [e[0] for e in events]
    assert names.count("repro.engine.decode") == 1
    assert names.count("repro.engine.sample") == 1
    assert "repro.host.gc" in names
    assert not any(n.startswith("bench.") for n in names)
    t = xplane.load(str(tmp_path))
    (w0, w1), = t.spans["window"]
    (d0, d1), = t.spans["decode"]
    dec = events[names.index("repro.engine.decode")]
    smp = events[names.index("repro.engine.sample")]
    assert d0 <= dec[1] <= smp[1] <= smp[2] <= dec[2] <= d1
    parent = ps.parents(events)
    assert parent[names.index("repro.engine.sample")] == \
        names.index("repro.engine.decode")
    got, nested = ps.stats(events, (w0, w1))
    dec_stat = got["repro.engine.decode"]
    assert dec_stat.count == 1
    assert 0 <= dec_stat.self_s < dec_stat.total_s
    assert nested[("repro.engine.decode", "repro.engine.sample")] == \
        pytest.approx(smp[2] - smp[1])
