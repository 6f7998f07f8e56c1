"""The trace reduction, on synthetic traces with known answers and on a
trace recorded on the CPU, and the collective_ms reader over it."""

import glob

import pytest

import tracereduce as TR

MS = 1e6     # ns


def trace_of(ops, spans, modules=None):
    return TR.Trace(ops=ops, modules=modules or {}, spans=spans)


def test_busy_is_the_union_clipped_to_the_step_window():
    # window 0..10 ms; ops overlap and spill outside the window
    ops = {0: [("%a = f32[] add(f32[] %x)", -1 * MS, 2 * MS),
               ("%b = f32[] multiply(f32[] %x)", 1 * MS, 3 * MS),
               ("%c = f32[] exp(f32[] %x)", 6 * MS, 12 * MS)]}
    spans = [("bench.step", 0, 5 * MS), ("bench.step", 5 * MS, 10 * MS)]
    s = TR.reduce(trace_of(ops, spans))
    assert s.window_s == pytest.approx(10e-3)
    assert s.busy_s == pytest.approx(7e-3)          # [0,3] + [6,10]
    assert s.n_steps == 2 and s.n_chips == 1
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.3)


def test_busy_and_collective_time_are_means_over_chips():
    spans = [("bench.step", 0, 10 * MS)]
    permute = ("%collective-permute.1 = bf16[4,8]{1,0} "
               "collective-permute(bf16[4,8]{1,0} %q), "
               "source_target_pairs={{0,1}}")
    consumer = "%fusion.3 = bf16[4,8] fusion(bf16[4,8] %all-gather.2)"
    ops = {0: [(permute, 0, 2 * MS), (consumer, 2 * MS, 6 * MS)],
           1: [(permute, 1 * MS, 3 * MS),
               ("%all-gather-done.2 = bf16[16,8] all-gather-done(%s)",
                2 * MS, 4 * MS)]}
    s = TR.reduce(trace_of(ops, spans))
    assert s.busy_s == pytest.approx((6e-3 + 3e-3) / 2)
    # chip 0: the permute only (the fusion merely reads a gather's
    # result); chip 1: permute and gather-done overlap -> [1,4]
    assert s.collective_s == pytest.approx((2e-3 + 3e-3) / 2)


def test_collective_ms_is_the_collective_time_per_traced_step():
    from conftest import BENCH
    import harness
    read = harness.metric_reader(BENCH, "collective_ms")
    window = harness.Window(steps=[], seconds=1.0, compiles=0, chips=2,
                            peak={}, trace=None)
    assert read(window) is None                  # no trace: nothing read
    window.trace = TR.reduce(trace_of({}, [("bench.step", 0, MS)]))
    assert read(window) is None                  # no device: nothing
    permute = "%collective-permute.1 = bf16[4] collective-permute(%q)"
    spans = [("bench.step", 0, 5 * MS), ("bench.step", 5 * MS, 10 * MS)]
    ops = {0: [(permute, 1 * MS, 3 * MS), (permute, 6 * MS, 7 * MS)],
           1: [(permute, 2 * MS, 3 * MS), ("%a = f32[] add()", 0, MS)]}
    window.trace = TR.reduce(trace_of(ops, spans))
    # (3 + 1) / 2 ms a chip over 2 steps
    assert read(window) == pytest.approx(1.0)


def test_idle_gaps_are_named_by_the_innermost_host_span():
    ops = {0: [("%a = f32[] add()", 1 * MS, 2 * MS),
               ("%b = f32[] add()", 7 * MS, 8 * MS)]}
    spans = [("bench.step", 0, 10 * MS),
             ("bench.plan_step", 0, 1 * MS),
             ("bench.submit", 2 * MS, 6.5 * MS),
             ("bench.collect", 8 * MS, 10 * MS)]
    s = TR.reduce(trace_of(ops, spans))
    names = [n for n, _ in s.idle_gaps]
    secs = [v for _, v in s.idle_gaps]
    assert names == ["bench.submit", "bench.collect", "bench.plan_step"]
    assert secs == pytest.approx([5e-3, 2e-3, 1e-3])


def test_device_ops_are_ranked_by_program_time():
    spans = [("bench.step", 0, 10 * MS)]
    mods = {0: [("jit_exp", 0, 1 * MS), ("jit__einsum", 1 * MS, 4 * MS),
                ("jit_exp", 5 * MS, 6 * MS)]}
    s = TR.reduce(trace_of({0: [("%x = f32[] exp()", 0, 6 * MS)]}, spans,
                           mods))
    assert s.device_ops == [["jit__einsum", pytest.approx(3e-3)],
                            ["jit_exp", pytest.approx(2e-3)]]


def test_no_device_or_no_step_reads_nothing():
    s = TR.reduce(trace_of({}, [("bench.step", 0, MS)]))
    assert s.n_chips == 0 and s.busy_s == 0.0 and s.device_ops == []
    s = TR.reduce(trace_of({0: [("%a = f32[] add()", 0, MS)]}, []))
    assert s.n_steps == 0 and s.window_s == 0.0


def test_extract_finds_the_benchmark_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    x = jnp.ones((64, 64))
    jax.block_until_ready(x @ x)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(2):
        with jax.profiler.TraceAnnotation(TR.STEP_SPAN):
            with jax.profiler.TraceAnnotation("bench.submit"):
                jax.block_until_ready(jnp.exp(x @ x))
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    t = TR.extract(path)
    names = sorted(n for n, _, _ in t.spans)
    assert names == ["bench.step", "bench.step", "bench.submit",
                     "bench.submit"]
    assert all(e > s for _, s, e in t.spans)
    # the CPU has no device plane: nothing is busy, nothing is invented
    assert t.ops == {} and TR.reduce(t).busy_s == 0.0
