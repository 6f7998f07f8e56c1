"""The readers of the program's phase timer (query_ms, partial_ms,
merge_ms, fused_ms), on synthetic windows and through tiny traced runs
on one device (the exec backend) and on four (the shard_map backend)."""

from types import SimpleNamespace

import pytest

from conftest import BENCH, CPU_PEAKS, TINY_CELL, make_root, run_four_chips

import harness

PHASE_S = [{"query": 0.010, "route": 0.040, "resident": 0.020,
            "merge": 0.030, "stack": 0.5},
           {"query": 0.002, "local": 0.004, "fetch": 0.006}]
# mean per step in ms, a phase absent from a step counting as 0
WANT = {"query_ms": 6.0, "partial_ms": 35.0, "merge_ms": 15.0,
        "fused_ms": 250.0}
# the phases the exec backend opens
EXEC = ("query_ms", "partial_ms", "merge_ms")


def window_of(stats):
    steps = [harness.StepRecord(stats=s, plan_s=0.0, exec_s=0.0, flops=0,
                                rows=1, n_requests=1) for s in stats]
    return harness.Window(steps=steps, seconds=1.0, compiles=0, chips=1,
                          peak={})


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_is_the_mean_self_time_per_step(name):
    read = harness.metric_reader(BENCH, name)
    w = window_of([SimpleNamespace(phase_s=p) for p in PHASE_S])
    assert read(w) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reads_nothing_without_steps_or_a_phase_timer(name):
    read = harness.metric_reader(BENCH, name)
    assert read(window_of([])) is None
    # a program without the phase timer: its StepStats have no phase_s
    assert read(window_of([SimpleNamespace(n_pairs=2)])) is None
    # steps that opened no phase read 0
    assert read(window_of([SimpleNamespace(phase_s={})])) == 0.0


def test_traced_run_reports_the_phases_inside_exec_ms(tmp_path,
                                                       compile_cache):
    root = make_root(tmp_path)
    res = harness.run(root, TINY_CELL, 2_200_000_321, 0.5, True,
                      require_tpu=False, peaks=CPU_PEAKS,
                      log=lambda msg: None).result
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert all(got[k] > 0 for k in EXEC)
    assert "fused_ms" not in got
    assert res["metrics"]["partial_ms"]["unit"] == "ms"
    # self times of phases inside backend.submit, which exec_ms times
    assert sum(got[k] for k in EXEC) <= got["exec_ms"]


def test_four_chip_traced_run_reads_the_fused_phases(tmp_path):
    """The shard_map backend opens stack, dispatch and barrier phases
    (fused_ms) and no resident, route, fetch or local phase, so the cell
    it stands for does not report partial_ms."""
    res = run_four_chips(tmp_path, trace=True)
    assert res["correct"] is True
    assert res["device"]["count"] == 4
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["fused_ms"] > 0
    assert "partial_ms" not in got
    assert got["query_ms"] > 0 and got["merge_ms"] > 0
    assert got["fused_ms"] + got["query_ms"] + got["merge_ms"] <= \
        got["exec_ms"]
