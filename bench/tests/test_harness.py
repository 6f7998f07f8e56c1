"""A tiny CPU cell driven through the whole run: set-up, rehearsal,
warm-up, window, reducers and the check. Its configuration, traffic and
one extra metric are new files the harness finds by name alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, CPU_PEAKS, TINY_CELL, make_root

import harness


def run_tiny(root, seed=2_200_000_123, trace=False, **kw):
    return harness.run(root, TINY_CELL, seed, 0.5, trace,
                       require_tpu=False, peaks=CPU_PEAKS,
                       log=lambda msg: None, **kw)


def test_untraced_run_reports_the_end_to_end_metrics(tmp_path,
                                                     compile_cache):
    done = run_tiny(make_root(tmp_path))
    res = done.result
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0 and res["attempted"] % 6 == 0
    assert set(res["metrics"]) == {"rows_per_s", "latency_ms_p95",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] >= 1
    assert res["checks"]["max_abs_err"]["value"] <= \
        res["checks"]["max_abs_err"]["limit"]
    assert [ln.split(":")[0] for ln in done.check_lines] == [
        f"check {k}" for k in res["checks"]]
    json.dumps(res)


def test_traced_run_reads_every_per_layer_metric_found_by_name(
        tmp_path, compile_cache):
    root = make_root(tmp_path, extra_metric="rows_in_window")
    res = run_tiny(root, trace=True).result
    got = res["metrics"]
    # the CPU has no device plane: the trace readers find nothing and the
    # harness leaves their metrics out; every other reader of the one-chip
    # cells reports
    assert set(got) == {"plan_ms", "resident_share", "exec_ms",
                        "compiles_in_window", "query_ms", "partial_ms",
                        "merge_ms", "rows_in_window"}
    assert got["rows_in_window"]["unit"] == "rows"
    assert got["rows_in_window"]["value"] > 0
    assert 0 <= got["resident_share"]["value"] <= 100
    assert got["exec_ms"]["value"] > 0
    assert res["device"]["busy_s"] == 0.0
    assert res["breakdown"] == {"device_ops": [], "idle_gaps": []}


def test_the_same_seed_serves_the_same_requests(tmp_path, compile_cache):
    root = make_root(tmp_path)
    seen = []
    for _ in range(2):
        seen.append(run_tiny(root, seed=77))
    a, b = (r.result for r in seen)
    assert a["checks"]["max_abs_err"]["value"] == \
        b["checks"]["max_abs_err"]["value"]


def test_the_cell_refuses_the_cpu(tmp_path, compile_cache):
    with pytest.raises(harness.SetupError, match="not 'tpu'"):
        harness.run(make_root(tmp_path), TINY_CELL, 1, 0.5, False,
                    log=lambda msg: None)


def test_unknown_cell_and_device_kind_are_errors(tmp_path):
    root = make_root(tmp_path)
    with pytest.raises(harness.SetupError, match="no workload"):
        harness.load_cell(root, "no.such-cell")
    with pytest.raises(harness.SetupError, match="not in peaks.json"):
        harness.peak_of("TPU v99")


def test_committed_files_alone_print_no_result(tmp_path):
    """A directory with BENCHMARK.json and bench/ but not the program
    exits non-zero and prints nothing on standard output."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "dsv2-1chip.agent-fleet", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_the_rehearsal_covers_the_window_at_the_provided_rate():
    traffic = harness.W.Traffic.from_dict(json.loads(
        (BENCH / "traffic" / "agent-fleet.json").read_text()))
    for seconds in (1, 10, 30):
        steps = harness.rehearse_horizon(traffic, seconds)
        assert steps == traffic.warmup_steps + \
            seconds * harness.REHEARSE_STEPS_PER_S


@pytest.mark.parametrize("n", [5, 130])
def test_chunks_drawn_over_several_devices_are_the_same_draw(n):
    """Chunk i is the seed's key folded with i, drawn on the i-th device
    of a round-robin list: the same data as the one-device draw, at sizes
    that leave a device or a group short."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    seed = 2_200_000_555
    one = harness.make_chunks(jax, seed, n, 8, 4, jnp.bfloat16)
    spread = harness.make_chunks(jax, seed, n, 8, 4, jnp.bfloat16,
                                 (jax.devices()[0],) * 3)
    assert len(one) == len(spread) == n
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32), seed >> 32)
    for i, (a, b) in enumerate(zip(one, spread)):
        want = jax.random.normal(jax.random.fold_in(key, i), (8, 4),
                                 jnp.bfloat16)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(b), np.asarray(want))
