"""A configuration's model hooks on the tiny CPU cell. Each module under
data/ replaces one hook, and the run has to show it; a configuration that
names no module runs the harness's own hooks, which hold the served
outputs to dense attention and count dense FLOPs."""

import itertools
import json

import numpy as np
import pytest

from conftest import BENCH, CPU_PEAKS, DATA, TINY_CELL, make_root

import flops as F
import harness
import reference as R
import tracereduce as TR
import workload as W

SEED = 2_200_000_987


def run_tiny(root):
    return harness.run(root, TINY_CELL, SEED, 0.5, False, require_tpu=False,
                       peaks=CPU_PEAKS, log=lambda msg: None)


def config_of(root):
    return json.loads((root / "bench" / "configs" /
                       "tiny-mla.4inst-cpu.json").read_text())


def attn_mfu(steps):
    """attn_mfu of the steps over a synthetic trace of fixed busy time."""
    trace = TR.Summary(window_s=1.0, busy_s=0.5, collective_s=0.0,
                       n_steps=len(steps), n_chips=1, device_ops=[],
                       idle_gaps=[])
    window = harness.Window(steps=steps, seconds=1.0, compiles=0, chips=1,
                            peak=CPU_PEAKS["cpu"], trace=trace)
    return harness.metric_reader(BENCH, "attn_mfu")(window)


def own_hooks(root, tmp_path):
    done = run_tiny(root)
    assert done.result["correct"] is True
    # the window's steps are the trace's steps after the warm-up, and each
    # counts the dense formula at the configuration's widths
    config = config_of(root)
    traffic = W.Traffic.from_dict(json.loads(
        (DATA / "tiny-fleet.json").read_text()))
    steps = done.window.steps
    reqs = list(itertools.islice(
        W.sessions(traffic, config["corpus"]["chunks"],
                   config["serving"]["instances"], SEED),
        traffic.warmup_steps, traffic.warmup_steps + len(steps)))
    geom = R.Geometry.of(config)
    assert [s.flops for s in steps] == [
        F.step_flops(r, config["corpus"]["chunk_tokens"], geom.n_heads,
                     geom.d_qk, geom.d_v) for r in reqs]


def reference_off(root, tmp_path):
    res = run_tiny(root).result
    assert res["correct"] is False and res["failed"] > 0
    chk = res["checks"]["max_abs_err"]
    assert chk["value"] > chk["limit"]


def flops_doubled(root, tmp_path):
    base = run_tiny(make_root(tmp_path / "own")).window.steps
    doubled = run_tiny(root).window.steps
    n = min(len(base), len(doubled))
    assert n > 0
    assert [s.flops for s in doubled[:n]] == [2 * s.flops for s in base[:n]]
    assert attn_mfu(doubled[:n]) == pytest.approx(2 * attn_mfu(base[:n]))


def engines_recorded(root, tmp_path):
    assert run_tiny(root).result["correct"] is True
    calls = [json.loads(line) for line in
             (root / "bench" / "models" / "hooks_recording.calls")
             .read_text().splitlines()]
    # the rehearsal's engine and the served one, each with the selector
    # and the same index keys from the seed
    assert len(calls) == 2
    assert calls[0]["engine"] != calls[1]["engine"]
    assert {c["selector"] for c in calls} == {"IndexerService"}
    assert {c["seed"] for c in calls} == {SEED}
    assert calls[0]["keys_sum"] == calls[1]["keys_sum"]


def misspelt_fails_setup(root, tmp_path):
    with pytest.raises(harness.SetupError,
                       match=r"defines \['step_flop'\]"):
        run_tiny(root)


CASES = {"": own_hooks, "hooks_expected_off": reference_off,
         "hooks_double_flops": flops_doubled,
         "hooks_recording": engines_recorded,
         "hooks_misspelt": misspelt_fails_setup}


@pytest.mark.parametrize("model", sorted(CASES), ids=lambda m: m or "none")
def test_a_hook_module_replaces_the_harness_hook(model, tmp_path,
                                                 compile_cache):
    root = make_root(tmp_path / "hooked", model=model)
    CASES[model](root, tmp_path)


def test_the_default_reference_is_the_dense_formula():
    """With no module the check holds each output to float32 attention over
    the request's concatenated chunks, as before hooks existed: the same
    gap, the control in fp8."""
    config = json.loads((DATA / "tiny-mla.4inst-cpu.json").read_text())
    model = harness.load_model(BENCH, config)
    geom = R.Geometry.of(config)
    import jax
    import jax.numpy as jnp
    dtype = jnp.bfloat16
    chunks = harness.make_chunks(jax, SEED, 4, 64, geom.d_qk, dtype)
    req = W.Req(7, 1, ("corpus_0002", "corpus_0000"), 4, 3,
                W.query_seed(SEED, 7))
    q = R.query(geom, req.query_seed, 11, req.m_q, dtype)
    cat = jnp.concatenate([chunks[2], chunks[0]])
    want = R.attend(q, cat, d_v=geom.d_v, scale=geom.scale)
    served = want + jnp.asarray(np.linspace(0, 3e-3, want.size)
                                .reshape(want.shape), jnp.float32)
    entry = {"req": req, "step": 11, "o": served}
    got, ctrl = harness.check([entry], chunks, model, config, SEED, dtype,
                              1e-2, jax.devices()[0], control=True)
    assert got.worst == R.gap(served, want) and got.passes
    assert ctrl.worst == R.gap(R.attend_fp8(q, cat, d_v=geom.d_v,
                                            scale=geom.scale), want)
    short = dict(entry, o=served[:, :1])
    assert harness.check([short], chunks, model, config, SEED, dtype,
                         1e-2, jax.devices()[0])[0].worst == float("inf")
