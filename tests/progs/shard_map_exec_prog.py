"""ShardMapExecBackend end-to-end on a real 8-device mesh (subprocess-only:
forces 8 host devices, so it must NOT run inside the main pytest process).

The ISSUE 7 acceptance gate:

* all three dense golden scenarios + the selection scenario execute with
  real collectives and reproduce the single-instance oracles to float
  round-off;
* planner StepStats are bit-identical to the AnalyticBackend run
  (host-clock fields excepted);
* every transporting step yields a measured-vs-analytic MeasuredReport
  whose flow structure matches the analytic schedule stage-for-stage;
* the mesh indexer service (ShardMapIndexerService) returns the SAME
  verdicts as the host IndexerService;
* a dead holder mid-run (fail_instance) still reproduces the oracle
  through the promoted replica;
* shard-shape mismatches fail up front with named shards, not as opaque
  XLA lowering errors.

Extended by ISSUE 8 (overlapped dispatch-group execution):

* every dense/selection check above runs in BOTH execution modes —
  fused/overlapped (the default) and the serial staged_call chain
  (`fused=False`, the A/B kill switch) — with identical StepStats;
* fused-vs-serial outputs agree to <= 1e-6 on the golden scenarios AND
  on randomized agentic workloads (several seeds);
* the fused path apportions stage walls without gaps: stage_fills == 0
  on every planned step (the _measured_flow silent-zero fix);
* fetched committed copies live in a BOUNDED pool that retires entries
  with their replicas (evict listener).

Extended by ISSUE 9 (flight recorder): one traced run must export planned
AND measured track groups, publish the exec-side metric series, and the
drift monitor must be LOUD on forced host devices (whose walls sit orders
of magnitude off the fabric model — silence there would mean the monitor
is broken).
"""

import math
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp
import numpy as np

from repro.core.merge import Partial
from repro.core.routing import check_route_shards
from repro.serving import timeline as TL
from repro.serving.backends import (AnalyticBackend, JaxExecBackend,
                                    ShardMapExecBackend)
from repro.serving.backends.jax_exec import max_oracle_err
from repro.serving.backends.shard_map import check_instance_shards
from repro.serving.engine import EngineConfig, Request, ServingEngine
from repro.serving.selection import (IndexerService, SelectionConfig,
                                     ShardMapIndexerService)

from engine_scenarios import SCENARIOS, selection_scenario

TOL = 2e-5


def stats_dict(st):
    return st.comparable()         # host-clock fields dropped


def run_engine(eng, steps):
    for reqs in steps:
        eng.schedule_step(reqs)
    return eng


def check_measured(eng, name):
    """Every step got a MeasuredReport; its analytic side IS the step's
    accounted timeline and the measured side mirrors the flow structure."""
    for st, rep in zip(eng.stats, eng.measured_reports):
        assert rep is not None, (name, st.step)
        assert rep.analytic.makespan_s == st.latency_s, (name, st.step)
        a_names = set(st.stage_totals)
        m_names = set(rep.measured.stage_totals())
        assert m_names == a_names, (name, st.step, a_names, m_names)
        if a_names:                 # a transporting step measured real time
            assert rep.measured.makespan_s > 0.0, (name, st.step)
            assert rep.wall_s > 0.0, (name, st.step)


def max_ab_err(eng_f, eng_s, step):
    """Worst |fused output - serial output| over one step's requests."""
    outs_f, outs_s = eng_f.outputs_of(step), eng_s.outputs_of(step)
    assert outs_f.keys() == outs_s.keys(), step
    worst = 0.0
    for rid, p in outs_f.items():
        worst = max(worst, float(jnp.max(jnp.abs(p.o - outs_s[rid].o))))
    return worst


def test_dense_scenarios():
    for name, build in SCENARIOS.items():
        eng_a = run_engine(*build(backend=AnalyticBackend()))
        eng_f = run_engine(*build(backend=ShardMapExecBackend()))
        eng_s = run_engine(*build(backend=ShardMapExecBackend(fused=False)))
        # BOTH modes must leave the planner's accounting untouched
        want = [stats_dict(s) for s in eng_a.stats]
        assert want == [stats_dict(s) for s in eng_f.stats], name
        assert want == [stats_dict(s) for s in eng_s.stats], name
        _, steps = build()
        ab = 0.0
        for reqs, st in zip(steps, eng_f.stats):
            for eng in (eng_f, eng_s):
                err = max_oracle_err(eng, reqs, st.step)
                assert err <= TOL, (name, st.step, err,
                                    eng.backend.fused)
            ab = max(ab, max_ab_err(eng_f, eng_s, st.step))
        assert ab <= 1e-6, (name, ab)
        for eng, mode in ((eng_f, "fused"), (eng_s, "serial")):
            check_measured(eng, name)
            for rep in eng.measured_reports:
                assert rep.mode == mode, (name, rep.step, rep.mode)
                # S6: apportioning covered every planned stage — a fill
                # on these golden traces would mean a silent 0.0 again
                assert rep.stage_fills == 0, (name, rep.step,
                                              rep.stage_fills)
        last = eng_f.measured_reports[-1]
        print(f"  {name}: StepStats parity both modes + oracle exact, "
              f"fused-vs-serial max|err| {ab:.2e} "
              f"(last-step makespan ratio x{last.makespan_ratio:.2f})")
    print(eng_f.measured_reports[0].summary())


def test_randomized_ab():
    """Fused vs serial on randomized agentic workloads (ISSUE 8 S3): the
    SAME generated trace through both modes — bit-identical StepStats,
    outputs within 1e-6, no apportioning gaps."""
    from repro.serving.workload import (WorkloadConfig, agentic_trace,
                                        materialize_trace, register_corpus)
    for seed in (0, 7, 23):
        def build(backend, seed=seed):
            eng = ServingEngine(8, pool_tokens=24 * 256,
                                cfg=EngineConfig(), instances_per_pod=4,
                                backend=backend)
            w = WorkloadConfig(n_steps=6, agents=6, n_corpus_chunks=10,
                               chunk_tokens=256, session_steps=(2, 6),
                               selection_frac=0.0, seed=seed)
            cids = register_corpus(eng, w)
            return eng, materialize_trace(agentic_trace(w, eng, cids))

        eng_f, steps = build(ShardMapExecBackend())
        eng_s, _ = build(ShardMapExecBackend(fused=False))
        ab = 0.0
        for reqs in steps:
            eng_f.schedule_step(reqs)
            eng_s.schedule_step(reqs)
            step = eng_f.stats[-1].step
            assert stats_dict(eng_f.stats[-1]) \
                == stats_dict(eng_s.stats[-1]), (seed, step)
            err = max_oracle_err(eng_f, reqs, step)
            assert err <= TOL, (seed, step, err)
            ab = max(ab, max_ab_err(eng_f, eng_s, step))
        assert ab <= 1e-6, (seed, ab)
        assert all(r.stage_fills == 0 for r in eng_f.measured_reports
                   if r is not None), seed
        print(f"  randomized A/B seed {seed}: {len(steps)} steps, "
              f"fused-vs-serial max|err| {ab:.2e}")


def test_pipelined_ab():
    """ISSUE 10: the pipelined engine (plan N+1 under execute N, deferred
    barrier) against the depth-1 lockstep oracle on the real mesh —
    bit-identical StepStats/records/residency at depths {2, 4}, outputs
    still §3.3-exact, and the warm steps demonstrably hide planner wall
    under the device barrier."""
    def rec_key(r):
        return (r.step, r.primitive, r.chunk_id, r.holder, r.n_requesters,
                r.m_q_total, r.backup, r.fabric_idx, r.link_instance,
                r.home, r.req_ids, r.est_cost_s, r.stages)

    for name, build in SCENARIOS.items():
        base, steps = build(backend=ShardMapExecBackend())
        base.run(iter(steps))
        for depth in (2, 4):
            eng, steps_d = build(
                backend=ShardMapExecBackend(),
                cfg=EngineConfig(pipeline_depth=depth))
            eng.run(iter(steps_d))
            assert [stats_dict(s) for s in base.stats] \
                == [stats_dict(s) for s in eng.stats], (name, depth)
            assert [rec_key(r) for r in base.log] \
                == [rec_key(r) for r in eng.log], (name, depth)
            assert base.store.residency_snapshot() \
                == eng.store.residency_snapshot(), (name, depth)
            assert eng.misspeculation_replans == 0, (name, depth)
            for reqs, st in zip(steps_d, eng.stats):
                err = max_oracle_err(eng, reqs, st.step)
                assert err <= TOL, (name, depth, st.step, err)

    # warm overlap: same trace repeated — after compile warm-up the
    # deferred barrier must actually hide planner wall (ISSUE 10 gate
    # proper lives in bench_serving_steadystate --exec-bench; this is the
    # functional floor: SOME wall was hidden)
    from repro.serving.workload import (WorkloadConfig, agentic_trace,
                                        materialize_trace, register_corpus)

    def wl_build(depth):
        eng = ServingEngine(8, pool_tokens=24 * 256,
                            cfg=EngineConfig(pipeline_depth=depth),
                            instances_per_pod=4,
                            backend=ShardMapExecBackend())
        w = WorkloadConfig(n_steps=6, agents=6, n_corpus_chunks=10,
                           chunk_tokens=256, session_steps=(2, 6),
                           selection_frac=0.0, seed=7)
        cids = register_corpus(eng, w)
        return eng, materialize_trace(agentic_trace(w, eng, cids))

    base, steps = wl_build(1)
    base.run(iter(steps))
    pipe, steps_p = wl_build(2)
    pipe.run(iter(steps_p))
    assert [stats_dict(s) for s in base.stats] \
        == [stats_dict(s) for s in pipe.stats], "randomized pipelined A/B"
    assert pipe.planner_overlap_s > 0.0, \
        "depth 2 on the mesh hid no planner wall at all"
    print(f"  pipelined A/B depths {{2,4}}: bit-identical to lockstep + "
          f"oracle exact; randomized depth-2 run hid "
          f"{pipe.planner_overlap_s*1e3:.2f}ms of planner wall")


def test_pool_retirement():
    """S1: fetch persistence fills the committed-copy pool; evicting the
    replica (LRU path / fail_instance) retires the pooled buffer too."""
    backend = ShardMapExecBackend()
    eng, steps = SCENARIOS["fetch_heavy"](backend=backend)
    eng.schedule_step(steps[0])            # three FETCHes persist on home 0
    rep = eng.measured_reports[-1]
    # 3 fetched copies on home 0 + 3 staged canonical copies at holders
    assert rep.pool_entries == 6, rep.pool_entries
    assert rep.pool_bytes > 0, rep.pool_bytes
    assert ("doc0", 0) in backend._pool
    eng.store.evict_replica("doc0", 0)
    assert ("doc0", 0) not in backend._pool, "evict listener did not fire"
    assert len(backend._pool) == 5
    eng.fail_instance(0)                   # drop_holder retires the rest
    assert not any(inst == 0 for _, inst in backend._pool), backend._pool
    # the surviving canonical holders keep their committed copies
    assert len(backend._pool) == 3, backend._pool
    print("  pool retirement: evict_replica + fail_instance both drain "
          "the committed-copy pool")


def test_selection_scenario():
    eng_a = run_engine(*selection_scenario(
        backend=AnalyticBackend(), selector=IndexerService()))
    eng_r = run_engine(*selection_scenario(
        backend=JaxExecBackend(), selector=IndexerService()))
    eng_m = run_engine(*selection_scenario(
        backend=ShardMapExecBackend(), selector=ShardMapIndexerService()))
    # mesh indexer == host indexer, verdict for verdict
    assert eng_m.selector.log.keys() == eng_r.selector.log.keys()
    for step, verd in eng_r.selector.log.items():
        mverd = eng_m.selector.log[step]
        assert verd.keys() == mverd.keys(), step
        for rid in verd:
            assert verd[rid].blocks == mverd[rid].blocks, (step, rid)
    # identical selections -> identical plans -> StepStats parity
    assert [stats_dict(s) for s in eng_a.stats] \
        == [stats_dict(s) for s in eng_m.stats]
    _, steps = selection_scenario()
    for reqs, st in zip(steps, eng_m.stats):
        err = max_oracle_err(eng_m, reqs, st.step)
        assert err <= TOL, ("selection", st.step, err)
    check_measured(eng_m, "selection")
    assert any(dt > 0.0 for dt in eng_m.selector.measured_index_s.values())
    print("  selection: mesh indexer verdict parity + selection oracle "
          "exact")


def test_fanout_group():
    """One dispatch group whose requesters span THREE homes: the fanout
    (all_gather / all_to_all) route schedule, not the pairwise one."""
    eng = ServingEngine(8, pool_tokens=10**6, cfg=EngineConfig(),
                        instances_per_pod=8, backend=ShardMapExecBackend())
    eng.register_chunk("fan", holder=0, length=256)
    reqs = [Request(i, home=1 + i, chunk_ids=["fan"], m_q=8)
            for i in range(3)]
    eng.schedule_step(reqs)
    grp = [r for r in eng.plans[0].records
           if r.primitive == "route" and not r.backup]
    assert any(r.n_requesters == 3 for r in grp), grp
    err = max_oracle_err(eng, reqs, 1)
    assert err <= TOL, err
    print(f"  fanout group (3 homes, 1 dispatch): max|err| = {err:.2e}")


def test_dead_holder():
    """fail_instance mid-run: the promoted replica serves the next step's
    plan and the mesh execution still reproduces the oracle (exec-mode
    failover — ISSUE 7 satellite)."""
    eng, steps = SCENARIOS["fetch_heavy"](backend=ShardMapExecBackend())
    eng.schedule_step(steps[0])          # replicas persist on home 0
    eng.fail_instance(1)                 # doc0's canonical holder dies
    reqs = [Request(7, home=3, chunk_ids=["doc0"], m_q=4)]
    eng.schedule_step(reqs)
    err = max_oracle_err(eng, reqs, eng.stats[-1].step)
    assert err <= TOL, err
    print(f"  dead holder -> promoted replica: max|err| = {err:.2e}")


def test_flight_recorder():
    """ISSUE 9 on the real mesh: the tracer renders planned AND measured
    track groups from one run, the registry picks up the exec-side series
    (phase walls, stage_fills, pool occupancy), and the drift monitor
    folds every MeasuredReport. Forced host devices run 10-5000x slower
    than the fabric model, so drift MUST trip at the calibrated 7% — we
    assert the trip (the monitor is loud where it should be) instead of
    pretending the fit holds here."""
    from repro.obs import DriftConfig, DriftError, DriftMonitor, Obs, Tracer
    from repro.obs.trace import PID_MEASURED, PID_PLANNED, validate_trace

    eng, steps = SCENARIOS["mixed_congested"](backend=ShardMapExecBackend())
    obs = Obs(tracer=Tracer(), drift=DriftMonitor(DriftConfig(
        threshold=0.07, min_samples=1)))
    eng.obs = obs
    obs.bind_engine(eng)
    run_engine(eng, steps)

    doc = obs.tracer.export()
    assert validate_trace(doc) == [], validate_trace(doc)
    steps_by_pid = {
        pid: [e for e in doc["traceEvents"] if e["ph"] == "X"
              and e["pid"] == pid and e.get("cat") == "step"]
        for pid in (PID_PLANNED, PID_MEASURED)}
    assert len(steps_by_pid[PID_PLANNED]) == len(steps), doc
    assert len(steps_by_pid[PID_MEASURED]) == len(steps), \
        "measured track group missing — MeasuredReports not traced"

    snap = obs.metrics.snapshot()
    assert obs.metrics.counter_value("exec.stage_fills") == 0.0
    # the phase gauges are the phase timer's totals over the run
    for phase in ("stack", "dispatch", "barrier", "merge"):
        assert all(s.phase_n[phase] == 1 for s in eng.stats), phase
        assert math.isclose(
            snap["gauges"][f"exec.phase_wall_s{{phase={phase}}}"],
            sum(s.phase_s[phase] for s in eng.stats)), phase
    assert snap["histograms"]["exec.measured_ratio"]["count"] == len(steps)
    assert obs.drift.n_reports == len(steps)
    assert obs.drift.n_unmatched == 0
    try:
        obs.drift.check()
        raise AssertionError("host-device walls inside 7% of the model?!")
    except DriftError as e:
        assert "ewma" in str(e)
    print(f"  flight recorder: planned+measured track groups, "
          f"{len(snap['counters'])} counters, drift loud on host devices "
          f"(worst cell |ewma| {max(abs(s.ewma) for s in obs.drift.cells.values()):.0f})")


def test_shape_validation():
    # per-requester route shard mismatch names the shard and both shapes
    q = jnp.zeros((4, 2, 24))
    ckv = jnp.zeros((64, 16))            # wrong d_qk
    try:
        check_route_shards("instance", q, ckv, shard=3)
        raise AssertionError("ragged route shard was accepted")
    except ValueError as e:
        msg = str(e)
        assert "shard 3" in msg and "24" in msg and "16" in msg, msg
    # ragged per-instance assembly names the shard and both shapes
    try:
        check_instance_shards({0: np.zeros((8, 4)), 2: np.zeros((7, 4))},
                              (8, 4), 8)
        raise AssertionError("ragged instance shard was accepted")
    except ValueError as e:
        msg = str(e)
        assert "shard 2" in msg and "(7, 4)" in msg and "(8, 4)" in msg, msg
    # a valid mask that disagrees with the cache raises the NAMED error at
    # trace time, not an opaque XLA lowering failure
    backend = ShardMapExecBackend()
    eng = ServingEngine(4, pool_tokens=10**6, backend=backend)
    eng.register_chunk("v", holder=1, length=64)
    eng.schedule_step([Request(0, home=0, chunk_ids=["v"], m_q=2)])
    try:
        check_route_shards("instance", jnp.zeros((2, 2, 24)),
                           jnp.zeros((64, 24)), jnp.zeros(63, bool))
        raise AssertionError("ragged valid mask was accepted")
    except ValueError as e:
        assert "disagree" in str(e), e
    print("  shape validation: named-shard ValueErrors up front")


if __name__ == "__main__":
    test_dense_scenarios()
    test_randomized_ab()
    test_pipelined_ab()
    test_pool_retirement()
    test_selection_scenario()
    test_fanout_group()
    test_dead_holder()
    test_flight_recorder()
    test_shape_validation()
    print("SHARD-MAP-EXEC-OK")
