"""The flight recorder's organs (ISSUE 9): metrics registry semantics,
drift-monitor verdicts (threshold trip / EWMA decay / per-stage keying /
the injected mis-calibrated fabric table), and the two engine contracts —
observability NEVER changes planner behavior, and a disabled recorder
costs (near) nothing on the step path."""

import dataclasses
import math

import pytest

from engine_scenarios import SCENARIOS
from repro.obs import (NULL_OBS, DriftConfig, DriftError, DriftMonitor,
                       Obs, Tracer)
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.serving import timeline as TL


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_counter_gauge_labels(self):
        m = MetricsRegistry()
        m.counter("x", fabric="ici").inc()
        m.counter("x", fabric="ici").inc(2.5)
        m.counter("x", fabric="dcn").inc()
        m.gauge("g", i=0).set(7)
        m.gauge("g", i=0).set(3)          # last write wins
        snap = m.snapshot()
        assert snap["counters"]["x{fabric=ici}"] == 3.5
        assert snap["counters"]["x{fabric=dcn}"] == 1.0
        assert snap["gauges"]["g{i=0}"] == 3.0

    def test_label_order_is_canonical(self):
        m = MetricsRegistry()
        assert m.counter("y", b=1, a=2) is m.counter("y", a=2, b=1)

    def test_interned_reference_is_live(self):
        m = MetricsRegistry()
        c = m.counter("hot")
        for _ in range(5):
            c.inc()
        assert m.counter_value("hot") == 5.0

    def test_histogram_streams_without_sample_storage(self):
        h = Histogram()
        n_buckets = len(h.buckets)
        for i in range(10_000):
            h.observe(1e-6 * (1 + i % 100))
        # bounded memory: the bucket array never grows
        assert len(h.buckets) == n_buckets
        s = h.summary()
        assert s["count"] == 10_000
        assert s["min"] == pytest.approx(1e-6)
        assert s["max"] == pytest.approx(1e-4)
        # log-bucket interpolation: p50 within a bucket-width of the true
        # median (~5.05e-5 for the uniform 1..100 multiplier)
        assert 2e-5 < s["p50"] < 8e-5
        assert s["p99"] <= s["max"]
        assert s["p50"] >= s["min"]

    def test_histogram_clamps_outliers(self):
        h = Histogram()
        h.observe(0.0)            # below span -> first bucket
        h.observe(1e9)            # above span -> last bucket
        s = h.summary()
        assert s["count"] == 2 and s["min"] == 0.0 and s["max"] == 1e9
        assert s["p50"] <= 1e9 and not math.isnan(s["p50"])

    def test_snapshot_deterministic(self):
        def build():
            m = MetricsRegistry()
            m.counter("b").inc(2)
            m.counter("a", z=1).inc()
            m.histogram("h").observe(0.5)
            m.gauge("g").set(1)
            return m.to_json()
        assert build() == build()


# ---------------------------------------------------------------------------
# drift monitor
# ---------------------------------------------------------------------------

KEY = ("route", 0, "transfer")


class TestDrift:
    def test_threshold_trip(self):
        d = DriftMonitor(DriftConfig(threshold=0.07, min_samples=3))
        for _ in range(4):
            d.observe_residual(KEY, 0.5)
        assert [k for k, _ in d.tripped()] == [KEY]
        with pytest.raises(DriftError, match="route/f0/transfer"):
            d.check()

    def test_min_samples_gate(self):
        d = DriftMonitor(DriftConfig(threshold=0.07, min_samples=3))
        d.observe_residual(KEY, 5.0)
        d.observe_residual(KEY, 5.0)
        assert d.tripped() == []          # loud but not yet conclusive
        d.observe_residual(KEY, 5.0)
        assert d.tripped() != []

    def test_ewma_decay(self):
        cfg = DriftConfig(threshold=0.07, alpha=0.25, min_samples=1)
        d = DriftMonitor(cfg)
        d.observe_residual(KEY, 1.0)      # transient spike
        assert d.tripped() != []
        ew = 1.0
        for _ in range(20):               # calibration healthy again
            d.observe_residual(KEY, 0.0)
            ew *= (1 - cfg.alpha)
            assert d.cells[KEY].ewma == pytest.approx(ew)
        assert d.tripped() == []          # the spike decayed out
        assert d.cells[KEY].worst == 1.0  # ... but stays on record

    def test_per_stage_keying(self):
        d = DriftMonitor(DriftConfig(threshold=0.07, min_samples=2))
        other = ("route", 0, "probe")
        cross = ("route", 1, "transfer")
        for _ in range(3):
            d.observe_residual(KEY, 0.9)
            d.observe_residual(other, 0.01)
            d.observe_residual(cross, -0.01)
        tripped = dict(d.tripped())
        assert KEY in tripped
        assert other not in tripped and cross not in tripped

    def test_negative_drift_trips_too(self):
        d = DriftMonitor(DriftConfig(threshold=0.07, min_samples=2))
        for _ in range(3):
            d.observe_residual(KEY, -0.2)  # model OVERprices: still drift
        assert [k for k, _ in d.tripped()] == [KEY]

    def test_injected_miscalibrated_fabric_table(self):
        """The acceptance scenario: a fabric table whose bandwidth fit
        rotted by 2.5x inflates every wire-stage wall by 2.5x relative to
        the model. Feeding those measured flows through the monitor must
        trip exactly the wire-stage cells, while compute/merge cells
        (whose calibration did not change) stay inside the envelope."""
        eng, steps = SCENARIOS["mixed_congested"]()
        reports = []
        for reqs in steps:
            eng.schedule_step(reqs)
            analytic = eng.timelines[-1]
            measured_flows = []
            for f in analytic.flows:
                stages = tuple(
                    dataclasses.replace(
                        s, duration_s=s.duration_s
                        * (2.5 if s.name in TL.WIRE_STAGES else 1.0))
                    for s in f.stages)
                measured_flows.append(dataclasses.replace(f, stages=stages))
            reports.append(TL.measured_vs_analytic(
                eng.step_idx, analytic, measured_flows))
        d = DriftMonitor(DriftConfig(threshold=0.07, min_samples=1))
        for rep in reports:
            assert d.observe_report(rep) > 0
        tripped = dict(d.tripped())
        assert tripped, "mis-calibrated wire constants must trip"
        wire_cells = [k for k in tripped if k[2] in TL.WIRE_STAGES]
        assert wire_cells, f"expected wire-stage cells, got {tripped}"
        # attribution: untouched (non-wire) stage cells stay healthy
        assert all(k[2] in TL.WIRE_STAGES for k in tripped), tripped
        # the injected 150% inflation is what the EWMA converged to
        for k in wire_cells:
            assert d.cells[k].ewma == pytest.approx(1.5, abs=1e-9)
        with pytest.raises(DriftError):
            d.check()

    def test_healthy_report_does_not_trip(self):
        """measured == analytic (residual 0 everywhere): silence."""
        eng, steps = SCENARIOS["routed_only"]()
        d = DriftMonitor(DriftConfig(threshold=0.07, min_samples=1))
        for reqs in steps:
            eng.schedule_step(reqs)
            analytic = eng.timelines[-1]
            d.observe_report(TL.measured_vs_analytic(
                eng.step_idx, analytic, list(analytic.flows)))
        assert d.n_residuals > 0
        assert d.tripped() == []
        d.check()                          # must not raise


# ---------------------------------------------------------------------------
# engine contracts: no behavior change, (near-)zero disabled cost
# ---------------------------------------------------------------------------


def _stats_signature(eng):
    """Everything in StepStats except the host-clock fields."""
    return [s.comparable() for s in eng.stats]


class TestEngineContracts:
    def test_default_engine_uses_null_obs(self):
        eng, _ = SCENARIOS["routed_only"]()
        assert eng.obs is NULL_OBS
        assert NULL_OBS.enabled is False

    def test_obs_never_changes_decisions(self):
        """Active tracer+metrics+drift: StepStats, records, and residency
        stay bit-identical to the bare engine on every golden scenario."""
        for name, build in SCENARIOS.items():
            eng_a, steps = build()
            eng_b, _ = build()
            obs = Obs(tracer=Tracer(), drift=DriftMonitor())
            eng_b.obs = obs
            obs.bind_engine(eng_b)
            for reqs in steps:
                ra = eng_a.schedule_step(reqs)
                rb = eng_b.schedule_step(reqs)
                assert ra == rb, name
            assert _stats_signature(eng_a) == _stats_signature(eng_b), name
            assert obs.metrics.counter_value("engine.steps") == len(steps)

    def test_disabled_recorder_near_zero_overhead(self):
        """The hot-path guarantee: with observability off the step path
        pays one identity check. We pin the mechanism (default obs IS the
        inert singleton, planner caches count via plain ints) and bound
        the wall-clock ratio generously — the binding perf gate is the CI
        planner-bench floor, which runs the 128x64 workload."""
        build = SCENARIOS["routed_only"]
        import time

        def run(with_obs):
            eng, steps = build()
            if with_obs:
                obs = Obs(tracer=Tracer(), drift=DriftMonitor())
                eng.obs = obs
                obs.bind_engine(eng)
            t0 = time.perf_counter()
            for _ in range(30):
                for reqs in steps:
                    eng.schedule_step(reqs)
            return time.perf_counter() - t0, eng

        base_t, base_eng = run(False)
        obs_t, obs_eng = run(True)
        assert base_eng.obs is NULL_OBS
        # planner cache counters run unconditionally and agree
        assert (base_eng.planner_cache_stats()
                == obs_eng.planner_cache_stats())
        # sched_wall (plan+execute, obs excluded by construction) within
        # noise; the enabled run's EXTRA work lives outside that window
        base_wall = sum(s.sched_wall_s for s in base_eng.stats)
        obs_wall = sum(s.sched_wall_s for s in obs_eng.stats)
        assert obs_wall < base_wall * 3 + 0.05, (base_wall, obs_wall)

    def test_on_step_publishes_registry(self):
        eng, steps = SCENARIOS["mixed_congested"]()
        obs = Obs()
        eng.obs = obs
        obs.bind_engine(eng)
        for reqs in steps:
            eng.schedule_step(reqs)
        m = obs.metrics
        snap = m.snapshot()
        # decisions by verdict: all three primitives appear in the mix
        assert m.counter_value("engine.dispatches", primitive="route") > 0
        assert m.counter_value("engine.dispatches", primitive="local") > 0
        # bytes by fabric flow onto the wire counters
        assert any(k.startswith("engine.wire_bytes{")
                   for k in snap["counters"])
        # the §8 congested link (K=4 on holder 1) is visible
        assert m.counter_value("engine.congested_links") > 0
        # planner cache + schedule memo gauges published
        assert "planner.cache.sig_hit" in snap["gauges"]
        assert "planner.sim_memo.miss" in snap["gauges"]
        # store occupancy gauges per instance
        assert "store.pool_used_tokens{instance=0}" in snap["gauges"]

    def test_store_churn_counters_via_listener(self):
        eng, steps = SCENARIOS["fetch_heavy"]()
        obs = Obs()
        eng.obs = obs
        obs.bind_engine(eng)
        for reqs in steps:
            eng.schedule_step(reqs)
        # force churn: evict a fetched replica, then kill its holder
        evicted_before = sum(
            v for k, v in obs.metrics.snapshot()["counters"].items()
            if k.startswith("store.copy_retirements"))
        replicated = [cid for cid in ("doc0", "doc1", "doc2")
                      if len(eng.store.holders_of(cid)) > 1]
        assert replicated, "fetch_heavy must have spawned replicas"
        cid = replicated[0]
        extra = [h for h in eng.store.holders_of(cid)
                 if h != eng.store.lookup(cid).holder][0]
        eng.store.evict_replica(cid, extra)
        after = sum(
            v for k, v in obs.metrics.snapshot()["counters"].items()
            if k.startswith("store.copy_retirements"))
        assert after == evicted_before + 1
