"""ISSUE 6: the columnar plan -> timeline path is an exact re-expression
of the object path, not an approximation. Three layers of lockdown:

  * scheduler exactness — simulate_arrays() equals simulate() stage-for-
    stage (same schedule order, same start/end floats, same aggregates)
    on randomized flow sets, zero-duration stages included; negative
    durations delegate to the object oracle by contract;
  * planner A/B — EngineConfig.vectorized_plan False vs True produces
    bitwise-identical DispatchRecords and StepStats (host clocks aside)
    over every golden scenario, the selection trace, and a multi-step
    randomized workload with evictions and replica spawns;
  * round trip — StepPlanArrays.to_records()/from_records() loses
    nothing: records -> arrays -> records is the identity on the golden
    traces.

The randomized scheduler properties run under hypothesis (dev-only; that
class skips without it — requirements-dev.txt). Everything else is
deterministic and always on."""

import dataclasses

import numpy as np
import pytest

from engine_scenarios import SCENARIOS, selection_scenario
from repro.serving import plan as PL
from repro.serving import timeline as TL
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.selection import IndexerService
from repro.serving.workload import (WorkloadConfig, agentic_trace,
                                    materialize_trace, register_corpus)

# ---------------------------------------------------------------------------
# Shared drivers.
# ---------------------------------------------------------------------------


def _drive(eng, steps):
    """Run a trace and return everything the A/B compares: records (as
    tuples — bitwise, floats included), StepStats minus wall-clock, and
    the final residency map."""
    for reqs in steps:
        eng.schedule_step(reqs)
    recs = [dataclasses.astuple(r) for r in eng.log]
    stats = [s.comparable() for s in eng.stats]   # host clocks dropped
    residency = sorted(
        (cid, c.holder, tuple(sorted(c.replicas)), c.last_access)
        for cid, c in eng.store._chunks.items())
    return recs, stats, residency


def _scenario(name, vectorized):
    if name == "selection":
        eng, steps = selection_scenario(selector=IndexerService())
    else:
        eng, steps = SCENARIOS[name]()
    eng.cfg.vectorized_plan = vectorized
    return eng, steps


GOLDEN_NAMES = sorted(SCENARIOS) + ["selection"]


# ---------------------------------------------------------------------------
# Planner A/B: object oracle vs array path, bit for bit.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", GOLDEN_NAMES)
def test_ab_bit_identical_golden(name):
    a = _drive(*_scenario(name, vectorized=True))
    b = _drive(*_scenario(name, vectorized=False))
    assert a == b


def test_ab_bit_identical_workload():
    """A multi-step randomized workload — session churn, evictions,
    replica spawns, congestion — planned through both paths. The pool is
    sized below the working set on purpose so replacement runs."""
    def build(vec):
        eng = ServingEngine(8, pool_tokens=24 * 2048,
                            cfg=EngineConfig(vectorized_plan=vec),
                            instances_per_pod=4)
        w = WorkloadConfig(n_steps=24, agents=16, n_corpus_chunks=20,
                           chunk_tokens=2048, session_steps=(4, 12),
                           selection_frac=0.0, seed=7)
        cids = register_corpus(eng, w)
        steps = materialize_trace(agentic_trace(w, eng, cids))
        return eng, steps

    a = _drive(*build(True))
    b = _drive(*build(False))
    assert len(a[0]) > 0
    assert a == b


# ---------------------------------------------------------------------------
# StepPlanArrays round trip on the golden traces.
# ---------------------------------------------------------------------------


def _arrays_equal(x: PL.StepPlanArrays, y: PL.StepPlanArrays) -> None:
    assert x.step == y.step
    assert x.chunk_ids == y.chunk_ids
    for f in ("prim", "holder", "chunk", "n_requesters", "m_q_total",
              "est_cost_s", "backup", "fabric_idx", "link_instance",
              "home", "stage_off", "stage_code", "stage_dur", "req_off",
              "req_ids"):
        a, b = getattr(x, f), getattr(y, f)
        assert a.dtype == b.dtype, f
        assert np.array_equal(a, b), f


@pytest.mark.parametrize("name", GOLDEN_NAMES)
def test_round_trip_golden(name):
    """records -> StepPlanArrays -> records is the identity (bitwise: the
    dataclass == compares est_cost_s and stage floats exactly), and the
    arrays themselves survive a second columnarization."""
    eng, steps = _scenario(name, vectorized=True)
    saw_records = 0
    for reqs in steps:
        recs = eng.schedule_step(reqs)
        arr = eng.plans[-1].arrays
        assert arr is not None           # the array path planned this step
        assert recs == arr.to_records()
        rt = PL.StepPlanArrays.from_records(arr.step, recs)
        assert rt.to_records() == recs
        _arrays_equal(rt, PL.StepPlanArrays.from_records(arr.step,
                                                         rt.to_records()))
        saw_records += len(recs)
    assert saw_records > 0


# ---------------------------------------------------------------------------
# Scheduler exactness: simulate_arrays == simulate, deterministic corners.
# ---------------------------------------------------------------------------


def _assert_schedules_identical(flows):
    want = TL.simulate(flows)
    got = TL.simulate_arrays(TL.FlowArrays.from_flows(flows))
    assert isinstance(got, TL.ArrayTimeline)
    # the schedule itself: same stages, same resources, same start/end
    # floats, in the same pop order
    assert got.scheduled == want.scheduled
    assert got.makespan_s == want.makespan_s
    assert got.serial_s == want.serial_s
    # the one-pass aggregates (satellite: Timeline caches these too)
    assert got.stage_totals() == want.stage_totals()
    assert got.busy_s() == want.busy_s()
    assert got.link_flow_counts() == want.link_flow_counts()
    for f in flows:
        assert got.flow_end_s(f.key) == want.flow_end_s(f.key)
    assert got.max_flow_serial_s == want.max_flow_serial_s
    assert got.overlap_efficiency == want.overlap_efficiency


def _mk_flows(spec):
    """spec: per flow, (primitive, link or None, holder, requester,
    durations)."""
    flows = []
    for i, (prim, link, holder, req, durs) in enumerate(spec):
        names = {"route": ("probe", "transfer", "compute", "return",
                           "merge"),
                 "fetch": ("pull", "splice"),
                 "local": ("prefill",)}[prim]
        stages = tuple(zip(names, durs))
        flows.append(TL.transport_flow(
            f"{prim}#{i}", stages,
            link_res=TL.link(*link) if link else None,
            holder_sm=TL.sm(holder), requester_sm=TL.sm(req),
            primitive=prim))
    return flows


def test_exact_zero_durations():
    """Zero-duration stages (the selection regime emits them when
    sel_frac is 0) schedule identically — ties resolve by flow index in
    both schedulers."""
    flows = _mk_flows([
        ("route", (0, 0), 0, 1, (0.0, 0.0, 0.0, 0.0, 0.0)),
        ("route", (0, 0), 0, 2, (0.0, 1e-6, 0.0, 1e-6, 0.0)),
        ("fetch", (0, 1), 0, 1, (0.0, 0.0)),
        ("local", None, 1, 1, (0.0,)),
    ])
    _assert_schedules_identical(flows)


def test_exact_contended_link():
    """Several flows queueing on one link: starts serialize in index
    order, exactly as the object scan does."""
    flows = _mk_flows([
        ("route", (1, 0), 1, i, (1e-6, 5e-6, 2e-6, 5e-6, 1e-6))
        for i in range(4)
    ] + [("fetch", (1, 0), 1, 0, (8e-6, 3e-6))])
    _assert_schedules_identical(flows)


def test_negative_durations_delegate_to_oracle():
    """Negative durations break the heap's monotonicity argument; the
    array scheduler hands that never-emitted corner to simulate()."""
    flows = _mk_flows([("fetch", (0, 0), 0, 1, (-1e-6, 1e-6))])
    out = TL.simulate_arrays(TL.FlowArrays.from_flows(flows))
    assert isinstance(out, TL.Timeline)


def test_empty_flow_set():
    _assert_schedules_identical([])


# ---------------------------------------------------------------------------
# Randomized scheduler equality (hypothesis, dev-only).
# ---------------------------------------------------------------------------


try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                      # pragma: no cover - dev-only dep
    st = None

if st is not None:
    durations = st.one_of(
        st.just(0.0),
        st.floats(min_value=1e-9, max_value=1e-2,
                  allow_nan=False, allow_infinity=False))

    @st.composite
    def flow_sets(draw):
        n = draw(st.integers(min_value=0, max_value=10))
        spec = []
        for _ in range(n):
            prim = draw(st.sampled_from(["route", "fetch", "local"]))
            n_stages = {"route": 5, "fetch": 2, "local": 1}[prim]
            durs = tuple(draw(durations) for _ in range(n_stages))
            link = (None if prim == "local"
                    else (draw(st.integers(0, 2)),
                          draw(st.integers(0, 1))))
            spec.append((prim, link,
                         draw(st.integers(0, 3)), draw(st.integers(0, 3)),
                         durs))
        return _mk_flows(spec)

    @given(flow_sets())
    @settings(max_examples=200, deadline=None)
    def test_simulate_arrays_equals_simulate(flows):
        _assert_schedules_identical(flows)
else:
    @pytest.mark.skip(
        reason="property tests need hypothesis (requirements-dev.txt)")
    def test_simulate_arrays_equals_simulate():
        pass
