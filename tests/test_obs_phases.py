"""The served step's phase timer (repro.obs.phases): self time under
nesting, per-step buckets the engine opens and closes, what the exec
backend records in StepStats.phase_s / phase_n, the profiler spans on
the capture's clock, and a planner-only process that stays JAX-free."""

import collections
import glob
import os
import pathlib
import subprocess
import sys
import warnings

import pytest

from repro.obs import phases
from repro.obs.phases import PhaseTimer
from repro.serving.backends import AnalyticBackend, JaxExecBackend
from repro.serving.backends.base import StepTicket
from repro.serving.engine import EngineConfig

from engine_scenarios import SCENARIOS

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


class FakeClock:
    """A clock that moves only when told to."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_self_time_excludes_nested_phases():
    clock = FakeClock()
    t = PhaseTimer(clock)
    t.open_step(1)
    with t.phase("route", 1):
        clock.t += 2.0
        with t.phase("query", 1):
            clock.t += 3.0
        clock.t += 1.0
        with t.phase("query", 1):
            clock.t += 0.5
            with t.phase("merge", 1):
                clock.t += 0.25
    secs, counts = t.close_step(1)
    assert secs == {"route": 3.0, "query": 3.5, "merge": 0.25}
    assert counts == {"route": 1, "query": 2, "merge": 1}
    assert sum(secs.values()) == clock.t              # the route's wall


def test_steps_interleaved_keep_their_own_buckets():
    clock = FakeClock()
    t = PhaseTimer(clock)
    t.open_step(1)
    with t.phase("stack", 1):
        clock.t += 1.0
    t.open_step(2)
    with t.phase("stack", 2):
        clock.t += 2.0
        with t.phase("query", 1):                    # step 1 inside step 2
            clock.t += 4.0
    with t.phase("barrier", 1):
        clock.t += 8.0
    assert t.close_step(1) == ({"stack": 1.0, "query": 4.0,
                                "barrier": 8.0},
                               {"stack": 1, "query": 1, "barrier": 1})
    with t.phase("merge", 2):
        clock.t += 16.0
    assert t.close_step(2) == ({"stack": 2.0, "merge": 16.0},
                               {"stack": 1, "merge": 1})


def test_a_phase_without_an_open_bucket_counts_nowhere():
    clock = FakeClock()
    t = PhaseTimer(clock)
    with t.phase("merge", 3):
        clock.t += 1.0
    assert t.close_step(3) == ({}, {})
    with t.phase("merge", 3):
        clock.t += 1.0
    t.open_step(3)
    with t.phase("merge", 3):
        clock.t += 2.0
    assert t.close_step(3) == ({"merge": 2.0}, {"merge": 1})
    assert t.close_step(3) == ({}, {})               # popped
    with t.span("submit", 3):                        # a span times nothing
        clock.t += 4.0
    assert t.close_step(3) == ({}, {})


def test_a_phase_left_by_an_exception_leaves_the_stack_clean():
    clock = FakeClock()
    t = PhaseTimer(clock)
    t.open_step(1)
    with pytest.raises(ValueError):
        with t.phase("route", 1):
            clock.t += 1.0
            raise ValueError
    with t.phase("merge", 1):
        clock.t += 2.0
    assert t.close_step(1) == ({"route": 1.0, "merge": 2.0},
                               {"route": 1, "merge": 1})


def _expected_counts(stats, plan):
    """What the exec backend opens for one step: a phase per resident
    pair, per primary dispatch and per request's merge."""
    want = collections.Counter(
        "fetch" if r.primitive == "fetch_replica" else r.primitive
        for r in plan.records if not r.backup and r.req_ids)
    want["resident"] = len(plan.resident_pairs)
    want["merge"] = stats.n_requests
    return {k: v for k, v in want.items() if v}


def test_exec_backend_counts_each_piece_of_the_step():
    eng, steps = SCENARIOS["mixed_congested"](backend=JaxExecBackend())
    for reqs in steps:
        eng.schedule_step(reqs)
    for st, plan in zip(eng.stats, eng.plans):
        n = dict(st.phase_n)
        assert n.pop("query") >= 1
        assert n == _expected_counts(st, plan), st.step
        assert set(st.phase_s) == set(st.phase_n)
        assert all(v >= 0.0 for v in st.phase_s.values())
        assert sum(st.phase_s.values()) <= st.sched_wall_s


def test_backend_driven_without_engine_adds_nothing_to_an_engine():
    """As the benchmark's rehearsal does: a throwaway engine plans each
    step and the backend is submitted to directly, for the step numbers
    the served engine then uses on the same backend."""
    backend = JaxExecBackend()
    scratch, steps = SCENARIOS["mixed_congested"](backend=backend)
    for reqs in steps:
        plan = scratch.plan_step(reqs)
        backend.submit(scratch, plan)
    eng, steps = SCENARIOS["mixed_congested"](backend=backend)
    for reqs in steps:
        eng.schedule_step(reqs)
    for st, plan in zip(eng.stats, eng.plans):
        n = dict(st.phase_n)
        n.pop("query")
        assert n == _expected_counts(st, plan), st.step


class SplitBackend(AnalyticBackend):
    """An analytic backend whose work is split over submit and await, as
    the shard_map backend's is, so that steps interleave at depth 2."""

    def submit(self, engine, plan):
        with phases.phase("stack", plan.step):
            pass
        return StepTicket(plan=plan)

    def await_result(self, engine, ticket):
        with phases.phase("merge", ticket.plan.step):
            return self.execute(engine, ticket.plan)


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_steps_in_flight_do_not_mix(depth):
    eng, steps = SCENARIOS["routed_only"](
        backend=SplitBackend(), cfg=EngineConfig(pipeline_depth=depth))
    eng.run(steps)
    assert [s.phase_n for s in eng.stats] \
        == [{"stack": 1, "merge": 1}] * len(steps)


def _host_events(path):
    """(name, start_ns, end_ns, step) of every repro.* event."""
    from jax.profiler import ProfileData
    out = []
    # the profiler's stats type warns on its first use
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(phases.PREFIX):
                        out.append((e.name, e.start_ns, e.end_ns,
                                    dict(e.stats).get("step")))
    return out


def test_phases_are_spans_of_a_profiler_capture(tmp_path):
    import jax
    backend = JaxExecBackend()
    warm, steps = SCENARIOS["mixed_congested"](backend=backend)
    warm.run(steps)                                  # compile outside
    eng, steps = SCENARIOS["mixed_congested"](backend=backend)
    with jax.profiler.trace(str(tmp_path)):
        eng.run(steps)
    found = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    assert len(found) == 1
    events = _host_events(found[0])

    submits = {step: (s, e) for name, s, e, step in events
               if name == "repro.submit"}
    assert sorted(submits) == [st.step for st in eng.stats]
    assert sorted(step for name, _, _, step in events
                  if name == "repro.await") == sorted(submits)
    seen = collections.defaultdict(collections.Counter)
    for name, s, e, step in events:
        if name in ("repro.submit", "repro.await"):
            continue
        lo, hi = submits[step]                       # the right step's
        assert lo <= s <= e <= hi, (name, step)
        seen[step][name[len(phases.PREFIX):]] += 1
    for st in eng.stats:
        assert seen[st.step]["merge"] == st.n_requests
        assert dict(seen[st.step]) == st.phase_n


_PLANNER_ONLY = """
import sys
if {block}:
    sys.modules["jax"] = None          # JAX absent: importing it raises
from repro.obs import phases
from repro.serving.engine import Request, ServingEngine
eng = ServingEngine(4, pool_tokens=10**5, instances_per_pod=2)
for i in range(4):
    eng.register_chunk(f"c{{i}}", holder=i, length=256)
for step in range(3):
    eng.schedule_step([Request(r, home=r % 4, chunk_ids=["c0", "c3"],
                               m_q=4) for r in range(5)])
phases.open_step(99)
with phases.span("submit", 99):
    with phases.phase("merge", 99, req=1):
        pass
secs, counts = phases.close_step(99)
assert counts == {{"merge": 1}}, counts
assert len(eng.stats) == 3 and eng.stats[-1].phase_n == {{}}
assert [m for m in sys.modules
        if m == "jax" or m.startswith("jax.")] == ({block} and ["jax"]
                                                    or []), "jax imported"
print("PLANNER-ONLY-OK")
"""


@pytest.mark.parametrize("block", [True, False],
                         ids=["jax-absent", "jax-unimported"])
def test_planner_only_process_stays_jax_free(block):
    env = dict(os.environ, PYTHONPATH=str(SRC))
    out = subprocess.run(
        [sys.executable, "-c", _PLANNER_ONLY.format(block=block)],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "PLANNER-ONLY-OK" in out.stdout
