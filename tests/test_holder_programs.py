"""The exec backend's holder partials as compiled programs.

* ROUTE GROUPS — a dispatch of 1–6 members with mixed m_q, stacked into
  a zero-padded buffer of route_bucket(rows) rows, reproduces
  single-instance attention for every member.
* MASKED ACCESSES — a selection route and a resident access through the
  indexer's mask reproduce the selection oracle.
* BOUNDED PROGRAMS — over a long agent-fleet-like trace the backend's
  `partial_programs` count stops growing once every bucket and m_q has
  been seen, and the obs registry publishes it.
"""

import numpy as np
import pytest

from repro.obs import Obs
from repro.serving.backends import JaxExecBackend, TINY_MLA
from repro.serving.backends.jax_exec import (oracle_partial,
                                             oracle_tolerance, route_bucket,
                                             selection_oracle_partial)
from repro.serving.engine import EngineConfig, Request, ServingEngine
from repro.serving.selection import IndexerService, SelectionConfig
from repro.serving.workload import (WorkloadConfig, agentic_trace,
                                    materialize_trace, register_corpus)

TOL = oracle_tolerance(np.float32)


def _assert_matches(got, want):
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=0, atol=TOL)


def _holder_rows(backend, routed):
    """Query rows of every holder-partial program the backend built."""
    return {k[2][0] for k in backend._program_keys
            if k[0] == "holder_partial" and k[1] is routed}


@pytest.mark.parametrize("rows, bucket", [(1, 16), (2, 16), (16, 16),
                                          (17, 32), (33, 64), (96, 128),
                                          (128, 128)])
def test_route_bucket_is_a_power_of_two_of_at_least_16(rows, bucket):
    assert route_bucket(rows) == bucket


@pytest.mark.parametrize("m_qs", [(1,), (16,), (1, 1), (4, 8, 1),
                                  (16, 16, 1), (1, 4, 8, 16, 16, 8),
                                  (16,) * 6],
                         ids=lambda m: "-".join(map(str, m)))
def test_route_group_matches_oracle(m_qs):
    """One planned ROUTE dispatch of len(m_qs) members on one holder: each
    member's output equals attention over the chunk, padded rows or not."""
    backend = JaxExecBackend()
    eng = ServingEngine(8, pool_tokens=10**6, instances_per_pod=8,
                        backend=backend)
    eng.register_chunk("doc", holder=1, length=64)
    reqs = [Request(i, home=2 + i, chunk_ids=["doc"], m_q=m)
            for i, m in enumerate(m_qs)]
    recs = eng.schedule_step(reqs)
    assert [(r.primitive, len(r.req_ids)) for r in recs] \
        == [("route", len(m_qs))]
    outs = eng.outputs_of(1)
    for rq in reqs:
        got = outs[rq.req_id]
        assert got.o.shape == (rq.m_q, TINY_MLA.n_heads,
                               TINY_MLA.kv_lora_rank)
        _assert_matches(got, oracle_partial(TINY_MLA, eng.store, rq, 1))
    rows = (m_qs[0] if len(m_qs) == 1 else route_bucket(sum(m_qs)))
    assert _holder_rows(backend, routed=True) == {rows}


def test_masked_route_and_resident_match_selection_oracle():
    """Under an active selection the routed chunk and the resident chunk
    both attend through the indexer's mask."""
    backend = JaxExecBackend()
    eng = ServingEngine(2, pool_tokens=10**6, backend=backend,
                        selector=IndexerService(
                            SelectionConfig(block_tokens=16)))
    eng.register_chunk("doc", holder=1, length=64)
    eng.register_chunk("own", holder=0, length=64)
    rq = Request(0, home=0, chunk_ids=["doc", "own"], m_q=4, k_selected=48)
    recs = eng.schedule_step([rq])
    plan = eng.plans[-1]
    assert [r.primitive for r in recs] == ["route"]
    assert [p.chunk_id for p in plan.resident_pairs] == ["own"]
    sel = plan.selections[rq.req_id]
    assert all(0 < m.sum() < 64 for m in sel.masks.values())
    assert {k[1] for k in backend._program_keys
            if k[0] == "holder_partial" and k[-1]} == {True, False}
    _assert_matches(eng.outputs_of(1)[rq.req_id],
                    selection_oracle_partial(TINY_MLA, eng.store, rq, sel,
                                             1))


def test_partial_programs_stay_bounded_over_a_long_trace():
    """200 steps of agent-fleet-shaped traffic (16 sessions, 2 Zipf chunks
    a request, m_q from {1, 4, 8, 16}): the backend builds programs for
    each m_q and route bucket once, far fewer than the distinct route
    group compositions, and the obs gauge reports the count."""
    wl = WorkloadConfig(n_steps=200, agents=16, n_corpus_chunks=64,
                        chunk_tokens=32, selection_frac=0.0, seed=3)
    backend = JaxExecBackend()
    obs = Obs()
    eng = ServingEngine(4, pool_tokens=16 * 32, instances_per_pod=4,
                        cfg=EngineConfig(), backend=backend, obs=obs)
    steps = materialize_trace(agentic_trace(
        wl, eng, register_corpus(eng, wl)))
    counts, groups = [], set()
    for reqs in steps:
        eng.schedule_step(reqs)
        counts.append(backend.partial_programs)
        m_q = {r.req_id: r.m_q for r in reqs}
        groups |= {tuple(m_q[i] for i in rec.req_ids)
                   for rec in eng.plans[-1].records
                   if rec.primitive == "route"}
    choices, buckets = len(wl.m_q_choices), 4           # 16, 32, 64, 128
    # holder programs: routed or not, per m_q or bucket; stack and take
    # per (bucket, m_q)
    assert counts[-1] <= 2 * (choices + buckets) + 2 * buckets * choices
    assert counts[-1] < len(groups)
    assert counts[99] == counts[-1]                     # flat after warm-up
    # two 1-row members ran in the 16-row bucket
    assert ("stack_query", 16, 1) in backend._program_keys
    snap = obs.metrics.snapshot()["gauges"]
    assert snap["exec.partial_programs"] == counts[-1]
