"""The distributed indexer subsystem (ISSUE 4): score -> select ->
scatter-attend through the scheduler.

* DISTRIBUTED == GLOBAL — per-holder local top-k + requester merge equals
  the single-instance ranking of every block (the §5.4 claim that the
  distributed selection is exact, not approximate).
* SELECTION EXACTNESS — JaxExecBackend selection-regime decode reproduces
  single-instance selection_k attention (the DSA path of models/model.py)
  to float round-off, for every primitive the planner picks.
* REPLAY PARITY — AnalyticBackend StepStats are bit-identical between a
  plan built with live indexer masks and the same plan replayed from a
  recorded selection trace (the acceptance criterion).
* GOLDEN TRACE — the frozen selection scenario's verdicts and StepStats
  are pinned to tests/fixtures/selection_trace.json.

Regenerate the fixture after an INTENTIONAL model change:

    PYTHONPATH=src python tests/test_selection_service.py
"""

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from engine_scenarios import selection_scenario
from repro.core import cost_model as cm
from repro.core import constants as C
from repro.serving import timeline as TL
from repro.serving.backends import JaxExecBackend, TINY_MLA
from repro.serving.backends.jax_exec import (max_oracle_err, oracle_partial,
                                             query_for,
                                             selection_oracle_partial)
from repro.serving.engine import EngineConfig, Request, ServingEngine
from repro.serving.selection import (IndexerService, ReplaySelector,
                                     SelectionConfig, save_selection_trace,
                                     selection_trace_payload)
from repro.models.mla import absorbed_partial

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "selection_trace.json"
RTOL, ATOL = 2e-5, 1e-6
REL_TOL = 1e-9

# StepStats fields that are deterministic closed forms (wall-clock stays
# out, as in the engine goldens)
STAT_FIELDS = ("step", "n_requests", "n_pairs", "n_priced", "n_resident",
               "n_dispatches", "primitives", "latency_s", "max_dispatch_s",
               "serial_stage_s", "stage_totals", "n_selected",
               "selection_fallbacks", "replicas_spawned", "evictions")


def _run(backend=None, selector=None):
    eng, steps = selection_scenario(backend, selector)
    for reqs in steps:
        eng.schedule_step(reqs)
    return eng, steps


def _stat_dict(s):
    return {f: getattr(s, f) for f in STAT_FIELDS}


# ---------------------------------------------------------------------------
# Distributed top-k == global top-k.
# ---------------------------------------------------------------------------

class TestDistributedTopk:
    def test_select_equals_global_on_scenario(self):
        svc = IndexerService()
        eng, steps = selection_scenario(selector=svc)
        for step_no, reqs in enumerate(steps, start=1):
            for rq in reqs:
                if rq.k_selected is None:
                    continue
                dist = svc.select_request(eng.store, rq, step_no)
                glob = svc.global_select(eng.store, rq, step_no)
                assert dist.blocks == glob.blocks, (step_no, rq.req_id)
                for cid in rq.chunk_ids:
                    np.testing.assert_array_equal(dist.masks[cid],
                                                  glob.masks[cid])

    def test_budget_rounds_up_to_blocks(self):
        """k_selected=96 at 64-token blocks selects ceil(96/64)=2 blocks
        (NSA granularity rounds the token budget up), and a partial tail
        block is selectable (the topk_blocks bugfix)."""
        svc = IndexerService()
        eng, _ = selection_scenario(selector=svc)
        rq = Request(1, home=0, chunk_ids=["sel2"], m_q=1, k_selected=96)
        sel = svc.select_request(eng.store, rq, 1)
        # sel2 is 160 tokens = blocks of 64, 64, 32 — all three addressable
        assert sum(len(b) for b in sel.blocks.values()) == 2
        assert sel.masks["sel2"].shape == (160,)
        assert all(b in (0, 1, 2) for b in sel.blocks["sel2"])


# ---------------------------------------------------------------------------
# Exec exactness: scheduler scatter-attend == single-instance selection_k.
# ---------------------------------------------------------------------------

class TestSelectionExactness:
    def test_exec_matches_selection_oracle(self):
        """Every step of the frozen selection trace: selection requests
        reproduce the selection_k oracle, the dense rider the dense
        oracle — end-to-end through the scheduler."""
        eng, steps = selection_scenario(JaxExecBackend(), IndexerService())
        for reqs in steps:
            eng.schedule_step(reqs)
            assert max_oracle_err(eng, reqs, eng.step_idx) < 1e-4
            # at least one request actually ran under selection
            assert eng.plans[-1].selections

    def test_matches_model_dsa_path(self):
        """block_tokens=1, m_q=1: the service degenerates to token-level
        top-k with the EXACT scoring rule of models/model.py's
        _mla_decode_cached (mean-head latent query . latent c^KV band,
        lax.top_k, attend the gathered entries) — the scheduler output
        equals that single-instance DSA decode to float round-off."""
        k = 5
        svc = IndexerService(SelectionConfig(block_tokens=1))
        eng = ServingEngine(2, pool_tokens=10**5,
                            backend=JaxExecBackend(), selector=svc)
        eng.register_chunk("doc", holder=1, length=48)
        rq = Request(0, home=0, chunk_ids=["doc"], m_q=1, k_selected=k)
        eng.schedule_step([rq])
        got = eng.outputs_of(1)[0]

        # the DSA path, verbatim on the serving cache
        mcfg = TINY_MLA
        q = query_for(mcfg, rq, 1)                        # (1, H, d_qk)
        ckv = eng.store.lookup("doc").data                # (S, d_qk)
        qi = jnp.mean(q[..., : mcfg.kv_lora_rank], axis=1)        # (1, d_c)
        scores = jnp.einsum("qc,sc->qs", qi,
                            ckv[:, : mcfg.kv_lora_rank])
        _, sel_idx = jax.lax.top_k(scores[0], k)
        sel_ckv = jnp.take(ckv, sel_idx, axis=0)
        want = absorbed_partial(mcfg, q, sel_ckv)
        np.testing.assert_allclose(got.o, want.o, rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(got.m, want.m, rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(got.l, want.l, rtol=RTOL, atol=ATOL)

    def test_fetch_selected_gathers_and_never_persists(self):
        """FETCH under selection executes as the scattered gather (selected
        entries at canonical positions, no splice) and leaves NO replica —
        a selection is re-chosen every step, there is nothing to amortise."""
        eng = ServingEngine(2, pool_tokens=10**5,
                            backend=JaxExecBackend(),
                            selector=IndexerService())
        eng.register_chunk("doc", holder=1, length=160)
        rq = Request(0, home=0, chunk_ids=["doc"], m_q=2, k_selected=96)
        plan = eng.plan_step([rq])
        assert len(plan.records) == 1 and plan.selections
        # re-express the planned dispatch as the gather path
        fetch_plan = dataclasses.replace(
            plan, records=[dataclasses.replace(plan.records[0],
                                               primitive="fetch")])
        ex = eng.backend.execute(eng, fetch_plan)
        want = selection_oracle_partial(TINY_MLA, eng.store, rq,
                                        plan.selections[0], plan.step)
        np.testing.assert_allclose(ex.outputs[0].o, want.o,
                                   rtol=RTOL, atol=ATOL)
        assert not eng.store.lookup("doc").replica_data
        assert eng.store.lookup("doc").replicas == []

    def test_empty_holder_selection_is_identity(self):
        """A holder the indexer chose nothing from still joins the fan-out;
        its masked partial is the merge identity and the merged output
        still equals the oracle (k_selected=64 over two chunks: one chunk
        necessarily gets zero blocks)."""
        eng = ServingEngine(4, pool_tokens=10**5,
                            backend=JaxExecBackend(),
                            selector=IndexerService())
        eng.register_chunk("a", holder=1, length=64)
        eng.register_chunk("b", holder=2, length=64)
        rq = Request(0, home=0, chunk_ids=["a", "b"], m_q=2, k_selected=64)
        eng.schedule_step([rq])
        sel = eng.plans[-1].selections[0]
        assert sorted(sel.kb_on(c) for c in ("a", "b")) == [0, 1]
        assert max_oracle_err(eng, [rq], 1) < 1e-4


# ---------------------------------------------------------------------------
# Analytic replay parity (acceptance criterion) + golden trace.
# ---------------------------------------------------------------------------

class TestReplayParity:
    def test_analytic_stats_bit_identical_live_vs_replay(self, tmp_path):
        svc = IndexerService()
        live, _ = _run(selector=svc)
        trace = tmp_path / "sel.json"
        save_selection_trace(trace, svc.log, svc.block_tokens, svc.d_index)

        rep, _ = _run(selector=ReplaySelector(str(trace)))
        for a, b in zip(live.stats, rep.stats):
            assert _stat_dict(a) == _stat_dict(b)       # bit-identical
        assert [(r.step, r.primitive, r.chunk_id, r.holder, r.est_cost_s,
                 r.stages, r.req_ids) for r in live.log] \
            == [(r.step, r.primitive, r.chunk_id, r.holder, r.est_cost_s,
                 r.stages, r.req_ids) for r in rep.log]

    def test_replay_rejects_world_mismatch(self, tmp_path):
        svc = IndexerService()
        _run(selector=svc)
        trace = tmp_path / "sel.json"
        save_selection_trace(trace, svc.log, svc.block_tokens, svc.d_index)
        eng, _ = selection_scenario(selector=ReplaySelector(str(trace)))
        with pytest.raises(KeyError, match="no request"):
            eng.schedule_step([Request(99, home=0, chunk_ids=["sel0"],
                                       m_q=1, k_selected=64)])

    def test_replay_rejects_unknown_chunk(self, tmp_path):
        """A chunk id the trace never recorded for a request is a
        trace/world mismatch and raises — it must NOT silently de-select
        (all-False masks would complete the run with wrong pricing)."""
        svc = IndexerService()
        _run(selector=svc)
        trace = tmp_path / "sel.json"
        save_selection_trace(trace, svc.log, svc.block_tokens, svc.d_index)
        eng, _ = selection_scenario(selector=ReplaySelector(str(trace)))
        eng.register_chunk("other", holder=1, length=64)
        with pytest.raises(KeyError, match="no entry for chunks"):
            # request 0 exists in step 1, but with different chunks
            eng.schedule_step([Request(0, home=0, chunk_ids=["other"],
                                       m_q=4, k_selected=128)])


def _golden_payload():
    svc = IndexerService()
    eng, _ = _run(selector=svc)
    payload = selection_trace_payload(
        svc.log, svc.block_tokens, svc.d_index,
        meta={"scenario": "selection_scenario"})
    payload["stats"] = [_stat_dict(s) for s in eng.stats]
    return payload


def _assert_close(got, want, path):
    if isinstance(want, float) and isinstance(got, (int, float)):
        assert got == pytest.approx(want, rel=REL_TOL), \
            f"{path}: {got} != {want}"
    elif isinstance(want, dict):
        assert isinstance(got, dict) and sorted(got) == sorted(want), \
            f"{path}: keys {sorted(got)} != {sorted(want)}"
        for k in want:
            _assert_close(got[k], want[k], f"{path}.{k}")
    elif isinstance(want, (list, tuple)):
        got = list(got)
        assert len(got) == len(want), f"{path}: {len(got)} != {len(want)}"
        for i, (g, w) in enumerate(zip(got, list(want))):
            _assert_close(g, w, f"{path}[{i}]")
    else:
        assert got == want, f"{path}: {got!r} != {want!r}"


class TestGoldenSelectionTrace:
    def test_golden(self):
        assert FIXTURE.exists(), \
            f"missing {FIXTURE}; regenerate: python {__file__}"
        want = json.loads(FIXTURE.read_text())
        got = _golden_payload()
        # the selected blocks must match EXACTLY (they are the trace)
        assert json.loads(json.dumps(got["steps"])) == want["steps"]
        _assert_close(got["stats"], want["stats"], "stats")

    def test_fixture_replays_through_planner(self):
        """The checked-in fixture IS a valid selection trace: feeding it
        back through a ReplaySelector reproduces the frozen StepStats."""
        want = json.loads(FIXTURE.read_text())
        eng, _ = _run(selector=ReplaySelector(str(FIXTURE)))
        _assert_close([_stat_dict(s) for s in eng.stats], want["stats"],
                      "replayed-stats")


# ---------------------------------------------------------------------------
# Fallback: k_selected with no selector — warn once, record always.
# ---------------------------------------------------------------------------

class TestSelectionFallback:
    def test_warns_once_and_records(self):
        eng = ServingEngine(2, pool_tokens=10**5)
        eng.register_chunk("doc", holder=1, length=2048)
        rq = Request(0, home=0, chunk_ids=["doc"], m_q=8, k_selected=512)
        with pytest.warns(RuntimeWarning, match="no selection service"):
            eng.schedule_step([rq])
        # second step: recorded again, but no second warning
        import warnings as W
        with W.catch_warnings():
            W.simplefilter("error")
            eng.schedule_step([rq])
        assert [s.selection_fallbacks for s in eng.stats] == [1, 1]
        assert all(s.n_selected == 0 for s in eng.stats)
        assert all(not p.selections for p in eng.plans)

    def test_fallback_exec_stays_dense_exact(self):
        """Without a selector the exec backend attends the full chunk, and
        the DENSE oracle still holds — the fallback changes nothing but
        the telemetry (that is the point of recording it)."""
        eng = ServingEngine(2, pool_tokens=10**5, backend=JaxExecBackend())
        eng.register_chunk("doc", holder=1, length=64)
        rq = Request(0, home=0, chunk_ids=["doc"], m_q=2, k_selected=32)
        with pytest.warns(RuntimeWarning):
            eng.schedule_step([rq])
        got = eng.outputs_of(1)[0]
        want = oracle_partial(TINY_MLA, eng.store, rq, 1)
        np.testing.assert_allclose(got.o, want.o, rtol=RTOL, atol=ATOL)


# ---------------------------------------------------------------------------
# The index sidecar in the chunk store.
# ---------------------------------------------------------------------------

class TestIndexSidecar:
    def test_attach_validates_length(self):
        from repro.core.chunk_store import ChunkStore
        st = ChunkStore(2, 10**4)
        st.register("c", holder=0, length=8)
        with pytest.raises(ValueError):
            st.attach_index_keys("c", np.zeros((9, 4)))
        st.attach_index_keys("c", np.zeros((8, 4)))
        assert st.index_keys_on("c", 0).shape == (8, 4)
        assert st.index_keys_on("c", 1) is None

    def test_replica_and_eviction_lifecycle(self):
        from repro.core.chunk_store import ChunkStore
        st = ChunkStore(2, 10**4)
        st.register("c", holder=0, length=8)
        st.attach_index_keys("c", np.ones((8, 4)))
        st.add_replica("c", 1)
        st.set_replica_index_keys("c", 1, np.ones((8, 4)) * 2)
        assert float(st.index_keys_on("c", 1)[0, 0]) == 2.0
        st.evict_replica("c", 1)
        assert st.index_keys_on("c", 1) is None

    def test_holder_failure_promotes_sidecar(self):
        from repro.core.chunk_store import ChunkStore
        st = ChunkStore(2, 10**4)
        st.register("c", holder=0, length=8)
        st.attach_index_keys("c", np.ones((8, 4)))
        st.add_replica("c", 1)
        st.set_replica_index_keys("c", 1, np.ones((8, 4)) * 3)
        assert st.drop_holder(0) == []
        assert float(st.lookup("c").index_keys[0, 0]) == 3.0

    def test_replica_sidecar_rides_fetch(self):
        """A persisted dense FETCH moves the index sidecar with the cache
        bytes: the replica instance can score locally afterwards (keys
        are position-invariant — the delta splice never touches them)."""
        svc = IndexerService()
        eng = ServingEngine(4, pool_tokens=10**5,
                            backend=JaxExecBackend(), selector=svc)
        eng.register_chunk("doc", holder=1, length=64)
        svc.ensure_index_keys(eng.store, "doc")
        rq = Request(0, home=0, chunk_ids=["doc"], m_q=2,
                     expected_reuse_steps=100_000)
        assert [r.primitive for r in eng.schedule_step([rq])] == ["fetch"]
        rep_keys = eng.store.index_keys_on("doc", 0)
        assert rep_keys is not None
        np.testing.assert_array_equal(
            rep_keys, np.asarray(eng.store.lookup("doc").index_keys))

    def test_service_materializes_sidecar(self):
        svc = IndexerService()
        eng, _ = selection_scenario(selector=svc)
        keys = svc.ensure_index_keys(eng.store, "sel0")
        assert keys.shape == (192, svc.d_index)
        assert eng.store.lookup("sel0").index_keys is not None
        # second touch is a cache hit (same object)
        assert svc.ensure_index_keys(eng.store, "sel0") is not None


# ---------------------------------------------------------------------------
# Cost model: the index stage and the selected stage chains.
# ---------------------------------------------------------------------------

class TestSelectionCosts:
    def test_index_is_a_wire_stage(self):
        assert "index" in TL.WIRE_STAGES

    def test_route_selected_stage_sum_is_closed_form(self):
        fab = C.fabric("tpu_dcn")
        for frac in (0.0, 0.25, 1.0):
            # identical positional args on both sides: the signatures are
            # kept in lockstep on purpose
            stages = cm.route_selected_stages(fab, 16, 0, frac, 4, 16)
            assert cm.stages_total_s(stages) == pytest.approx(
                cm.t_route_selected_full(fab, 16, 0, frac, 4, 16), rel=1e-12)
        assert stages[0][0] == "index"

    def test_fetch_selected_stage_sum_is_closed_form(self):
        fab = C.fabric("tpu_dcn")
        stages = cm.fetch_selected_stages(fab, 96, 16, 2, 16)
        assert cm.stages_total_s(stages) == pytest.approx(
            cm.t_fetch_selected(fab, 96, 16, 2, 16), rel=1e-12)
        assert [n for n, _ in stages] == ["index", "gather"]

    def test_gather_sum_over_holders_is_scattered_closed_form(self):
        """Selection FETCH split across M holders reproduces the Fig 4a
        closed form exactly: M gather stages == t_fetch_scattered(K, M)."""
        fab = C.fabric("h100_ibgda")
        K, M = 2048, 7
        per_holder = cm.fetch_selected_stages(fab, K / M, 256, 32, 64)
        gather = dict(per_holder)["gather"] * M
        assert gather == pytest.approx(cm.t_fetch_scattered(fab, K, M),
                                       rel=1e-12)

    def test_selection_step_prices_index_on_the_timeline(self):
        eng, _ = _run(selector=IndexerService())
        sel_steps = [s for s in eng.stats if s.n_selected]
        assert sel_steps
        for s in sel_steps:
            assert s.stage_totals.get("index", 0.0) > 0.0
        # holder compute is scaled by the budget, not the store: a
        # selection route's compute stage is strictly below the dense one
        dense_compute = dict(cm.route_stages(C.fabric("tpu_ici"), 4))
        for r in eng.log:
            if r.req_ids and r.req_ids[0] in eng.plans[r.step - 1].selections \
                    and r.primitive == "route":
                assert dict(r.stages)["compute"] \
                    < dense_compute["compute"] + 1e-12


# ---------------------------------------------------------------------------
# Serve CLI: the selection flags.
# ---------------------------------------------------------------------------

@pytest.mark.usefixtures("compile_cache_dir")
class TestServeSelectionCLI:
    WORLD = ["--instances", "4", "--pods", "2", "--chunks", "6",
             "--chunk-tokens", "128", "--agents", "6", "--steps", "3"]
    ARGS = WORLD + ["--selection-frac", "0.5", "--selection-k", "128"]

    def test_selection_exec_verify_and_replay(self, tmp_path, capsys):
        from repro.launch import serve
        trace = tmp_path / "sel.json"
        serve.main(self.ARGS + ["--selection", "--backend", "exec",
                                "--verify",
                                "--save-selection-trace", str(trace)])
        out = capsys.readouterr().out
        assert "selector=indexer" in out and "selected pairs" in out
        for line in out.splitlines():
            if "max|err|" in line:
                assert float(line.rsplit("max|err| ", 1)[1]) < 1e-4
        # the recorded trace replays through the (numpy-only) planner —
        # WITHOUT the selection flags: the trace's meta must reconstruct
        # the recorded k/frac (they flow into pricing), like --trace does
        # for the corpus geometry
        serve.main(self.WORLD + ["--selection-trace", str(trace)])
        out2 = capsys.readouterr().out
        assert "selector=replay" in out2
        assert "--selection-trace meta overrides --selection-frac" in out2
        assert "--selection-trace meta overrides --selection-k" in out2
        # identical makespans line-for-line (same masks -> same plans)
        def makespans(text):
            return [ln.split("makespan ")[1].split(",")[0]
                    for ln in text.splitlines() if "makespan" in ln]
        assert makespans(out) == makespans(out2)

    def test_flag_conflicts(self, tmp_path):
        from repro.launch import serve
        with pytest.raises(SystemExit, match="cannot be combined"):
            serve.main(self.ARGS + ["--selection", "--selection-trace",
                                    str(tmp_path / "x.json")])
        with pytest.raises(SystemExit, match="requires --selection"):
            serve.main(self.ARGS + ["--save-selection-trace",
                                    str(tmp_path / "y.json")])


if __name__ == "__main__":
    FIXTURE.parent.mkdir(exist_ok=True)
    FIXTURE.write_text(json.dumps(_golden_payload(), indent=1) + "\n")
    print(f"wrote {FIXTURE}")
