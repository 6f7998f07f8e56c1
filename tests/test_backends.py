"""Plan/execute split + pluggable execution backends (ISSUE 3).

* PLANNER PARITY — the same trace through the analytic and the exec
  engine yields IDENTICAL per-step primitive decisions and dispatch
  plans (the planner is backend-independent by construction; these tests
  keep it that way).
* EXEC EXACTNESS — the JaxExecBackend's decode outputs reproduce
  single-instance attention over each request's concatenated chunks to
  float round-off, regardless of which primitive the predicate picked
  (§3.3, end-to-end through the scheduler) — asserted on all three
  golden traces (routed-only / fetch-heavy / mixed-congested).
* fabric calibration (benchmarks/calibrate_fabric.py) round-trips
  through Fabric.from_json / load_table / register_fabrics, and the
  serve CLI drives both backends from one saved trace.
"""

import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

from engine_scenarios import SCENARIOS
from repro.core import constants as C
from repro.core.constants import Fabric, register_fabrics
from repro.models.mla import absorbed_partial
from repro.serving.backends import (AnalyticBackend, ExecutionBackend,
                                    JaxExecBackend, TINY_MLA)
from repro.serving.backends.jax_exec import (chunk_array, oracle_partial,
                                             query_for)
from repro.serving.engine import EngineConfig, Request, ServingEngine
from repro.serving.workload import (WorkloadConfig, agentic_trace,
                                    load_trace, materialize_trace,
                                    register_corpus, save_trace)

RTOL, ATOL = 2e-5, 1e-6


def _run(build, backend=None):
    """Drive one scenario; returns (engine, per-step request lists)."""
    eng, steps = build(backend)
    for reqs in steps:
        eng.schedule_step(reqs)
    return eng, steps


def _record_key(r):
    return (r.step, r.primitive, r.chunk_id, r.holder, r.n_requesters,
            r.m_q_total, r.backup, r.fabric_idx, r.link_instance, r.home,
            r.req_ids, r.est_cost_s, r.stages)


# ---------------------------------------------------------------------------
# Planner parity: analytic vs exec.
# ---------------------------------------------------------------------------

class TestBackendParity:
    def test_default_backend_is_analytic(self):
        eng = ServingEngine(2, pool_tokens=10**4)
        assert eng.backend.name == "analytic"
        assert isinstance(eng.backend, AnalyticBackend)
        assert isinstance(eng.backend, ExecutionBackend)
        assert isinstance(JaxExecBackend(), ExecutionBackend)

    @pytest.mark.parametrize("name", sorted(SCENARIOS))
    def test_identical_decisions_and_plans(self, name):
        """Same trace -> identical per-step primitive decisions AND full
        dispatch plans (costs, stages, grouping) across backends."""
        ana, _ = _run(SCENARIOS[name], AnalyticBackend())
        exe, _ = _run(SCENARIOS[name], JaxExecBackend())
        assert [_record_key(r) for r in ana.log] \
            == [_record_key(r) for r in exe.log]
        for sa, se in zip(ana.stats, exe.stats):
            assert sa.primitives == se.primitives
            assert sa.n_resident == se.n_resident
            assert sa.latency_s == se.latency_s            # same timeline
            assert sa.stage_totals == se.stage_totals
        # analytic produced no outputs; exec produced them for every step
        assert all(not o for o in ana.step_outputs)
        assert all(exe.step_outputs)

    def test_parity_on_agentic_workload(self):
        """The generated (sessioned, Zipf) workload drives both backends to
        the same decisions too — not just the hand-built scenarios."""
        def build(backend):
            eng = ServingEngine(4, pool_tokens=32 * 256,
                                cfg=EngineConfig(), instances_per_pod=2,
                                backend=backend)
            wl = WorkloadConfig(n_steps=10, agents=8, n_corpus_chunks=6,
                                chunk_tokens=256, session_steps=(2, 6),
                                seed=3)
            cids = register_corpus(eng, wl)
            return eng, materialize_trace(agentic_trace(wl, eng, cids))
        ana, steps_a = build(AnalyticBackend())
        exe, steps_e = build(JaxExecBackend())
        assert [[dataclasses.asdict(r) for r in s] for s in steps_a] \
            == [[dataclasses.asdict(r) for r in s] for s in steps_e]
        # the workload's selection_frac puts some sessions in the §5.4
        # regime with NO selector configured: the engines' warn-once
        # fallback RuntimeWarning is intentional here — assert it instead
        # of leaking it (tier-1 runs with filterwarnings = error)
        with pytest.warns(RuntimeWarning, match="k_selected"):
            for reqs_a, reqs_e in zip(steps_a, steps_e):
                ana.schedule_step(reqs_a)
                exe.schedule_step(reqs_e)
        assert [_record_key(r) for r in ana.log] \
            == [_record_key(r) for r in exe.log]


# ---------------------------------------------------------------------------
# Exec exactness: scheduler-driven attention == single-instance attention.
# ---------------------------------------------------------------------------

def _assert_step_exact(eng: ServingEngine, reqs, step: int):
    outs = eng.outputs_of(step)
    for rq in reqs:
        assert rq.req_id in outs, (step, rq.req_id)
        got = outs[rq.req_id]
        want = oracle_partial(TINY_MLA, eng.store, rq, step)
        np.testing.assert_allclose(got.o, want.o, rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(got.m, want.m, rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(got.l, want.l, rtol=RTOL, atol=ATOL)
        assert got.o.shape == (rq.m_q, TINY_MLA.n_heads,
                               TINY_MLA.kv_lora_rank)


class TestExecExactness:
    @pytest.mark.parametrize("name", sorted(SCENARIOS))
    def test_matches_single_instance_attention(self, name):
        """Routed, fetched (spliced replica), local and resident accesses
        all reproduce attention over the request's concatenated chunks."""
        eng, steps = SCENARIOS[name](JaxExecBackend())
        for reqs in steps:
            eng.schedule_step(reqs)
            _assert_step_exact(eng, reqs, eng.step_idx)

    def test_fetch_persists_real_replica_bytes(self):
        """A persisted FETCH leaves the spliced array on the requester; the
        next step's resident access attends THAT copy and stays exact."""
        eng = ServingEngine(4, pool_tokens=10**5,
                            backend=JaxExecBackend())
        eng.register_chunk("doc", holder=1, length=64)
        rq = Request(0, home=0, chunk_ids=["doc"], m_q=2,
                     expected_reuse_steps=100_000)
        assert [r.primitive for r in eng.schedule_step([rq])] == ["fetch"]
        rep = eng.store.array_on("doc", 0)
        assert rep is not None and rep.shape == (64, TINY_MLA.d_qk)
        # delta-0 splice: the replica equals the canonical bytes exactly
        np.testing.assert_allclose(rep, eng.store.lookup("doc").data,
                                   rtol=0, atol=0)
        assert eng.schedule_step([rq]) == []       # resident now
        _assert_step_exact(eng, [rq], eng.step_idx)

    def test_exactness_survives_holder_failure(self):
        """Orphaned chunk -> LOCAL re-prefill path regenerates the same
        canonical entries, so outputs stay exact after a failure."""
        eng = ServingEngine(4, pool_tokens=10**5,
                            backend=JaxExecBackend())
        eng.register_chunk("doc", holder=1, length=32)
        rq = Request(0, home=0, chunk_ids=["doc"], m_q=4)
        eng.schedule_step([rq])
        assert eng.fail_instance(1) == ["doc"]
        recs = eng.schedule_step([rq])
        assert [r.primitive for r in recs] == ["local"]
        _assert_step_exact(eng, [rq], eng.step_idx)

    def test_output_retention_window(self):
        """Old steps' output arrays are released (bounded memory over a
        long exec run); recent steps stay queryable."""
        eng = ServingEngine(4, pool_tokens=10**5,
                            cfg=EngineConfig(retain_outputs=2),
                            backend=JaxExecBackend())
        eng.register_chunk("c", holder=1, length=16)
        rq = Request(0, home=0, chunk_ids=["c"], m_q=1)
        for _ in range(4):
            eng.schedule_step([rq])
        assert eng.outputs_of(1) == {} and eng.outputs_of(2) == {}
        assert eng.outputs_of(3) and eng.outputs_of(4)

    def test_deterministic_materialization(self):
        """Chunk arrays and query tensors are pure functions of ids/seeds:
        two independent engines materialize identical bytes."""
        a = chunk_array(TINY_MLA, "corpus_0001", 16)
        b = chunk_array(TINY_MLA, "corpus_0001", 16)
        np.testing.assert_array_equal(a, b)
        r1 = Request(7, home=0, chunk_ids=["x"], m_q=3, query_seed=42)
        np.testing.assert_array_equal(query_for(TINY_MLA, r1, 5),
                                      query_for(TINY_MLA, r1, 5))
        assert not np.array_equal(query_for(TINY_MLA, r1, 5),
                                  query_for(TINY_MLA, r1, 6))


# ---------------------------------------------------------------------------
# Fetch source resolution + exec-mode failover (ISSUE 7 satellites).
# ---------------------------------------------------------------------------


class TestFetchSourceResolution:
    def test_shared_resolver(self):
        """Both fetch exec paths resolve the wire source through ONE
        function: link_instance when the planner set it (fetch_replica
        spawns carry the canonical holder there — their `holder` field is
        the TARGET), else the record's holder."""
        from repro.serving.backends.jax_exec import fetch_source
        rec = dataclasses.make_dataclass(
            "R", ["link_instance", "holder"])(link_instance=2, holder=5)
        assert fetch_source(rec) == 2              # fetch_replica shape
        rec = dataclasses.make_dataclass(
            "R", ["link_instance", "holder"])(link_instance=-1, holder=5)
        assert fetch_source(rec) == 5              # no-wire fallback

    def test_selected_fetch_rejects_replica_spawn(self):
        """fetch_replica-under-selection is unreachable by construction
        (replica spawns batch only dense overflow); the exec path pins it
        with an assertion so the source resolution cannot silently
        diverge again."""
        backend = JaxExecBackend()
        rec = dataclasses.make_dataclass(
            "R", ["primitive", "req_ids", "link_instance", "holder"])(
            primitive="fetch_replica", req_ids=(0,), link_instance=1,
            holder=2)
        with pytest.raises(AssertionError, match="replica spawns"):
            backend._exec_fetch_selected(None, rec, None, None, None)

    def test_exec_serves_from_promoted_replica(self):
        """Exec-mode failover: a persisted replica survives its canonical
        holder's death (promotion), and the NEXT step's execution attends
        the promoted copy — outputs stay exact (ISSUE 7 satellite)."""
        eng, steps = SCENARIOS["fetch_heavy"](JaxExecBackend())
        eng.schedule_step(steps[0])        # FETCHes persist replicas on 0
        assert eng.store.array_on("doc0", 0) is not None
        assert eng.fail_instance(1) == []  # doc0 promoted, not orphaned
        assert eng.store.lookup("doc0").holder == 0
        rq = Request(7, home=3, chunk_ids=["doc0"], m_q=4)
        eng.schedule_step([rq])
        _assert_step_exact(eng, [rq], eng.step_idx)

    def test_analytic_and_exec_record_no_measured_report(self):
        """measured_reports stays aligned with stats for every backend;
        only the shard_map backend fills it (tested in the mesh prog)."""
        for backend in (AnalyticBackend(), JaxExecBackend()):
            eng, steps = SCENARIOS["routed_only"](backend)
            for reqs in steps:
                eng.schedule_step(reqs)
            assert len(eng.measured_reports) == len(eng.stats)
            assert all(r is None for r in eng.measured_reports)


# ---------------------------------------------------------------------------
# Up-front shard-shape validation (ISSUE 7 satellite; in-process — the
# checks are host-side shape logic, no mesh needed).
# ---------------------------------------------------------------------------


class TestShardShapeValidation:
    def test_route_shards_name_axis_shard_and_shapes(self):
        from repro.core.routing import check_route_shards
        with pytest.raises(ValueError, match=r"shard 3.*d_qk=24.*d_qk=16"):
            check_route_shards("instance", np.zeros((4, 2, 24)),
                               np.zeros((64, 16)), shard=3)
        with pytest.raises(ValueError, match=r"S_local=63.*S_local=64"):
            check_route_shards("instance", np.zeros((4, 2, 24)),
                               np.zeros((64, 24)), np.zeros(63, bool))
        # well-formed shards pass silently
        check_route_shards("instance", np.zeros((4, 2, 24)),
                           np.zeros((64, 24)), np.zeros(64, bool), shard=1)

    def test_instance_shards_name_shard_and_both_shapes(self):
        from repro.serving.backends.shard_map import check_instance_shards
        with pytest.raises(ValueError,
                           match=r"shard 2.*\(7, 4\).*\(8, 4\)"):
            check_instance_shards({0: np.zeros((8, 4)),
                                   2: np.zeros((7, 4))}, (8, 4), 8)
        with pytest.raises(ValueError, match="outside the mesh"):
            check_instance_shards({9: np.zeros((8, 4))}, (8, 4), 8)
        check_instance_shards({0: np.zeros((8, 4))}, (8, 4), 8)


# ---------------------------------------------------------------------------
# Array-bearing chunk store.
# ---------------------------------------------------------------------------

class TestChunkStoreArrays:
    def test_attach_validates_length(self):
        from repro.core.chunk_store import ChunkStore
        st = ChunkStore(2, 10**4)
        st.register("c", holder=0, length=8)
        with pytest.raises(ValueError):
            st.attach_data("c", jnp.zeros((9, 4)))
        st.attach_data("c", jnp.zeros((8, 4)))
        assert st.array_on("c", 0).shape == (8, 4)
        assert st.array_on("c", 1) is None            # not resident

    def test_register_with_data_validates_too(self):
        """register(data=...) enforces the same length check as
        attach_data — and a failed registration leaves no trace."""
        from repro.core.chunk_store import ChunkStore
        st = ChunkStore(2, 10**4)
        with pytest.raises(ValueError):
            st.register("c", holder=0, length=8, data=jnp.zeros((9, 4)))
        assert st.used(0) == 0                        # allocation rolled back
        st.register("c", holder=0, length=8, data=jnp.zeros((8, 4)))
        assert st.array_on("c", 0).shape == (8, 4)

    def test_eviction_drops_replica_bytes(self):
        from repro.core.chunk_store import ChunkStore
        st = ChunkStore(2, 10**4)
        st.register("c", holder=0, length=8, data=jnp.ones((8, 4)))
        st.add_replica("c", 1)
        st.set_replica_data("c", 1, jnp.ones((8, 4)) * 2)
        assert float(st.array_on("c", 1)[0, 0]) == 2.0
        st.evict_replica("c", 1)
        assert st.array_on("c", 1) is None

    def test_holder_failure_promotes_replica_bytes(self):
        from repro.core.chunk_store import ChunkStore
        st = ChunkStore(2, 10**4)
        st.register("c", holder=0, length=8, data=jnp.ones((8, 4)))
        st.add_replica("c", 1)
        st.set_replica_data("c", 1, jnp.ones((8, 4)) * 3)
        assert st.drop_holder(0) == []
        c = st.lookup("c")
        assert c.holder == 1 and float(c.data[0, 0]) == 3.0


# ---------------------------------------------------------------------------
# Fabric calibration + JSON tables (satellite).
# ---------------------------------------------------------------------------

class TestFabricTables:
    def test_json_roundtrip(self):
        fab = C.fabric("h100_ibgda")
        back = Fabric.from_json(json.loads(json.dumps(fab.to_json())))
        assert back == fab
        # unknown keys (fit diagnostics) are ignored
        assert Fabric.from_json(dict(fab.to_json(), mape_pct=3.1)) == fab
        with pytest.raises(ValueError):
            Fabric.from_json({"t_probe_s": 1e-6, "bw_Bps": 1e9,
                              "link_peak_Bps": 1e9})

    def test_calibrate_writes_loadable_table(self, tmp_path):
        from benchmarks import calibrate_fabric as cf
        out = tmp_path / "table.json"
        cf.main(["--fabrics", "tpu_ici", "h100_ibgda",
                 "--out", str(out)])
        table = Fabric.load_table(out)
        assert set(table) == {"tpu_ici_fit", "h100_ibgda_fit"}
        # noiseless model sweep recovers the two constants (BW exactly up
        # to fit arithmetic; probe absorbs the t_launch residual)
        ici = table["tpu_ici_fit"]
        assert ici.bw_Bps == pytest.approx(C.fabric("tpu_ici").bw_Bps,
                                           rel=1e-6)
        assert ici.t_probe_s == pytest.approx(
            C.fabric("tpu_ici").t_probe_s, rel=1e-3)
        register_fabrics(table)
        try:
            assert C.fabric("tpu_ici_fit") == ici
            # an engine runs on the measured table
            eng = ServingEngine(
                4, pool_tokens=10**5,
                cfg=EngineConfig(intra_pod_fabric="tpu_ici_fit",
                                 cross_pod_fabric="h100_ibgda_fit"),
                instances_per_pod=2)
            eng.register_chunk("c", holder=1, length=2048)
            recs = eng.schedule_step(
                [Request(0, home=0, chunk_ids=["c"], m_q=64)])
            assert [r.primitive for r in recs] == ["route"]
        finally:
            for name in table:
                C.FABRICS.pop(name, None)

    def test_register_no_overwrite(self):
        ref = C.fabric("tpu_ici")
        other = Fabric("tpu_ici", 9e-6, 1e9, 1e9)
        register_fabrics({"tpu_ici": other}, overwrite=False)
        assert C.fabric("tpu_ici") == ref
        register_fabrics({"tpu_ici": other})
        try:
            assert C.fabric("tpu_ici") == other
        finally:
            register_fabrics({"tpu_ici": ref})

    def test_calibrate_run_rows(self):
        from benchmarks import calibrate_fabric as cf
        rows = cf.run()
        assert len(rows) == len(cf.DEFAULT_FABRICS)
        assert all(r["bw_err_pct"] < 2.0 for r in rows)


# ---------------------------------------------------------------------------
# Serve CLI: one saved trace drives both backends (satellite).
# ---------------------------------------------------------------------------

@pytest.mark.usefixtures("compile_cache_dir")
class TestServeCLI:
    ARGS = ["--instances", "4", "--pods", "2", "--chunks", "6",
            "--chunk-tokens", "64", "--agents", "6", "--steps", "3"]

    def test_workload_not_inline_rng(self, tmp_path, capsys):
        """The CLI builds its trace via serving.workload: requests carry
        session reuse horizons (amortisation can accrue), not the old
        inline loop's constant reuse=1."""
        from repro.launch import serve
        trace = tmp_path / "t.json"
        serve.main(self.ARGS + ["--save-trace", str(trace)])
        assert "backend=analytic" in capsys.readouterr().out
        steps = load_trace(trace)
        assert len(steps) == 3 and len(steps[0]) == 6
        assert any(rq.expected_reuse_steps > 1
                   for step in steps for rq in step)
        assert all(rq.query_seed is not None
                   for step in steps for rq in step)

    def test_same_trace_both_backends(self, tmp_path, capsys):
        from repro.launch import serve
        trace = tmp_path / "t.json"
        serve.main(self.ARGS + ["--save-trace", str(trace)])
        capsys.readouterr()
        serve.main(self.ARGS + ["--trace", str(trace),
                                "--backend", "exec", "--verify"])
        out = capsys.readouterr().out
        assert "backend=exec" in out
        for line in out.splitlines():
            if "max|err|" in line:
                assert float(line.rsplit("max|err| ", 1)[1]) < 1e-4

    def test_replay_reconstructs_recorded_world(self, tmp_path, capsys):
        """A replay with mismatched flags must rebuild the corpus the
        trace was recorded against (meta header), not trust the flags —
        otherwise chunk geometry silently changes every decision."""
        from repro.launch import serve
        from repro.serving.workload import trace_meta
        trace = tmp_path / "t.json"
        serve.main(self.ARGS + ["--save-trace", str(trace)])
        assert trace_meta(trace)["chunk_tokens"] == 64
        capsys.readouterr()
        # replay with DIFFERENT corpus flags: meta must win
        serve.main(["--instances", "8", "--chunks", "16",
                    "--chunk-tokens", "2048", "--steps", "3",
                    "--trace", str(trace), "--backend", "exec", "--verify"])
        out = capsys.readouterr().out
        assert "meta overrides --chunk-tokens: 2048 -> 64" in out
        for line in out.splitlines():
            if "max|err|" in line:
                assert float(line.rsplit("max|err| ", 1)[1]) < 1e-4

    def test_verify_requires_exec_backend(self):
        from repro.launch import serve
        with pytest.raises(SystemExit, match="--backend exec"):
            serve.main(self.ARGS + ["--verify"])

    def test_save_and_replay_flags_conflict(self, tmp_path):
        from repro.launch import serve
        with pytest.raises(SystemExit, match="cannot"):
            serve.main(self.ARGS + ["--trace", str(tmp_path / "a.json"),
                                    "--save-trace",
                                    str(tmp_path / "b.json")])

    def test_verify_exits_nonzero_past_tolerance(self, capsys):
        """--verify TOL fails the run when a step's max|err| exceeds TOL;
        the dtype's own tolerance passes the same run."""
        from repro.launch import serve
        run = self.ARGS + ["--backend", "exec", "--verify"]
        serve.main(run)
        assert "max|err|" in capsys.readouterr().out
        with pytest.raises(SystemExit, match="--verify FAILED"):
            serve.main(run + ["0"])

    def test_named_geometry_and_dtype(self):
        import jax.numpy as jnp
        from repro.launch import serve
        from repro.serving.backends.jax_exec import (DEEPSEEK_V2_MLA,
                                                     TINY_MLA)
        args = serve.build_parser().parse_args(self.ARGS + [
            "--backend", "exec", "--mla", "deepseek-v2",
            "--dtype", "bfloat16"])
        backend = serve.build_engine(args).backend
        assert backend.cfg == DEEPSEEK_V2_MLA and backend.dtype == jnp.bfloat16
        assert (DEEPSEEK_V2_MLA.d_qk, DEEPSEEK_V2_MLA.kv_lora_rank) == (576,
                                                                        512)
        args = serve.build_parser().parse_args(self.ARGS + ["--backend",
                                                            "exec"])
        backend = serve.build_engine(args).backend
        assert backend.cfg == TINY_MLA and backend.dtype == jnp.float32

    def test_compile_cache_lives_where_the_env_says(self, compile_cache_dir):
        """With JAX_COMPILATION_CACHE_DIR set, entries land there and the
        checkout's default cache directory is left alone."""
        import pathlib
        from repro.launch import serve
        default = pathlib.Path(serve.__file__).resolve().parents[3] / \
            ".jax_cache"
        before = set(default.iterdir()) if default.exists() else set()
        # a chunk length no other test uses, so this process compiles anew
        serve.main(self.ARGS + ["--backend", "exec", "--steps", "1",
                                "--chunk-tokens", "40"])
        assert any(compile_cache_dir.iterdir())
        after = set(default.iterdir()) if default.exists() else set()
        assert after == before

    def test_compile_cache_defaults_to_checkout(self, monkeypatch):
        import pathlib
        import jax
        from repro.launch import serve
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = serve.enable_compile_cache()
        root = pathlib.Path(serve.__file__).resolve().parents[3]
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path


# ---------------------------------------------------------------------------
# The planner must stay importable (and runnable) without jax.
# ---------------------------------------------------------------------------

def test_planner_importable_without_jax():
    """repro.serving's planner + analytic backend are numpy-only; the
    jax-dependent exec backend loads lazily. Simulate a jax-free
    environment in a subprocess with an import blocker."""
    import os
    import pathlib
    import subprocess
    import sys
    import repro
    # repro is a namespace package: __file__ is None, use __path__
    src = str(pathlib.Path(list(repro.__path__)[0]).resolve().parent)
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    prog = (
        "import sys\n"
        "class Block:\n"
        "    def find_module(self, name, path=None):\n"
        "        if name == 'jax' or name.startswith('jax.'):\n"
        "            return self\n"
        "    def load_module(self, name):\n"
        "        raise ImportError('jax blocked for this test')\n"
        "sys.meta_path.insert(0, Block())\n"
        "from repro.serving import EngineConfig, Request, ServingEngine\n"
        "eng = ServingEngine(4, pool_tokens=10**5, instances_per_pod=2)\n"
        "eng.register_chunk('c', holder=1, length=2048)\n"
        "recs = eng.schedule_step([Request(0, home=0, chunk_ids=['c'],\n"
        "                                  m_q=64)])\n"
        "assert [r.primitive for r in recs] == ['route'], recs\n"
        "assert 'jax' not in sys.modules\n"
        "print('NO-JAX-PLAN-OK')\n")
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env)
    assert out.returncode == 0, out.stderr
    assert "NO-JAX-PLAN-OK" in out.stdout


# ---------------------------------------------------------------------------
# route_batched: the plan-keyed entry point.
# ---------------------------------------------------------------------------

class TestRouteBatched:
    def test_groups_match_route_simulated(self):
        from repro.core.routing import route_batched, route_simulated
        import jax
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(k1, (5, TINY_MLA.n_heads, TINY_MLA.d_qk))
        s1 = jax.random.normal(k2, (12, TINY_MLA.d_qk))
        s2 = jax.random.normal(k3, (7, TINY_MLA.d_qk))
        got = route_batched(TINY_MLA, [q, q[:2]], [[s1, s2], [s2]])
        want0 = route_simulated(TINY_MLA, q, [s1, s2])
        np.testing.assert_allclose(got[0].o, want0.o, rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(
            got[1].o, absorbed_partial(TINY_MLA, q[:2], s2).o,
            rtol=RTOL, atol=ATOL)

    def test_length_mismatch_raises(self):
        from repro.core.routing import route_batched
        with pytest.raises(ValueError):
            route_batched(TINY_MLA, [jnp.zeros((1, 2, 24))], [])


# ---------------------------------------------------------------------------
# Overlapped execution units (ISSUE 8) — everything here is single-device:
# the fused path's host-side machinery (query memo, stage apportioning,
# report telemetry, pool retirement hooks) without a mesh.
# ---------------------------------------------------------------------------

class TestExecOverlapUnits:
    def test_query_memo_reuses_and_prunes(self):
        from repro.serving.backends import JaxExecBackend
        b = JaxExecBackend()
        rq = Request(3, home=0, chunk_ids=["c"], m_q=4)
        q1 = b.query_of(rq, 1)
        assert b.query_of(rq, 1) is q1            # memo hit, same buffer
        # a different request pinning the SAME query_seed shares the entry
        twin = Request(9, home=1, chunk_ids=["c"], m_q=4, query_seed=3)
        assert b.query_of(twin, 1) is q1
        b.query_of(rq, 2)
        assert (3, 1, 4) in b._qmemo              # previous step retained
        b.query_of(rq, 4)
        assert (3, 1, 4) not in b._qmemo          # ... then pruned
        assert (3, 4, 4) in b._qmemo

    def test_apportion_spreads_wall_over_planned_ratios(self):
        from types import SimpleNamespace
        from repro.serving.backends import ShardMapExecBackend
        b = ShardMapExecBackend()
        rec = SimpleNamespace(stages=[("probe", 1e-6), ("transfer", 3e-6)],
                              req_ids=[0], chunk_id="c", primitive="route")
        meas = b._apportion(rec, 8e-6, {}, 1)
        assert meas["probe"] == pytest.approx(2e-6)
        assert meas["transfer"] == pytest.approx(6e-6)
        assert b._fill_count == 0

    def test_apportion_zero_base_is_counted_fill(self):
        from types import SimpleNamespace
        from repro.serving.backends import ShardMapExecBackend
        b = ShardMapExecBackend()
        rec = SimpleNamespace(stages=[("pull", 0.0), ("splice", 0.0)],
                              req_ids=[0], chunk_id="c", primitive="fetch")
        meas = b._apportion(rec, 4e-6, {}, 1)
        assert meas["pull"] == pytest.approx(2e-6)
        assert meas["splice"] == pytest.approx(2e-6)
        assert b._fill_count == 2                 # the S6 counter, not 0.0s

    def test_apportion_index_stage_uses_selector_measurement(self):
        from types import SimpleNamespace
        from repro.serving.backends import ShardMapExecBackend
        b = ShardMapExecBackend()
        rec = SimpleNamespace(
            stages=[("index", 9e-6), ("probe", 1e-6), ("compute", 1e-6)],
            req_ids=[5], chunk_id="sel", primitive="route")
        meas = b._apportion(rec, 6e-6, {(2, 5, "sel"): 7e-6}, 2)
        assert meas["index"] == pytest.approx(7e-6)   # plan-time wall
        # the fused wall is spread over the NON-index planned ratios only
        assert meas["probe"] == pytest.approx(3e-6)
        assert meas["compute"] == pytest.approx(3e-6)
        assert b._fill_count == 0

    def test_measured_report_telemetry(self):
        import repro.serving.timeline as TL
        flows = [TL.transport_flow(
            "route:c@1#0", [("probe", 1e-6), ("transfer", 2e-6)],
            link_res=TL.link(1, 0), holder_sm=TL.sm(1),
            requester_sm=TL.sm(0), primitive="route", chunk_id="c")]
        ana = TL.simulate(flows)
        rep = TL.measured_vs_analytic(1, ana, flows, 0.5, mode="fused",
                                      pool_entries=2, pool_bytes=64,
                                      stage_fills=1)
        assert (rep.mode, rep.pool_entries, rep.pool_bytes,
                rep.stage_fills) == ("fused", 2, 64, 1)
        head = rep.summary().splitlines()[0]
        assert "makespan analytic" in head      # the CI smoke's grep line
        assert "fused" in head and "pool 2/64B" in head
        assert "1 stage fills" in head
        assert rep.overlap_efficiency == pytest.approx(
            ana.makespan_s / sum(ana.stage_totals().values()))
        # defaults stay backward compatible (the serial path's call)
        bare = TL.measured_vs_analytic(1, ana, flows)
        assert (bare.mode, bare.pool_entries, bare.stage_fills) \
            == ("serial", 0, 0)
        assert "stage fills" not in bare.summary().splitlines()[0]

    def test_measured_overview_aggregates(self):
        import repro.serving.timeline as TL
        eng = ServingEngine(2, pool_tokens=10**5)
        assert eng.measured_overview() is None    # analytic-only run
        flows = [TL.transport_flow(
            "route:c@1#0", [("transfer", 2e-6)], link_res=TL.link(1, 0),
            holder_sm=TL.sm(1), requester_sm=TL.sm(0), primitive="route",
            chunk_id="c")]
        ana = TL.simulate(flows)
        eng.measured_reports = [
            None, TL.measured_vs_analytic(1, ana, flows, 0.1, mode="fused",
                                          pool_entries=3, pool_bytes=96)]
        line = eng.measured_overview()
        assert "ratio p50 x1.0" in line and "fused" in line
        assert "pool 3 entries/96B" in line

    def test_evict_listener_fires_on_evict_and_drop(self):
        from repro.core.chunk_store import ChunkStore
        st = ChunkStore(3, 10**4)
        seen = []
        listener = lambda cid, inst: seen.append((cid, inst))
        st.add_evict_listener(listener)
        st.add_evict_listener(listener)           # idempotent registration
        st.register("c", holder=0, length=8, data=jnp.ones((8, 4)))
        st.add_replica("c", 1)
        st.set_replica_data("c", 1, jnp.ones((8, 4)))
        st.evict_replica("c", 1)
        assert seen == [("c", 1)]                 # fired once, not twice
        st.add_replica("c", 2)
        st.set_replica_data("c", 2, jnp.ones((8, 4)))
        st.drop_holder(0)                         # holder dies, 2 promoted
        assert seen == [("c", 1), ("c", 0)]
