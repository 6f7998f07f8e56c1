"""Serving launcher: a partitioned canonical c^KV store driven by the
ROUTE/FETCH/LOCAL predicate (the paper's artifact end-to-end), with a
pluggable execution backend (ISSUE 3):

    # plan + analytic timeline only (default)
    PYTHONPATH=src python -m repro.launch.serve --instances 8 --pods 2 \
        --chunks 16 --agents 12 --steps 5

    # plan AND execute on real c^KV arrays, verifying §3.3 exactness
    PYTHONPATH=src python -m repro.launch.serve --backend exec --verify

    # ... at DeepSeek-V2 attention widths in bf16 (the chip's geometry)
    PYTHONPATH=src python -m repro.launch.serve --backend exec --verify \
        --mla deepseek-v2 --dtype bfloat16

    # the §5.4 selection regime end-to-end (ISSUE 4): the distributed
    # indexer scores/selects per step, the backends scatter-attend the
    # masks, selection requests verify against the selection_k oracle
    PYTHONPATH=src python -m repro.launch.serve --selection \
        --selection-k 128 --backend exec --verify \
        --save-selection-trace /tmp/sel.json
    # ... and a recorded selection trace replays through the planner
    # (numpy-only: no jax needed to PRICE the regime from a trace)
    PYTHONPATH=src python -m repro.launch.serve \
        --selection-trace /tmp/sel.json --selection-k 128

    # replay a saved trace (the SAME trace drives both backends)
    PYTHONPATH=src python -m repro.launch.serve --save-trace /tmp/t.json
    PYTHONPATH=src python -m repro.launch.serve --trace /tmp/t.json \
        --backend exec

    # run on measured fabric constants (benchmarks/calibrate_fabric.py)
    PYTHONPATH=src python -m repro.launch.serve \
        --fabric-table benchmarks/results/fabric_table.json \
        --intra-fabric tpu_ici_fit --cross-fabric tpu_dcn_fit

The workload comes from repro.serving.workload (agentic sessions with
Zipf-popular working sets and session lifetimes), NOT an inline RNG loop:
session lifetimes are the FETCH amortisation horizon (§5.5 rule 2), so
the CLI path exercises fetch persistence and replica spawning like the
benchmarks do.
"""

import argparse
import os
import pathlib

import numpy as np

from repro.core.constants import Fabric, register_fabrics
from repro.serving.engine import (EngineConfig, ServingEngine,
                                  transport_latencies)
from repro.serving.workload import (WorkloadConfig, agentic_trace,
                                    materialize_trace, read_trace,
                                    register_corpus, save_trace)

# args whose values define the WORLD a trace was recorded against; a replay
# must reconstruct them from the trace's meta header, not trust the flags
TRACE_META_ARGS = ("instances", "pods", "chunks", "chunk_tokens",
                   "agents", "steps", "seed")
# a SELECTION trace additionally depends on the workload's selection knobs:
# k_selected flows into every selection dispatch's pricing (kb_wire, the
# predicate's k column) and selection_frac decides WHICH sessions select —
# replaying with different values would silently produce different StepStats
SELECTION_META_ARGS = TRACE_META_ARGS + ("selection_k", "selection_frac")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="predicate-driven serving engine (plan/execute/account)")
    ap.add_argument("--instances", type=int, default=8)
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--chunks", type=int, default=16)
    ap.add_argument("--chunk-tokens", type=int, default=2048)
    ap.add_argument("--agents", type=int, default=12,
                    help="concurrent agent sessions (fan-in N)")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--pool-tokens", type=int, default=10_000_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", choices=("analytic", "exec", "shard_map"),
                    default="analytic")
    ap.add_argument("--serial-exec", action="store_true",
                    help="shard_map backend: run dispatch groups through "
                         "the PR-7 serial staged_call chain instead of the "
                         "fused/overlapped path (A/B debug knob, ISSUE 8)")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="steps in flight between submit and account "
                         "(ISSUE 10): 1 = lockstep plan/execute/account "
                         "(the kill switch), >= 2 plans step N+1 "
                         "speculatively while step N's device work runs")
    ap.add_argument("--trace", default="",
                    help="replay a save_trace() JSON instead of generating")
    ap.add_argument("--save-trace", default="",
                    help="write the generated trace as JSON and run it")
    ap.add_argument("--verify", nargs="?", type=float, const=True,
                    default=None, metavar="TOL",
                    help="exec backend: check outputs against the "
                         "single-instance attention oracle (§3.3) and exit "
                         "non-zero when a step's max|err| exceeds TOL "
                         "(default: jax_exec.oracle_tolerance of --dtype)")
    ap.add_argument("--mla", choices=("tiny", "deepseek-v2"), default="tiny",
                    help="execution geometry of the exec backends "
                         "(jax_exec.MLA_GEOMETRIES); the planner's payload "
                         "does not depend on it")
    ap.add_argument("--dtype", choices=("float32", "bfloat16"),
                    default="float32",
                    help="dtype of the exec backends' cache and queries")
    ap.add_argument("--fabric-table", default="",
                    help="JSON fabric table (calibrate_fabric output) to "
                         "register before building the engine")
    ap.add_argument("--intra-fabric", default="tpu_ici")
    ap.add_argument("--cross-fabric", default="tpu_dcn")
    # §5.4 selection regime (ISSUE 4)
    ap.add_argument("--selection", action="store_true",
                    help="run the distributed indexer service: score -> "
                         "select -> scatter-attend through the scheduler")
    ap.add_argument("--selection-k", type=int, default=2048,
                    help="per-request selection budget in tokens (the "
                         "workload's k_selected)")
    ap.add_argument("--selection-frac", type=float, default=0.1,
                    help="fraction of agent sessions in the selection "
                         "regime (workload generator)")
    ap.add_argument("--block-tokens", type=int, default=64,
                    help="NSA selection granularity (indexer block size)")
    ap.add_argument("--selection-trace", default="",
                    help="replay a recorded selection trace through the "
                         "planner (numpy-only) instead of live scoring")
    ap.add_argument("--save-selection-trace", default="",
                    help="with --selection: record the indexer's per-step "
                         "verdicts as JSON")
    # flight recorder (ISSUE 9)
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome trace-event / Perfetto JSON of the "
                         "run: engine wall spans + planned (and, under "
                         "--backend shard_map, measured) timeline track "
                         "groups per step. Load at https://ui.perfetto.dev")
    ap.add_argument("--metrics-out", default="",
                    help="write the obs metrics registry snapshot "
                         "(counters/gauges/histograms) as JSON at exit")
    ap.add_argument("--drift-threshold", type=float, default=None,
                    help="enable the model-vs-measured drift monitor with "
                         "this |EWMA| envelope (the paper's §7 claim is "
                         "~0.07 on calibrated fabrics; forced host devices "
                         "need a very loose value). Exits non-zero when a "
                         "(primitive, fabric, stage) cell trips. Requires a "
                         "measuring backend (shard_map)")
    return ap


def build_obs(args):
    """The flight recorder, from the CLI flags: None when every obs flag
    is off (the engine then keeps its inert NULL_OBS and the planner hot
    path pays nothing)."""
    if not (args.trace_out or args.metrics_out
            or args.drift_threshold is not None):
        return None
    from repro.obs import DriftConfig, DriftMonitor, Obs, Tracer
    tracer = Tracer() if args.trace_out else None
    drift = (DriftMonitor(DriftConfig(threshold=args.drift_threshold))
             if args.drift_threshold is not None else None)
    return Obs(tracer=tracer, drift=drift)


def build_selector(args):
    """The engine's selection seam: live indexer (--selection), recorded
    trace (--selection-trace, numpy-only), or None (selection requests are
    priced but executed dense — the engine warns once and counts them)."""
    if args.selection:
        from repro.serving.selection import (IndexerService, SelectionConfig,
                                             ShardMapIndexerService)
        svc = (ShardMapIndexerService if args.backend == "shard_map"
               else IndexerService)
        return svc(SelectionConfig(block_tokens=args.block_tokens))
    if args.selection_trace:
        from repro.serving.selection import ReplaySelector
        return ReplaySelector(args.selection_trace)
    return None


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    JAX_COMPILATION_CACHE_DIR, when set, is the cache and no other
    directory is set. Otherwise the cache is <checkout>/.jax_cache, a
    fixed path, so that a later run in the same checkout finds what an
    earlier one wrote. Every compile is written, however short: the
    served path compiles many small programs, and the default one-second
    floor would cache none of them. Call before the first compile."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(pathlib.Path(__file__).resolve().parents[3]
                   / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def build_engine(args) -> ServingEngine:
    if args.fabric_table:
        register_fabrics(Fabric.load_table(args.fabric_table))
    if args.backend in ("exec", "shard_map"):
        import jax.numpy as jnp
        from repro.serving.backends import (JaxExecBackend,
                                            ShardMapExecBackend)
        from repro.serving.backends.jax_exec import MLA_GEOMETRIES
        geom = dict(cfg=MLA_GEOMETRIES[args.mla], dtype=jnp.dtype(args.dtype))
        backend = (JaxExecBackend(**geom) if args.backend == "exec" else
                   ShardMapExecBackend(fused=not args.serial_exec, **geom))
    else:
        backend = None
    return ServingEngine(
        args.instances, pool_tokens=args.pool_tokens,
        cfg=EngineConfig(intra_pod_fabric=args.intra_fabric,
                         cross_pod_fabric=args.cross_fabric,
                         pipeline_depth=args.pipeline_depth),
        instances_per_pod=max(1, args.instances // args.pods),
        backend=backend, selector=build_selector(args),
        obs=build_obs(args))


def apply_trace_meta(args, meta: dict, keys=TRACE_META_ARGS,
                     source: str = "--trace") -> None:
    """A replayed trace's chunk ids, homes and seeds only mean anything in
    the world they were recorded against: override the world-defining args
    from the trace's meta header (flag mismatches would otherwise silently
    change every decision — or crash on unknown chunk ids)."""
    for key in keys:
        if key in meta and meta[key] != getattr(args, key):
            print(f"[serve] {source} meta overrides "
                  f"--{key.replace('_', '-')}"
                  f": {getattr(args, key)} -> {meta[key]}")
            setattr(args, key, meta[key])


def build_trace(args, eng: ServingEngine, replay=None):
    """The per-step request lists: the pre-parsed --trace replay if given,
    else generated by the agentic workload (sessions, lifetimes, Zipf
    corpus — §1, §6.3). Either way the corpus registers from the (possibly
    meta-overridden) geometry args."""
    wl = WorkloadConfig(n_steps=args.steps, agents=args.agents,
                        n_corpus_chunks=args.chunks,
                        chunk_tokens=args.chunk_tokens, seed=args.seed,
                        selection_frac=args.selection_frac,
                        k_selected=args.selection_k)
    cids = register_corpus(eng, wl)
    if replay is not None:
        return replay
    gen = agentic_trace(wl, eng, cids)
    if args.save_trace:
        meta = {key: getattr(args, key) for key in TRACE_META_ARGS}
        return save_trace(args.save_trace, gen, meta=meta)
    return materialize_trace(gen)


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    if args.verify is not None and args.backend not in ("exec",
                                                        "shard_map"):
        raise SystemExit("--verify checks exec outputs against the §3.3 "
                         "oracle: it requires --backend exec or shard_map")
    if args.trace and args.save_trace:
        raise SystemExit("--save-trace records a GENERATED trace; it cannot "
                         "be combined with --trace (replay)")
    if args.selection and args.selection_trace:
        raise SystemExit("--selection scores live; it cannot be combined "
                         "with --selection-trace (replay)")
    if args.save_selection_trace and not args.selection:
        raise SystemExit("--save-selection-trace records the live "
                         "indexer's verdicts: it requires --selection")
    replay = None
    if args.trace:
        meta, replay = read_trace(args.trace)
        apply_trace_meta(args, meta)
    if args.selection_trace:
        # the selection trace defines its world too — including the
        # selection knobs, which flow into pricing (bit-identical replay
        # requires the recorded k/frac, not whatever the flags say)
        from repro.serving.selection import load_selection_trace
        sel_meta, _ = load_selection_trace(args.selection_trace)
        apply_trace_meta(args, sel_meta, keys=SELECTION_META_ARGS,
                         source="--selection-trace")
    if args.backend != "analytic" or args.selection:
        enable_compile_cache()
    eng = build_engine(args)
    steps = build_trace(args, eng, replay)
    tol = None
    if args.verify is not None:
        from repro.serving.backends.jax_exec import oracle_tolerance
        # a bare --verify takes the dtype's tolerance
        tol = (oracle_tolerance(args.dtype) if args.verify is True
               else args.verify)
    over_tol = []

    # reporting trails accounting: at --pipeline-depth >= 2 a scheduled
    # step may still be in flight when the loop moves on, so per-step
    # lines print from the accounted prefix of eng.stats, not from the
    # just-scheduled step (at depth 1 the cursor stays caught up and the
    # output is identical to the historical lockstep loop)
    reported = [0]

    def report_accounted():
        while reported[0] < len(eng.stats):
            s = eng.stats[reported[0]]
            reqs = steps[reported[0]]
            recs = eng.plans[reported[0]].records
            line = (f"[serve] step {s.step}: {len(recs)} dispatches "
                    f"{s.primitives}, {s.n_resident}/{s.n_pairs} resident, "
                    f"makespan {s.latency_s*1e6:.0f}us")
            if eng.selector is not None:
                line += f", {s.n_selected} selected pairs"
            if tol is not None:
                from repro.serving.backends.jax_exec import max_oracle_err
                err = max_oracle_err(eng, reqs, s.step)
                if not err <= tol:
                    over_tol.append((s.step, err))
                line += f", max|err| {err:.2e}"
            print(line)
            report = eng.measured_reports[reported[0]]
            if report is not None:
                # the shard_map backend's measured-vs-analytic loop (§7)
                print("\n".join("[serve]   " + ln
                                for ln in report.summary().splitlines()))
            reported[0] += 1

    depth = max(1, args.pipeline_depth)
    for i, reqs in enumerate(steps):
        eng.schedule_step(reqs)
        if depth >= 2 and i + 1 < len(steps):
            eng.speculate_step(steps[i + 1])
        report_accounted()
    eng.flush()
    report_accounted()
    if depth > 1:
        print(f"[serve] pipeline: depth {depth}, planner overlap hidden "
              f"{eng.planner_overlap_s*1e3:.2f}ms, "
              f"{eng.misspeculation_replans} replans")
    if over_tol:
        raise SystemExit(
            f"[serve] --verify FAILED: {len(over_tol)} step(s) past "
            f"max|err| {tol:g}: "
            + ", ".join(f"step {st} {err:.2e}" for st, err in over_tol))

    if args.save_selection_trace:
        from repro.serving.selection import save_selection_trace
        save_selection_trace(args.save_selection_trace, eng.selector.log,
                             eng.selector.block_tokens, eng.selector.d_index,
                             meta={key: getattr(args, key)
                                   for key in SELECTION_META_ARGS})
        print(f"[serve] selection trace -> {args.save_selection_trace} "
              f"({len(eng.selector.log)} steps)")
    if eng.selector is not None:
        index_s = sum(s.stage_totals.get("index", 0.0) for s in eng.stats)
        mk = sum(s.latency_s for s in eng.stats)
        print(f"[serve] selection: selector={eng.selector.name}, "
              f"{sum(s.n_selected for s in eng.stats)} selected pairs, "
              f"indexer-stage share of makespan "
              f"{index_s / mk if mk else 0.0:.3f}")

    overview = eng.measured_overview()
    if overview is not None:
        print(f"[serve] exec: {overview}")
    lat = transport_latencies(eng.stats)
    n_route = sum(1 for r in eng.log if r.primitive == "route")
    print(f"[serve] backend={eng.backend.name}; total dispatches "
          f"{len(eng.log)}; route fraction "
          f"{n_route/max(1, len(eng.log)):.2f} (decode defaults to ROUTE, "
          f"§5.5); replicas spawned "
          f"{sum(s.replicas_spawned for s in eng.stats)}")
    if len(lat):
        print(f"[serve] p50 step latency {np.percentile(lat, 50)*1e6:.0f}us, "
              f"p99 {np.percentile(lat, 99)*1e6:.0f}us over {len(lat)} "
              "transporting steps")

    # -- flight recorder exports + drift verdict (ISSUE 9) -------------------
    obs = eng.obs
    if obs.enabled:
        if args.trace_out and obs.tracer is not None:
            doc = obs.tracer.export(args.trace_out)
            print(f"[serve] trace -> {args.trace_out} "
                  f"({len(doc['traceEvents'])} events, "
                  f"{obs.tracer.n_steps} steps)")
        if args.metrics_out and obs.metrics is not None:
            obs.metrics.to_json(args.metrics_out)
            snap = obs.metrics.snapshot()
            print(f"[serve] metrics -> {args.metrics_out} "
                  f"({len(snap['counters'])} counters, "
                  f"{len(snap['gauges'])} gauges, "
                  f"{len(snap['histograms'])} histograms)")
        if obs.drift is not None:
            for ln in obs.drift.summary_lines():
                print(f"[serve] {ln}")
            if obs.drift.n_reports == 0:
                print("[serve] drift: no measured reports — the monitor "
                      "needs --backend shard_map")
            tripped = obs.drift.tripped()
            if tripped:
                raise SystemExit(
                    f"[serve] drift monitor TRIPPED: {len(tripped)} "
                    f"cell(s) past |ewma| > "
                    f"{obs.drift.config.threshold:g} — the fabric table "
                    f"no longer tracks measured walls (recalibrate via "
                    f"benchmarks/calibrate_fabric.py)")
            print(f"[serve] drift: OK ({len(obs.drift.cells)} cells within "
                  f"|ewma| <= {obs.drift.config.threshold:g})")


if __name__ == "__main__":
    main()
