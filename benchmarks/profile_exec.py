"""Where the fused execution path's wall time goes (ISSUE 8).

Runs the frozen mixed_congested trace through the fused/overlapped
shard_map backend and sums the phase timer's per-step self seconds
(`StepStats.phase_s`, repro.obs.phases) — the four phases of the fused
path, plus the `query` phases of the queries it materialises:

  * stack    — host-side shard assembly + the ONE batched device_put
               per step (`_StackBatch.commit`);
  * dispatch — issuing every group's fused jitted program without
               blocking (async dispatch; compile cost lands here on the
               cold rep, warm reps are just launch overhead);
  * barrier  — the single per-step block_until_ready over all launched
               tasks (this is where the device compute is actually
               waited out);
  * merge    — wall attribution + stage apportioning + on-device merges
               of the committed partials.

Mirrors benchmarks/profile_planner.py for the execution side. Needs an
8-device mesh (the mesh size is fixed at jax import, so the CALLER sets
XLA_FLAGS):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src:. python benchmarks/profile_exec.py [--reps N]
"""

from __future__ import annotations

import argparse
import pathlib
import sys
import time


def profile(repetitions: int, serial: bool = False,
            pipeline_depth: int = 1) -> dict:
    import jax
    if len(jax.devices()) < 8:
        raise SystemExit(
            "profile_exec needs an 8-device mesh: set XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 before python starts")
    tests_dir = str(pathlib.Path(__file__).resolve().parent.parent / "tests")
    if tests_dir not in sys.path:
        sys.path.insert(0, tests_dir)
    from engine_scenarios import SCENARIOS
    from repro.serving.backends import ShardMapExecBackend
    from repro.serving.engine import EngineConfig

    backend = ShardMapExecBackend(fused=not serial)
    per_rep, overlap = [], []
    plan_wall = 0.0
    split: dict = {}
    for _ in range(repetitions):
        eng, steps = SCENARIOS["mixed_congested"](
            backend, cfg=EngineConfig(pipeline_depth=pipeline_depth))
        t0 = time.perf_counter()
        eng.run(iter(steps))
        per_rep.append(time.perf_counter() - t0)
        overlap.append(eng.planner_overlap_s)
        plan_wall += sum(eng.plan_walls)
        for st in eng.stats:
            for k, v in st.phase_s.items():
                split[k] = split.get(k, 0.0) + v
    return {"reps": per_rep, "split": split,
            "last_step_split": dict(eng.stats[-1].phase_s),
            "mode": "serial" if serial else "fused",
            "pipeline_depth": pipeline_depth,
            "plan_wall_s": plan_wall,
            "overlap_per_rep": overlap,
            "device_wall_s": split.get("barrier", 0.0)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reps", type=int, default=3,
                    help="engines to run through one backend (rep 0 cold)")
    ap.add_argument("--serial", action="store_true",
                    help="profile the serial staged_call chain instead "
                         "(no phase split: it has no stack/dispatch/"
                         "barrier structure)")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="engine pipeline depth (ISSUE 10): >= 2 plans "
                         "step N+1 while step N's device work runs, and "
                         "the plan-overlap row below becomes non-zero")
    a = ap.parse_args()
    out = profile(a.reps, a.serial, a.pipeline_depth)
    print(f"mode {out['mode']}; per-rep wall "
          + " ".join(f"{1000 * t:.1f}ms" for t in out["reps"])
          + " (rep 0 cold: compiles land there)")
    total = sum(out["split"].values())
    if "barrier" not in out["split"]:
        print("  (no phase split recorded — serial mode bypasses "
              "the fused path)")
        return
    print(f"phase split over all reps ({1000 * total:.1f} ms attributed):")
    for name, v in sorted(out["split"].items(), key=lambda kv: -kv[1]):
        share = v / total if total else 0.0
        print(f"  {name:10s} {1000 * v:8.2f} ms  ({share:5.1%})")
    last = sum(out["last_step_split"].values())
    print(f"warmest step ({1000 * last:.1f} ms): "
          + ", ".join(f"{k} {1000 * v:.2f}ms"
                      for k, v in sorted(out["last_step_split"].items(),
                                         key=lambda kv: -kv[1])))
    # plan-overlap row (ISSUE 10): attribute the pipelining win instead
    # of leaving it as a per-rep wall ratio
    hidden = sum(out["overlap_per_rep"])
    frac = hidden / out["plan_wall_s"] if out["plan_wall_s"] else 0.0
    print(f"plan overlap (depth {out['pipeline_depth']}): plan wall "
          f"{1000 * out['plan_wall_s']:.2f}ms, device (barrier) wall "
          f"{1000 * out['device_wall_s']:.2f}ms, hidden "
          f"{1000 * hidden:.2f}ms ({frac:.1%} of plan wall)")


if __name__ == "__main__":
    main()
