"""Calibrate a cell's correctness limit on the chip: the program's widest
gap and the fp8 control's, seed by seed, in one process.

    python3 bench/control.py --workload dsv2-1chip.agent-fleet \
        --seconds 5 --seeds 11 12 13

Each seed is a full run of the cell (set-up, warm-up, a short window at
the cell's own load, the sample drawn from the seed) whose check also
puts the control in the program's place: the configuration's `control`
hook, by default the reference with the queries and the latent cache in
float8_e4m3fn (reference.attend_fp8), held to the configuration's limit
by the same predicate. The limit lies between
the largest program gap and the smallest control gap (PERF.md gives the
readings). Exits 1 where the program reads not correct or the control
reads correct on any seed. The benchmark's own runs never compute the
control.
"""

import argparse
import gc
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    import harness
    program, control, sound = [], [], True
    for seed in args.seeds:
        try:
            done = harness.run(ROOT, args.workload, seed, args.seconds,
                               False, control=True)
        except harness.SetupError as e:
            print(f"[control] cannot run: {e}", file=sys.stderr)
            return 2
        chk = done.result["checks"]
        program.append(chk["max_abs_err"]["value"])
        control.append(chk["control_max_abs_err"]["value"])
        sound &= done.result["correct"] and done.control_correct is False
        print(json.dumps({"seed": seed, "correct": done.result["correct"],
                          "control_correct": done.control_correct,
                          "program": program[-1], "control": control[-1],
                          "metrics": done.result["metrics"]}), flush=True)
        del done
        gc.collect()
    print(json.dumps({"workload": args.workload, "seeds": len(args.seeds),
                      "program_max": max(program),
                      "control_min": min(control), "separated": sound}))
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
