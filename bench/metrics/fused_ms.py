"""Shard_map backend host time per step in its fused holder programs: the
self seconds of the `stack` (every record's inputs shipped in one
transfer), `dispatch` (every fused program launched) and `barrier` (the
step's one wait for them) phases that the program's phase timer puts in
StepStats.phase_s, mean over the window's steps. None where the program
has no phase timer."""

PHASES = ("stack", "dispatch", "barrier")


def read(window):
    timed = [s.stats.phase_s for s in window.steps
             if hasattr(s.stats, "phase_s")]
    if not timed:
        return None
    return 1e3 * sum(p.get(k, 0.0) for p in timed
                     for k in PHASES) / len(window.steps)
