"""Exec backend host time per step in holder partials: the self seconds
of the `resident`, `route`, `fetch` and `local` phases (one per resident
access or dispatch; queries materialised inside them excluded) that the
program's phase timer puts in StepStats.phase_s, mean over the window's
steps. None where the program has no phase timer."""

PHASES = ("resident", "route", "fetch", "local")


def read(window):
    timed = [s.stats.phase_s for s in window.steps
             if hasattr(s.stats, "phase_s")]
    if not timed:
        return None
    return 1e3 * sum(p.get(k, 0.0) for p in timed
                     for k in PHASES) / len(window.steps)
