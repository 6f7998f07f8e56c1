"""Device time per step in cross-chip collectives: the union of the
collective operations' intervals in the traced window (tracereduce's
`collective_s`, mean over the chips), over the step spans the trace
holds. Nothing to read where the trace shows no device or no step."""


def read(window):
    t = window.trace
    if t is None or t.n_chips == 0 or t.n_steps == 0:
        return None
    return 1e3 * t.collective_s / t.n_steps
