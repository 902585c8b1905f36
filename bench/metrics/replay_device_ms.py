"""Device busy time in the traced window per round (solo: per call)."""


def read(run):
    if run.trace is None:
        return None
    rounds = {c.round_id for c in run.calls if c.traced}
    if not rounds:
        return None
    return 1e3 * run.trace["busy_s"] / len(rounds)
