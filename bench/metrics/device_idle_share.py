"""Share of the traced window in which the device ran no operation."""


def read(run):
    if run.trace is None or run.trace["idle_share"] is None:
        return None
    return 100.0 * run.trace["idle_share"]
