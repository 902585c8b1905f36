"""Median host wall time of one offloaded decode call, prompt or output
token, over every call of every client in the window (in a batched round
each member's call lasts the whole round)."""
import numpy as np


def read(run):
    if not run.calls:
        return None
    return 1e3 * float(np.median([c.host_s for c in run.calls]))
