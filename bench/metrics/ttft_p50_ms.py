"""Median, over the requests begun in the window, of the host wall time
from the request's start (its first prompt token fed) to its first output
token on the host.  A request still waiting when the window closed is
followed until its first token, so its whole wait counts."""
import numpy as np


def read(run):
    ttft = [r.ttft_s for r in run.requests if r.ttft_s is not None]
    if not ttft:
        return None
    return 1e3 * float(np.median(ttft))
