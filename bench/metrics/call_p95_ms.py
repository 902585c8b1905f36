"""95th percentile of the host wall time of the window's decode calls."""
import numpy as np


def read(run):
    if not run.calls:
        return None
    return 1e3 * float(np.percentile([c.host_s for c in run.calls], 95))
