"""RPCs per decode call over the window (InferenceResult.rpcs)."""


def read(run):
    if not run.calls:
        return None
    return sum(c.rpcs for c in run.calls) / len(run.calls)
