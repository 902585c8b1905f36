"""Real client lanes per vmap-batched round over the window: submissions
served from a batch (ReplayBatcher.batched_replays) per batched execution
(vmap_batches)."""


def read(run):
    batches = run.counters.get("vmap_batches", 0)
    if not batches:
        return None
    return run.counters["batched_replays"] / batches
