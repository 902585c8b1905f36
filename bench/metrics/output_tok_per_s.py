"""Output tokens delivered to all clients over the whole window, per
second of the window."""


def read(run):
    return sum(c.output for c in run.calls) / run.window_s
