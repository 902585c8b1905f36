"""Host wall time of the set-up rounds in which a client recorded
(eager op-by-op execution on the server)."""


def read(run):
    return run.setup["record_s"]
