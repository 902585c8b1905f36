"""Host wall time of the set-up rounds, all clients replaying, in which
JAX compiled or loaded a program from its cache: the first replayed calls
and the first batched rounds."""


def read(run):
    return run.setup["compile_s"]
