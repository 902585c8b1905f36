"""Process start to the first timed call: weights, session trace and
upload, recording, compile and warm-up."""


def read(run):
    return run.setup["setup_s"]
