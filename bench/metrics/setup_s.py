"""Process start to the window's start: loading, data and weights,
warm-up and every compile."""


def read(run):
    return run.setup_s
