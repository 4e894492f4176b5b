"""Process start to window start: imports, data, cluster, compile and
warm-up."""


def read(run):
    return run.setup_s
