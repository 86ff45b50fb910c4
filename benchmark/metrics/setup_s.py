"""The launcher's start to the first instant of the measured window."""


def read(run, metric):
    return run.setup_s
