"""Seconds from the process's start to the measured window: imports,
device start-up, weights, quantisation, compiles and warm-up."""


def read(run):
    return run.setup_s
