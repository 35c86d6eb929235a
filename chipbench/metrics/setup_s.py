"""Set-up: process start to the first request handed to the engine.
Loading, weights, warm-up and, in a run that compiles, compilation."""
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    return run.setup_s
