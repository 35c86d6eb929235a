"""90th percentile of the wait from a request's hand-off to submit() to its
admission into a slot (``Request.admitted_s``): the scheduler's own share
of the time to first token."""
from chipbench import engine_readings

LAYER = "scheduler (serving/engine.py)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "ttft_p90_ms"


def read(run):
    return engine_readings.queue_wait_p90_ms(run)
