"""90th percentile of the wait from a request's due time to its hand-off to
submit(): the engine step in progress when it fell due."""
import numpy as np

from chipbench import loop

LAYER = "scheduler (serving/engine.py)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "ttft_p90_ms"


def read(run):
    lag = loop.submit_lag_s(run.record)
    return float(np.percentile(lag, 90)) * 1e3 if lag.size else None
