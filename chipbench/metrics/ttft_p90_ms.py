"""90th percentile of time to first token, from each request's due time,
over every request due in the window; a request still without its first
token at the close counts with close - due."""
import numpy as np

from chipbench import loop

UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    t = loop.ttft_s(run.record)
    return float(np.percentile(t, 90)) * 1e3 if t.size else None
