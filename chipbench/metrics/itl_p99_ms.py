"""99th percentile of every gap between consecutive output tokens of a
request, both tokens inside the window."""
import numpy as np

from chipbench import loop

UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    g = loop.itl_s(run.record)
    return float(np.percentile(g, 99)) * 1e3 if g.size else None
