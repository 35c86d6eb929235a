"""``readings.device_idle_pct``, in the open-loop cell, where idle time
inside a step lengthens the gap between tokens."""
from chipbench import readings

LAYER = "device (TPU v5e)"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "itl_p99_ms"


def read(run):
    return readings.device_idle_pct(run)
