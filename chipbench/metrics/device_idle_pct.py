"""``readings.device_idle_pct``, in the closed-loop cell."""
from chipbench import readings

LAYER = "device (TPU v5e)"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    return readings.device_idle_pct(run)
