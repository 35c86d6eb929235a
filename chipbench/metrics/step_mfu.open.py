"""``readings.step_mfu``, in the open-loop cell, beside decode_roofline."""
from chipbench import readings

LAYER = "step roofline (chipbench/work.py over the device trace)"
UNIT = "%"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "itl_p99_ms"


def read(run):
    return readings.step_mfu(run)
