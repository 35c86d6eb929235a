"""``readings.decode_roofline``, in the closed-loop cell."""
from chipbench import readings

LAYER = "step roofline (chipbench/work.py over the device trace)"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    return readings.decode_roofline(run)
