"""``readings.decode_roofline``, in the open-loop cell."""
from chipbench import readings

LAYER = "step roofline (chipbench/work.py over the device trace)"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_p99_ms"


def read(run):
    return readings.decode_roofline(run)
