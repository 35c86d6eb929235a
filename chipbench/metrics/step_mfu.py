"""``readings.step_mfu``, in the closed-loop cell."""
from chipbench import readings

LAYER = "step roofline (chipbench/work.py over the device trace)"
UNIT = "%"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = "tokens_per_s"


def read(run):
    return readings.step_mfu(run)
