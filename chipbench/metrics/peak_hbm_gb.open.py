"""``readings.peak_hbm_gb``, in the open-loop cell, where memory bounds the
slots and a full house makes requests wait."""
from chipbench import readings

LAYER = "device (TPU v5e)"
UNIT = "GB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "ttft_p90_ms"


def read(run):
    return readings.peak_hbm_gb(run)
