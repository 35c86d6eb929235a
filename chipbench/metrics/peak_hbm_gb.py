"""``readings.peak_hbm_gb``, in the closed-loop cell, where memory bounds
the slots."""
from chipbench import readings

LAYER = "device (TPU v5e)"
UNIT = "GB"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(run):
    return readings.peak_hbm_gb(run)
