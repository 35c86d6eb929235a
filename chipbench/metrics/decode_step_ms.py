"""``readings.decode_step_ms``, in the open-loop cell, where a decode
step's time sets the gap between tokens."""
from chipbench import readings

LAYER = "model step (models/decoding.py, jitted in serving/engine.py)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "itl_p99_ms"


def read(run):
    return readings.decode_step_ms(run)
