"""``readings.decode_step_ms``, in the closed-loop cell, where with every
slot full a decode step's time sets the tokens per second."""
from chipbench import readings

LAYER = "model step (models/decoding.py, jitted in serving/engine.py)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    return readings.decode_step_ms(run)
