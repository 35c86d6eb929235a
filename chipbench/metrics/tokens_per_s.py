"""Output tokens delivered inside the window over its seconds."""
from chipbench import loop

UNIT = "tokens/s"
BETTER = "higher"
SOURCE = "host_clock"


def read(run):
    return loop.tokens_in_window(run.record) / run.record.seconds
