"""Share of decode slots that carried a request, over the window: (tokens
out - prefills) / (decode steps * slots), from the engine's own
counters."""
LAYER = "scheduler (serving/engine.py)"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(run):
    r = run.record
    steps = r.stats_close[0] - r.stats_open[0]
    prefills = r.stats_close[1] - r.stats_open[1]
    tokens = r.stats_close[2] - r.stats_open[2]
    if steps <= 0:
        return None
    return 100.0 * (tokens - prefills) / (steps * r.slots)
