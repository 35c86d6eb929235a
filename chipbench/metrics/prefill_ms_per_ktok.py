"""Device time of the prefill module per 1,000 prompt tokens prefilled, in
the traced stretch."""
from chipbench import trace

LAYER = "model step (models/decoding.py, jitted in serving/engine.py)"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "ttft_p90_ms"


def read(run):
    if run.trace is None:
        return None
    pairs = [(s, t) for s, t in trace.matched_steps(run, trace.is_prefill)
             if s.prefill_lens and t > 0]
    if not pairs:
        return None
    tokens = sum(sum(s.prefill_lens) for s, _ in pairs)
    return 1e3 * sum(t for _, t in pairs) / (tokens / 1000.0)
