"""Readings shared by metrics that read one quantity in cells whose
end-to-end metrics differ: ``decode_step_ms`` moves the gaps between
tokens in the open-loop cell, ``decode_step_ms.closed`` the tokens per
second of the closed loop. Each metric's own file names its layer,
unit and the end-to-end metric it moves."""
from chipbench import peaks, trace, work


def decode_step_ms(run):
    """Device time per execution of the decode step module, in the
    traced stretch."""
    if run.trace is None:
        return None
    n, t = trace.module_totals(run.trace, trace.is_decode)
    return 1e3 * t / n if n else None


def decode_roofline(run):
    """Roofline share of the decode steps in the traced stretch: the sum
    of each step's least time (``work.py``: the larger of its FLOPs over
    peak FLOP/s and its bytes over HBM bandwidth, for the work the step
    needs at its live lengths) over the sum of their device time."""
    if run.trace is None:
        return None
    pairs = [(s, t) for s, t in trace.matched_steps(run, trace.is_decode)
             if s.decode_ctx and t > 0]
    if not pairs:
        return None
    pk = peaks.peak(run.device_kind)
    bound = sum(work.bound_s(*work.decode_step(run.model, s.decode_ctx),
                             pk) for s, _ in pairs)
    return 100.0 * bound / sum(t for _, t in pairs)


def step_mfu(run):
    """Model FLOPs of every prompt and output token processed in the
    window (``work.py``) over the window's seconds times the chips' peak
    bf16 FLOP/s: the whole window, not busy time."""
    r = run.record
    flops = 0.0
    for s in r.steps:
        if r.t_open < s.t1 <= r.t_close:
            flops += sum(work.prefill(run.model, T) for T in s.prefill_lens)
            if s.decode_ctx:
                flops += work.decode_step(run.model, s.decode_ctx)[0]
    pk = peaks.peak(run.device_kind)
    return 100.0 * flops / (r.seconds * pk["bf16_flops"] * run.chips)


def device_idle_pct(run):
    """Share of the traced time in which the engine held work and no
    operation ran on the device: 1 - busy / (traced time less the spans
    in which nothing was queued or running)."""
    if run.trace is None or run.trace["work_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["work_s"])


def peak_hbm_gb(run):
    """Peak bytes in use on the chip after the window
    (``memory_stats()["peak_bytes_in_use"]``), in GB."""
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
