"""Operations and bytes that serving work needs, from the model group
of a configuration file and the live lengths. They count what the
algorithm must do, not what a program happens to do: a program that
wastes less does not lower them.

The counts of an architecture are its family's (``families/<family>.py``,
named by the group's ``family``); this module finds them and turns
them into the least time the chip could take.
"""
from __future__ import annotations

from chipbench import spec


def decode_step(m: dict, ctx: list) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over slots with lengths ctx."""
    return spec.family_of(m).decode_step(m, ctx)


def prefill(m: dict, T: int) -> float:
    """FLOPs of prefilling one prompt of T tokens."""
    return spec.family_of(m).prefill(m, T)


def bound_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
