"""The roofline.  What a token's work costs in operations and bytes is
the configuration's model module's to count (bench/models/<model>.py)."""
from __future__ import annotations


def least_time_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
