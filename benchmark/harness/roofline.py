"""A kernel's share of its roofline from the reduced trace: the least time
the chip could take for the operations and bytes the algorithm needs (the
larger of operations over peak FLOP/s and bytes over peak bytes/s), over the
summed device time of the kernel's events in the stretch."""
from __future__ import annotations

from typing import Callable, Optional


def share(run, is_kernel: Callable[[str], bool], flops_per_sample: float,
          bytes_per_sample: float, what: str) -> Optional[float]:
    r = run.reduced
    if r is None:
        return None
    kernel_s = sum(v for name, v in r.ops_s.items() if is_kernel(name))
    if kernel_s <= 0.0:
        return None
    samples = r.periods * run.window.steps_per_mark * int(run.cell.traffic["batch"])
    by_flops = flops_per_sample * samples / run.peaks["bf16_flops_per_s"]
    by_bytes = bytes_per_sample * samples / run.peaks["hbm_bytes_per_s"]
    value = 100.0 * max(by_flops, by_bytes) / kernel_s
    if value > 100.0:
        raise ValueError(
            f"{what} reads {value:.1f}% of its roofline: its operations or "
            "bytes are counted too high, or its events leave out part of the work")
    return value
