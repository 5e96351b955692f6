"""A kernel's share of its roofline.

The least time the chip could take for a step's calls of the kernel is the
larger of its operations over the bf16 peak and its bytes over the HBM peak
(``peaks.json``); the share is that time over the kernel's measured time. A
reader gives the work the model requires of the kernel, not what an
implementation does: causal pairs only, the backward as 2 x the forward,
recomputation not counted, operands at bf16, the MXU's operand width that the
bf16 peak assumes. So no implementation at default precision can read above
100%.
"""
from __future__ import annotations


def share(ctx, ns_per_step, ops_per_step: float, bytes_per_step: float):
    """% of the roofline: ``ns_per_step`` is the kernel's device ns per step
    on one chip, ``ops_per_step`` and ``bytes_per_step`` its work per step on
    that chip. None where there is no time to read."""
    if not ns_per_step or ns_per_step <= 0:
        return None
    floor_s = max(ops_per_step / ctx.peak["bf16_flops_per_s"],
                  bytes_per_step / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * floor_s / (ns_per_step / 1e9)
