"""Device time of the flash-attention forward kernel (``flash_fwd``), in
ms per step: the self time of its calls in the traced window, over the
window's steps, averaged over the cell's chips.  Under layer remat the
forward runs twice per layer and step."""
from chipbench import named


def read(run):
    calls = named.kernel_self_ns(run, "flash_fwd")
    if not any(calls.values()):
        return None
    total_ns = sum(sum(ns) for ns in calls.values())
    return named.per_chip_step(run, total_ns / 1e6)
