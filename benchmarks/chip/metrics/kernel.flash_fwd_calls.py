"""Calls of the flash-attention forward kernel (``flash_fwd``) per step
and chip in the traced window: one per layer for the forward and one
for its recomputation under layer remat."""
from chipbench import named


def read(run):
    calls = named.kernel_self_ns(run, "flash_fwd")
    if not any(calls.values()):
        return None
    return named.per_chip_step(run, sum(len(ns) for ns in calls.values()))
