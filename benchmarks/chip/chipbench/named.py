"""What per-layer metrics read by the names the program gives: a kernel's
device operations in the traced window, and the program's compile log."""
from __future__ import annotations

import re

from . import trace as tr


def kernel_self_ns(run, kernel):
    """{chip: [self ns of each call]} of the kernel ``kernel``: the device
    operations whose instruction is ``%<kernel>`` or ``%<kernel>.<n>``
    (the name its ``pallas_call`` gives), wholly inside the traced
    window, as the breakdown counts them."""
    name = re.compile(rf"%{re.escape(kernel)}(\.\d+)?")
    out = {}
    for d in run.devices:
        inside = [(n, s, e) for n, s, e in run.trace.ops.get(d, ())
                  if run.lo <= s and e <= run.hi]
        out[d] = [ns for n, ns in tr.self_times(inside)
                  if name.fullmatch(n.split(" = ")[0])]
    return out


def per_chip_step(run, total):
    """``total`` over the cell's chips and the window's steps; None
    where the run counts no steps."""
    steps = run.counters.get("steps")
    if not steps:
        return None
    return total / len(run.devices) / steps


def compile_log():
    """The program's compile log (``repro.obs.compile_log()``, read in
    the measured process), or None where the program keeps none."""
    try:
        from repro.obs import compile_log as read
    except ImportError:
        return None
    return read()
