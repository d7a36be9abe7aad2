"""Seconds the process spent tracing, lowering and compiling (or loading
from the persistent compilation cache) the trainer's two programs,
``jit(init_state)`` and ``jit(train_step)``, from the program's compile
log.  Part of ``setup_s``."""
from chipbench import named

PROGRAMS = ("init_state", "train_step")


def read(run):
    log = named.compile_log() or {}
    if not all(p in log for p in PROGRAMS):
        return None
    return sum(log[p]["trace_s"] + log[p]["lower_s"] + log[p]["compile_s"]
               for p in PROGRAMS)
