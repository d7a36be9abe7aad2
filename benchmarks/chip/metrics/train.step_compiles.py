"""How many times the process compiled the training step
(``jit(train_step)``) or loaded it from the persistent compilation
cache, from the program's compile log."""
from chipbench import named


def read(run):
    entry = (named.compile_log() or {}).get("train_step")
    if entry is None:
        return None
    return entry["compiles"]
