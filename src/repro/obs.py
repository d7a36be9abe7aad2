"""The process's compile log, kept from JAX's own compile events.

``install()`` registers one ``jax.monitoring`` duration listener; calling
it again does nothing.  From then on, for every function JAX traces,
lowers or compiles, the log keeps how many times and for how many
seconds.  A program loaded from the persistent compilation cache counts
as a backend compile: JAX times the load under the same event.  Nothing
runs on a step that compiles nothing.

``compile_log()`` reads it, keyed by the function's name as JAX reports
it without the ``jit(...)`` around it (``Trainer``'s programs are
``init_state`` and ``train_step``)::

    {"train_step": {"traces": n, "trace_s": s,
                    "lowerings": n, "lower_s": s,
                    "compiles": n, "compile_s": s}, ...}

The log is one per process, as JAX's listeners are.
"""
from __future__ import annotations

import threading

from jax import monitoring

__all__ = ["install", "compile_log", "reset"]

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": ("traces", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lowerings",
                                                        "lower_s"),
    "/jax/core/compile/backend_compile_duration": ("compiles", "compile_s"),
}

_lock = threading.Lock()
_log: dict = {}
_installed = False


def _name(fun_name: str) -> str:
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _listen(event: str, duration_s: float, fun_name: str = "", **_):
    keys = _PHASES.get(event)
    if keys is None or not fun_name:
        return
    count, seconds = keys
    with _lock:
        entry = _log.setdefault(_name(fun_name), dict.fromkeys(
            [k for pair in _PHASES.values() for k in pair], 0))
        entry[count] += 1
        entry[seconds] += duration_s


def install() -> None:
    """Start keeping the compile log (once per process)."""
    global _installed
    with _lock:
        if _installed:
            return
        monitoring.register_event_duration_secs_listener(_listen)
        _installed = True


def compile_log() -> dict:
    """A copy of the log: function name -> counts and seconds."""
    with _lock:
        return {name: dict(entry) for name, entry in _log.items()}


def reset() -> None:
    """Empty the log; the listener stays."""
    with _lock:
        _log.clear()
