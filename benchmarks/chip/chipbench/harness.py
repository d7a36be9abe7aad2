"""What every runner shares: the measured window with its optional
profiler trace and host spans, what a runner hands back, and the result
line."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import shutil
import sys
import tempfile
import time

from . import trace as tr


class Window:
    """The measured window.  With ``trace`` the profiler records it, and
    the harness's spans (``span(name)``) land in the trace beside the
    device's operations.  ``end()`` marks the close of the work the window
    counts; the trace's flush after it is not timed."""

    def __init__(self, trace):
        self.trace = trace
        self.dir = None
        self.t0 = self.t1 = None

    def __enter__(self):
        import jax

        if self.trace:
            self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def elapsed(self):
        return time.perf_counter() - self.t0

    def end(self):
        if self.t1 is None:
            self.t1 = time.perf_counter()
            self._span.__exit__(None, None, None)

    def __exit__(self, *exc):
        import jax

        self.end()
        if self.trace:
            jax.profiler.stop_trace()

    @property
    def seconds(self):
        return self.t1 - self.t0

    def read_trace(self):
        """The trace's reduction; the files are deleted once read."""
        if not self.trace:
            return None
        try:
            return tr.read(tr.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def seed_key(seed):
    """A JAX PRNG key for any whole number, 64 bits and over included."""
    import jax

    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")
    key = jax.random.PRNGKey(seed % 2**32)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


@dataclasses.dataclass
class Outcome:
    """What a runner hands back to the harness."""

    end_to_end: dict  # metric name -> value, as measured with the trace off
    checks: dict  # number compared -> (value, limit)
    attempted: int
    failed: int
    memory_peak_bytes: int
    window: Window
    counters: dict = dataclasses.field(default_factory=dict)
    notes: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric's reader gets."""

    trace: tr.Trace
    devices: list  # ids of the chips the cell used, as in the trace
    lo: int  # the traced window, on the trace's clock (ns)
    hi: int
    counters: dict
    peaks: dict

    @property
    def window_s(self):
        return (self.hi - self.lo) / 1e9


def memory_peak(devices):
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def emit(result, checks):
    """Print each number compared beside its limit as the last lines of
    standard error, then the result as the last line of standard
    output, with the same numbers under ``checks``, its last key."""
    for name, (value, limit) in checks.items():
        print(f"check {name} = {value!r} (limit {limit!r})",
              file=sys.stderr, flush=True)
    result["checks"] = {n: {"value": v, "limit": l}
                        for n, (v, l) in checks.items()}
    print(json.dumps(result), flush=True)


@contextlib.contextmanager
def no_compiles(fn, what):
    """Warns when ``fn`` (a jitted function) compiled inside the block."""
    before = fn._cache_size()
    yield
    grew = fn._cache_size() - before
    if grew:
        print(f"warning: {what} compiled {grew} time(s) inside the "
              "measured window", file=sys.stderr, flush=True)


def leaf_name(path):
    return ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)
