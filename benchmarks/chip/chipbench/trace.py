"""Reduction of a profiler trace to device times.

The JAX profiler writes one ``.xplane.pb`` per host.  In it each chip is
a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
operation run on the chip and whose line ``XLA Modules`` holds one event
per execution of a compiled program.  The host plane ``/host:CPU`` holds
the spans the harness writes with ``jax.profiler.TraceAnnotation``
around its calls into the system (names starting ``bench.``).  All
events share one clock, in nanoseconds from the start of the trace; the
profiler aligns the chip's events with the host's to within about a
millisecond (``tests/data/v5e_sample.xplane.pb``: the chip's runs start
about 1.2 ms before the spans of the dispatches that started them).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    ops: dict  # device id -> [(name, start_ns, end_ns)]
    modules: dict  # device id -> [(name, start_ns, end_ns)]
    spans: list  # [(name, start_ns, end_ns)] of the harness's host spans

    def span(self, name):
        """(start, end) of the harness's span ``name``; its last instance."""
        found = [(s, e) for n, s, e in self.spans if n == name]
        if not found:
            raise KeyError(f"trace has no host span {name!r}")
        return found[-1]


def find_xplane(directory):
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def read(path):
    """The device ops, program executions and harness spans of an
    ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, spans = defaultdict(list), defaultdict(list), []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is None:
                    continue
                dest[dev].extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events if ev.name.startswith(SPAN_PREFIX))
    return Trace(ops=dict(ops), modules=dict(modules), spans=spans)


def union(intervals, lo, hi):
    """Merged (start, end) intervals clipped to [lo, hi]."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(trace, dev, lo, hi):
    """Nanoseconds in [lo, hi] in which some operation ran on ``dev``."""
    return sum(e - s for s, e in union(
        [(s, e) for _, s, e in trace.ops.get(dev, ())], lo, hi))


def mean_busy_s(trace, devices, lo, hi):
    return sum(busy_ns(trace, d, lo, hi) for d in devices) / len(devices) / 1e9


def self_times(events):
    """[(name, self ns)] of nested events: each event's duration less that
    of the events directly inside it (a loop's body ops run inside the
    loop's own event)."""
    out, stack = [], []  # stack: [name, end, self ns]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            out.append(tuple(stack.pop()[::2]))
        if stack:
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    out.extend(tuple(x[::2]) for x in stack)
    return out


def top_ops(trace, devices, lo, hi, n=10):
    """The ``n`` operations with most self time on the chip in [lo, hi],
    named by their HLO instruction: [[name, seconds per chip]]."""
    total = defaultdict(int)
    for d in devices:
        inside = [(name, s, e) for name, s, e in trace.ops.get(d, ())
                  if lo <= s and e <= hi]
        for name, ns in self_times(inside):
            total[name.split(" = ")[0]] += ns
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / len(devices) / 1e9] for name, ns in ranked]


def idle_gaps(trace, dev, lo, hi, n=10, outer="bench.window"):
    """The ``n`` longest stretches of [lo, hi] in which ``dev`` ran
    nothing, each named by the innermost harness span around its middle:
    [[span name, seconds]]."""
    busy = union([(s, e) for _, s, e in trace.ops.get(dev, ())], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        around = [(se - ss, name) for name, ss, se in trace.spans
                  if ss <= mid <= se and name != outer]
        out.append([min(around)[1] if around else "none", (e - s) / 1e9])
    return out
