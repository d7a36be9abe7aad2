"""``run.py``'s body: find the cell, check the chips, run, report."""
from __future__ import annotations

import argparse
import sys

from . import harness, spec


def parse(argv):
    ap = argparse.ArgumentParser(
        prog="run.py", description="Run one cell of the on-chip benchmark "
        "and print its result as the last line.")
    ap.add_argument("--workload", required=True,
                    help="a workload name from BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace the window, report per-layer metrics")
    return ap.parse_args(argv)


def main(argv, t_start):
    args = parse(argv)
    if args.seed < 0:
        print("run.py: --seed must be >= 0", file=sys.stderr)
        return 2
    cell = spec.load_cell(args.workload)
    src = spec.REPO / "src"
    if not (src / "repro").is_dir():
        print(f"run.py: the system under test is not at {src}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    from repro.launch.cache import enable_compilation_cache

    enable_compilation_cache()  # <checkout>/.jax_cache, a fixed path
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run_cell(cell, args.seed, args.seconds, bool(args.trace),
             devices[:cell.chips], t_start)
    return 0


def run_cell(cell, seed, seconds, trace, devices, t_start, peaks=None):
    """Run ``cell`` on ``devices`` and print its result.  Returns the
    result.  ``peaks`` stands in for the table's row in tests."""
    if trace and peaks is None:
        from .peaks import peaks as lookup

        peaks = lookup(devices[0].device_kind)
    out = cell.runner().run(cell, seed, seconds, trace, devices, t_start)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": all(v <= lim for v, lim in out.checks.values()),
              "attempted": out.attempted, "failed": out.failed}
    if trace:
        from . import trace as tr

        t = out.window.read_trace()
        lo, hi = t.span("bench.window")
        ids = [d.id for d in devices]
        record = harness.RunRecord(trace=t, devices=ids, lo=lo, hi=hi,
                                   counters=out.counters, peaks=peaks)
        metrics = {}
        for m in cell.per_layer:
            value = spec.load_reader(cell.root, m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=tr.mean_busy_s(t, ids, lo, hi),
                      window_s=record.window_s)
        result["breakdown"] = {
            "device_ops": tr.top_ops(t, ids, lo, hi),
            "idle_gaps": tr.idle_gaps(t, ids[0], lo, hi),
        }
    else:
        metrics = {m["name"]: {"value": out.end_to_end[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = device
    if out.notes:
        print("notes " + " ".join(f"{k}={v!r}" for k, v in out.notes.items()),
              file=sys.stderr, flush=True)
    harness.emit(result, out.checks)
    return result
