"""Readings of the correctness check's control and planted faults, which
set the upper ends of the limits in ``limits/``.  The benchmark's own runs
do not run this.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1 2 3

For a training cell: the reference computed in float8 (``quant="fp8"``:
the precision below the configuration's bfloat16) in the program's place,
and two faults planted in the reference, "half of the batch left out, the
mean taken over the rest" and "a step that returns its state unchanged";
each compared with the float32 reference by the cell's three numbers,
with the gradients of each checked step in turn.  One JSON line per seed.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def _unchanged(cell, seed):
    """The reference with a step that returns its state unchanged."""
    from chipbench import spec

    runner = cell.runner()
    reference = spec.load_module(cell.root / cell.config["reference"])
    update = reference._adamw
    reference._adamw = lambda w, m, v, g, step, lr, opt: (w, m, v, g)
    try:
        return runner.reference_readings(cell, seed)
    finally:
        reference._adamw = update


def train_readings(cell, seed):
    runner = cell.runner()
    ref = runner.reference_readings(cell, seed)
    faults = {
        "control": runner.reference_readings(cell, seed, quant="fp8"),
        "half_batch": runner.reference_readings(
            cell, seed, rows=cell.traffic["batch"] // 2),
        "state_unchanged": _unchanged(cell, seed),
    }
    steps = range(1, cell.traffic["checked_steps"] + 1)
    return {"reference_losses": ref["losses"],
            **{name: {f"grad_step_{k}": runner.readings(got, ref, k)
                      for k in steps}
               for name, got in faults.items()}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from chipbench import spec

    cell = spec.load_cell(args.workload)
    sys.path.insert(0, str(spec.REPO / "src"))
    import jax
    from repro.launch.cache import enable_compilation_cache

    enable_compilation_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"control.py: needs {cell.chips} TPU chips, JAX found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 1
    for seed in args.seeds:
        t = time.perf_counter()
        rec = train_readings(cell, seed)
        rec.update(workload=cell.name, seed=seed,
                   seconds=time.perf_counter() - t,
                   device=devices[0].device_kind)
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
