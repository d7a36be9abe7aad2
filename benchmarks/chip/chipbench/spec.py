"""A cell of the benchmark, read from ``BENCHMARK.json`` and the files it
names.  A later cell, configuration, traffic mix, runner, generator or
metric is added by adding files and entries; nothing here names one.

Under the benchmark's root (this package's parent directory) a cell
finds, by name:

* ``configs/<file>.json``: its configuration; its ``system`` key names
  the runner ``runners/<system>.py``, whose ``run(cell, seed, seconds,
  trace, devices, t_start)`` drives the system under test;
* ``traffic/<traffic>.json``: its traffic mix; its ``generator`` key
  names the generator ``traffic/<generator>.py``, whose ``generate``
  makes the mix from its parameters and the seed;
* ``limits/<workload>.json``: the limit of each number its correctness
  check compares;
* ``metrics/<metric>.py``: one ``read(run)`` per per-layer metric.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]  # benchmarks/chip
REPO = HERE.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list  # BENCHMARK.json entries this cell reports
    per_layer: list
    root: Path  # directory holding configs/, traffic/, limits/, ...

    def runner(self):
        return load_module(self.root / "runners"
                           / f"{self.config['system']}.py")

    def generator(self):
        """The ``generate`` function of the traffic mix's generator."""
        return load_module(self.root / "traffic"
                           / f"{self.traffic['generator']}.py").generate


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} {name!r}; it has "
                   f"{sorted(e['name'] for e in entries)}")


def _read_json(path):
    return json.loads(Path(path).read_text())


def load_cell(name, bench_file=REPO / "BENCHMARK.json", root=HERE):
    """The workload ``name`` with its configuration, traffic, limits and
    the metrics it reports.  ``bench_file`` and ``root`` default to the
    repository's own."""
    bench_file = Path(bench_file)
    bench = _read_json(bench_file)
    w = _by_name(bench["workloads"], name, "workload")
    c = _by_name(bench["configs"], w["config"], "configuration")
    root = Path(root)

    def listed(m):
        return name in m["workloads"] if "workloads" in m else None

    e2e = [m for m in bench["end_to_end"] if listed(m) in (True, None)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if listed(m) or (listed(m) is None and m["moves"] in e2e_names)
    ]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_read_json(bench_file.parent / c["file"]),
        traffic=_read_json(root / "traffic" / f"{w['traffic']}.json"),
        limits=_read_json(root / "limits" / f"{name}.json"),
        end_to_end=e2e,
        per_layer=per_layer,
        root=root,
    )


def load_module(path):
    """The Python file ``path`` as a module, loaded once per file."""
    return _load(Path(path).resolve())


@functools.lru_cache(maxsize=None)
def _load(path):
    name = "chipbench_" + re.sub(r"\W", "_", path.relative_to(
        path.parents[1]).with_suffix("").as_posix())
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root, metric):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    return load_module(Path(root) / "metrics" / f"{metric}.py").read
