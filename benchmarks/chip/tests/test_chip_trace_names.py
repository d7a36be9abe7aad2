"""The per-layer metrics that read the program's names: the flash
forward kernel's calls and time from a synthetic trace, and the training
step's compiles from a fake compile log."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(HERE, "..", "..", "..", "src"))

from chipbench import harness, spec  # noqa: E402
from chipbench.trace import Trace  # noqa: E402

MS = 10**6
FLASH = "%flash_fwd{} = bf16[8,15,2048,64] custom-call(%q, %k, %v)"


def _read(metric, record):
    return spec.load_reader(spec.HERE, metric)(record)


def _record(ops, steps=2):
    """A traced window [100, 900] ms over chips 0 and 1."""
    t = Trace(ops=ops, modules={}, spans=[("bench.window", 100 * MS,
                                           900 * MS)])
    return harness.RunRecord(trace=t, devices=[0, 1], lo=100 * MS,
                             hi=900 * MS, counters={"steps": steps},
                             peaks={})


def _flash_record():
    """Chip 0: two calls of 30 ms, one inside a loop whose own event
    holds it and a 10 ms op nested in it; one call crossing the window's
    start (left out, as the breakdown leaves it out); an op of another
    kernel whose name extends ``flash_fwd``.  Chip 1: two calls of 20 and
    40 ms."""
    ops = {
        0: [(FLASH.format(".1"), 50 * MS, 150 * MS),  # crosses lo
            ("%while.3 = (s32[]) while(%t)", 200 * MS, 400 * MS),
            (FLASH.format(".1"), 210 * MS, 240 * MS),
            ("%copy.9 = bf16[8] copy(%a)", 215 * MS, 225 * MS),
            (FLASH.format(""), 500 * MS, 530 * MS),
            ("%flash_fwd_v2.1 = f32[8] custom-call(%q)", 600 * MS,
             700 * MS)],
        1: [(FLASH.format(".7"), 300 * MS, 320 * MS),
            (FLASH.format(".8"), 400 * MS, 440 * MS)],
    }
    return _record(ops)


def test_flash_fwd_reads_self_time_per_step_and_chip():
    rec = _flash_record()
    # chip 0: (30 - 10) + 30 = 50 ms; chip 1: 60 ms; over 2 chips, 2 steps
    assert _read("kernel.flash_fwd_ms", rec) == pytest.approx(110 / 2 / 2)
    assert _read("kernel.flash_fwd_calls", rec) == pytest.approx(4 / 2 / 2)


def test_flash_fwd_on_one_chip_of_two():
    rec = _record({0: [(FLASH.format(".2"), 200 * MS, 260 * MS)], 1: []},
                  steps=1)
    assert _read("kernel.flash_fwd_ms", rec) == pytest.approx(30)
    assert _read("kernel.flash_fwd_calls", rec) == pytest.approx(0.5)


@pytest.mark.parametrize("metric", ["kernel.flash_fwd_ms",
                                    "kernel.flash_fwd_calls"])
def test_missing_kernel_reads_nothing(metric):
    other = {0: [("%_flash_attention_pallas.16 = bf16[8] custom-call(%q)",
                  200 * MS, 300 * MS)], 1: []}
    assert _read(metric, _record(other)) is None
    outside = {0: [(FLASH.format(".1"), 0, 50 * MS)], 1: []}
    assert _read(metric, _record(outside)) is None
    assert _read(metric, _record({0: [(FLASH.format(""), 200 * MS,
                                       300 * MS)]}, steps=0)) is None


LOG = {
    "init_state": {"traces": 2, "trace_s": 0.5, "lowerings": 1,
                   "lower_s": 1.0, "compiles": 1, "compile_s": 14.0},
    "train_step": {"traces": 2, "trace_s": 1.5, "lowerings": 2,
                   "lower_s": 2.0, "compiles": 2, "compile_s": 6.0},
    "_per_leaf": {"traces": 1, "trace_s": 9.0, "lowerings": 1,
                  "lower_s": 9.0, "compiles": 1, "compile_s": 9.0},
}


@pytest.fixture
def fake_log(monkeypatch):
    from repro import obs

    def use(log):
        monkeypatch.setattr(obs, "compile_log", lambda: log)

    return use


def test_compile_metrics_read_the_programs_log(fake_log):
    fake_log(LOG)
    rec = _record({})
    assert _read("train.step_compiles", rec) == 2
    assert _read("train.compile_s", rec) == pytest.approx(25.0)


def test_compile_metrics_without_the_programs_read_nothing(fake_log):
    rec = _record({})
    fake_log({"_per_leaf": LOG["_per_leaf"]})
    assert _read("train.step_compiles", rec) is None
    assert _read("train.compile_s", rec) is None
    fake_log({"train_step": LOG["train_step"]})  # init_state not logged
    assert _read("train.step_compiles", rec) == 2
    assert _read("train.compile_s", rec) is None


def test_compile_metrics_without_a_compile_log_read_nothing(monkeypatch):
    """A program that keeps no compile log (no ``repro.obs``)."""
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    rec = _record({})
    assert _read("train.step_compiles", rec) is None
    assert _read("train.compile_s", rec) is None
