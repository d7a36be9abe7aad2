"""The reduction from a profiler trace to device times, on a small trace
recorded on one TPU v5e (``data/v5e_sample.xplane.pb``): three runs of a
matrix-product program, each dispatched, followed by 5 ms of host work,
then waited on, inside ``bench.window``, and one run of a second program
inside ``bench.lax_control``."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import trace  # noqa: E402

SAMPLE = os.path.join(HERE, "data", "v5e_sample.xplane.pb")


@pytest.fixture(scope="module")
def sample():
    return trace.read(SAMPLE)


def test_harness_spans_and_chip_events(sample):
    names = [n for n, _, _ in sample.spans]
    for span, count in (("bench.window", 1), ("bench.dispatch", 3),
                        ("bench.feed", 3), ("bench.wait", 3),
                        ("bench.lax_control", 1)):
        assert names.count(span) == count, span
    assert list(sample.ops) == [0] and list(sample.modules) == [0]
    programs = [n.split("(")[0] for n, s, e in sample.modules[0] if e > s]
    assert sorted(programs) == ["jit_matmul_chain"] * 3 + ["jit_scale_sum"]


def test_device_and_host_clocks_agree_to_a_few_ms(sample):
    """The profiler puts the chip's events and the host's spans on one
    clock, to within about a millisecond: each run of the window's
    program starts near the dispatch that started it."""
    starts = [s for n, s, _ in sample.spans if n in ("bench.dispatch",
                                                     "bench.lax_control")]
    runs = [s for _, s, _ in sample.modules[0]]
    assert len(runs) == len(starts) == 4
    for run, dispatch in zip(runs, starts):
        assert abs(run - dispatch) < 5e6


def test_busy_is_the_union_of_operations(sample):
    lo, hi = sample.span("bench.window")
    busy = trace.busy_ns(sample, 0, lo, hi)
    ops = [(s, e) for _, s, e in sample.ops[0]]
    assert 0 < busy <= min(hi - lo, sum(e - s for s, e in ops))
    # The window holds three program runs and the host's sleeps between
    # them, so the chip is idle for at least those 3 x 5 ms.
    assert hi - lo - busy >= 3 * 5e6
    assert trace.mean_busy_s(sample, [0], lo, hi) == busy / 1e9


def test_breakdown(sample):
    lo, hi = sample.span("bench.window")
    top = trace.top_ops(sample, [0], lo, hi)
    assert 0 < len(top) <= 10
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
    gaps = trace.idle_gaps(sample, 0, lo, hi)
    assert [t for _, t in gaps] == sorted((t for _, t in gaps), reverse=True)
    # The longest gaps are the host's 5 ms sleeps, inside ``bench.feed``.
    assert gaps[0][0] == "bench.feed" and gaps[0][1] >= 5e-3


def test_self_times_subtract_nested_events():
    events = [("loop", 0, 10), ("a", 1, 3), ("b", 4, 8), ("b.in", 5, 6),
              ("after", 12, 13)]
    assert sorted(trace.self_times(events)) == sorted(
        [("loop", 4), ("a", 2), ("b", 3), ("b.in", 1), ("after", 1)])


def test_union_merges_and_clips():
    assert trace.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == \
        [[1, 4], [5, 10]]
    assert trace.union([(0, 1)], 2, 3) == []
