"""The traffic generator and the per-layer metric readers at tiny size on
the CPU."""
import os
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(HERE, "..", "..", "..", "src"))

from chipbench import flops, harness, spec  # noqa: E402
from chipbench.peaks import UnknownDevice, peaks  # noqa: E402
from chipbench.trace import Trace  # noqa: E402

lm_tokens = spec.load_module(spec.HERE / "traffic" / "lm_tokens.py").generate


def test_lm_tokens_are_seeded_distinct_rows_of_targets():
    tr = dict(spec.load_cell("smollm360m.train.2k").traffic, batch=4,
              seq_len=64)
    big = 2**31 + 12345
    a = [next(lm_tokens(tr, 300, big))["tokens"] for _ in range(2)]
    feed = lm_tokens(tr, 300, big)
    b = [next(feed)["tokens"] for _ in range(2)]
    np.testing.assert_array_equal(a[0], b[0])  # same seed, same batch
    assert not np.array_equal(b[0], b[1])  # the stream moves on
    assert not np.array_equal(b[0], next(lm_tokens(
        tr, 300, big + 1))["tokens"])
    t = b[0]
    assert t.shape == (4, 64) and t.dtype == np.int32
    assert t.min() >= 1 and t.max() <= 298  # every position is a target
    assert len({r.tobytes() for r in t}) == 4  # rows all differ


def test_seed_key_takes_seeds_past_32_bits():
    k = [tuple(np.asarray(harness.seed_key(s)))
         for s in (5, 2**32 + 5, 2**40 + 5)]
    assert len(set(k)) == 3
    with pytest.raises(ValueError):
        harness.seed_key(-1)


def test_flops_per_token_is_the_hand_count():
    """smollm-360m: 6 x 361,758,720 matrix-product weights (all but the
    embedding lookup; the head, tied to the embedding, counts) plus causal
    attention 6 L S H D = 6 x 32 x 2048 x 960."""
    cfg = spec.load_cell("smollm360m.train.2k").config
    per_layer = 960 * 960 * 2 + 960 * 320 * 2 + 3 * 960 * 2560
    assert flops.dense_lm_matmul_params(cfg) == 32 * per_layer \
        + 960 * 49152 == 361_758_720
    assert flops.dense_lm_train_flops_per_token(cfg, 2048) == \
        6 * 361_758_720 + 6 * 32 * 2048 * 960 == 2_548_039_680


def test_matmul_weights_are_the_tied_models_parameters():
    """With the head tied to the embedding, the matrix-product weights
    number the system's parameters of the cell's model, less the 960
    RMSNorm weights of each of the 65 norms."""
    from repro.models import init_params

    cell = spec.load_cell("smollm360m.train.2k")
    mcfg = cell.runner().model_config(cell.config)
    shapes = jax.eval_shape(lambda k: init_params(mcfg, k),
                            jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == cell.config["parameters"] == 361_821_120
    assert flops.dense_lm_matmul_params(cell.config) == n - 65 * 960


def test_peaks_are_keyed_by_device_kind():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(UnknownDevice):
        peaks("TPU v9 imaginary")


def _record(counters):
    """A traced window of 1 s on chips 0 and 1: chip 0 busy 0.6 s, chip 1
    0.8 s."""
    s = 10**9
    ops = {0: [("fusion", 0, int(0.3 * s)), ("all-reduce", int(0.5 * s),
                                             int(0.8 * s))],
           1: [("fusion", 0, int(0.8 * s))]}
    t = Trace(ops=ops, modules={}, spans=[("bench.window", 0, s)])
    return harness.RunRecord(trace=t, devices=[0, 1], lo=0, hi=s,
                             counters=counters,
                             peaks={"bf16_flops_per_s": 1e12})


def _read(metric, record):
    return spec.load_reader(spec.HERE, metric)(record)


def test_metric_readers():
    rec = _record({"flops_per_token": 1e3, "tokens": 10**9})
    assert _read("train.mfu", rec) == pytest.approx(100 * 1e12 / 1.0 / 2e12)
    assert _read("idle_share.train", rec) == pytest.approx(100 * (1 - 0.7))


def test_readers_with_nothing_to_read_return_nothing():
    assert _read("train.mfu", _record({})) is None


def test_every_metric_has_a_reader():
    import json

    bench = json.loads((spec.REPO / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert (spec.HERE / "metrics" / f"{m['name']}.py").is_file()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.per_layer and any(m["name"] == "setup_s"
                                      for m in cell.end_to_end)
