"""The harness end to end at tiny size on the CPU, past its look for a
chip: a configuration, runner, traffic mix, generator and metric added
as files are found by name; the training cell's check passes a sound run
and fails a run whose timed path is broken underneath."""
import contextlib
import io
import json
import os
import sys
import time

import jax
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(HERE, "..", "..", "..", "src"))

from chipbench import cli, spec  # noqa: E402

CELL = "smollm360m.train.2k"
TINY = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=128,
            vocab_size=256)


def tiny(cell):
    cell.config.update(TINY)
    cell.traffic.update(batch=4, seq_len=16)
    return cell


def run(cell, trace=False):
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        cli.run_cell(cell, 2**31 + 5, 0.2, trace, jax.devices()[:1],
                     time.perf_counter(), peaks={"bf16_flops_per_s": 1e12})
    last = json.loads(out.getvalue().splitlines()[-1])
    return last, err.getvalue().splitlines()


def test_cell_added_as_files_is_found_by_name(tmp_path):
    """A throwaway configuration, runner, traffic mix, generator, limits
    and two metrics, written only to a temporary directory, run through
    the harness."""
    for d in ("configs", "runners", "traffic", "limits", "metrics"):
        (tmp_path / d).mkdir()
    cfg = dict(spec.load_cell(CELL).config, **TINY, system="tiny_trainer",
               reference=str(spec.HERE / "reference.py"))
    (tmp_path / "configs" / "tiny-lm.json").write_text(json.dumps(cfg))
    (tmp_path / "runners" / "tiny_trainer.py").write_text(
        "from chipbench import spec\n"
        "real = spec.load_module(spec.HERE / 'runners' / 'trainer.py')\n"
        "runs = []\n"
        "def run(cell, *args):\n"
        "    runs.append(cell.name)\n"
        "    return real.run(cell, *args)\n")
    (tmp_path / "traffic" / "tiny_tokens.py").write_text(
        "from chipbench import spec\n"
        "real = spec.load_module(spec.HERE / 'traffic' / 'lm_tokens.py')\n"
        "def generate(traffic, vocab_size, seed):\n"
        "    for batch in real.generate(traffic, vocab_size, seed):\n"
        "        yield {'tokens': batch['tokens'] % 7 + 1}\n")
    tr = dict(spec.load_cell(CELL).traffic, batch=4, seq_len=16,
              generator="tiny_tokens")
    (tmp_path / "traffic" / "tiny.mix.json").write_text(json.dumps(tr))
    (tmp_path / "limits" / "tiny-lm.tiny.json").write_text(json.dumps(
        spec.load_cell(CELL).limits))
    (tmp_path / "metrics" / "tiny.steps.py").write_text(
        "def read(run):\n    return run.counters['steps']\n")
    (tmp_path / "metrics" / "tiny.nothing.py").write_text(
        "def read(run):\n    return None\n")
    bench = {
        "configs": [{"name": "tiny-lm", "file": "configs/tiny-lm.json"}],
        "workloads": [{"name": "tiny-lm.tiny", "config": "tiny-lm",
                       "traffic": "tiny.mix", "chips": 1}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s"},
            {"name": "train_tokens_per_s", "unit": "tokens/s",
             "workloads": ["tiny-lm.tiny"]},
            {"name": "coll_GBps", "unit": "GB/s", "workloads": ["other"]}],
        "per_layer": [
            {"name": "tiny.steps", "unit": "steps",
             "moves": "train_tokens_per_s"},
            {"name": "tiny.nothing", "unit": "%",
             "moves": "train_tokens_per_s"},
            {"name": "coll.device_GBps", "unit": "GB/s", "moves": "coll_GBps"},
        ],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("tiny-lm.tiny", bench_file=tmp_path /
                          "BENCHMARK.json", root=tmp_path)
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "train_tokens_per_s"]
    assert [m["name"] for m in cell.per_layer] == ["tiny.steps",
                                                   "tiny.nothing"]

    feed = cell.generator()(cell.traffic, cell.config["vocab_size"], 1)
    assert next(feed)["tokens"].max() <= 7  # the throwaway generator

    traced, _ = run(cell, trace=True)
    assert cell.runner().runs == ["tiny-lm.tiny"]
    assert traced["correct"] is True, traced["checks"]  # a sound run
    assert traced["metrics"] == {"tiny.steps": {
        "value": traced["attempted"], "unit": "steps"}}
    assert list(traced) == ["correct", "attempted", "failed", "breakdown",
                            "metrics", "device", "checks"]


@contextlib.contextmanager
def broken(monkeypatch, fault):
    """Breaks the system's training step underneath the cell."""
    import repro.train.trainer as trainer

    if fault == "state_unchanged":
        def update(cfg, grads, state, param_dtype="bfloat16"):
            params = jax.tree.map(lambda w: w.astype(param_dtype),
                                  state["master"])
            return params, state, {}
        monkeypatch.setattr(trainer, "adamw_update", update)
    elif fault == "half_batch":
        loss = trainer.loss_and_metrics

        def half(params, batch, *a, **k):
            rows = batch["tokens"].shape[0] // 2
            return loss(params, {"tokens": batch["tokens"][:rows]}, *a, **k)
        monkeypatch.setattr(trainer, "loss_and_metrics", half)
    yield


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_training_check_fails_a_broken_step(monkeypatch, fault):
    with broken(monkeypatch, fault):
        r, stderr = run(tiny(spec.load_cell(CELL)))  # broken: not correct
    checks = {k: v["value"] for k, v in r["checks"].items()}
    assert [l.split()[1] for l in stderr[-3:]] == list(checks) == [
        "loss_gap", "grad_gap", "update_gap"]
    assert set(r["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert r["correct"] is False, checks
    if fault == "state_unchanged":
        assert checks["update_gap"] == pytest.approx(1.0)
