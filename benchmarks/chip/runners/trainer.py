"""Runner of training cells: the system's ``Trainer`` drives a dense LM
from the seed, and its first steps are checked against the plain
reference that the configuration names (``reference.py``).

Set-up builds one trainer with its state and jitted step, and drives it
through the traffic's first ``checked_steps`` steps: the warm-up, which
compiles, and the steps the reference follows.  The same step function,
state and feed then run the measured window.  Once it has closed and the
program's state is freed, the reference follows the same steps from the
same seed and three numbers are compared, each against its limit in
``limits/<workload>.json``:

* ``loss_gap``: the largest gap between the program's loss and the
  reference's over the checked steps;
* ``grad_gap``: over the leaves, the largest gap between the norm of one
  step's gradient as the optimizer got it and the reference's, over the
  larger of the reference's norm of that leaf and of the median leaf.
  The step is the first that ran the compiled program the window runs
  (the last checked step at which the jitted step compiled).  The
  program's norm comes from its second moments: a step turns ``nu`` into
  ``b2 * nu + (1 - b2) * g**2``, so the squared norm of a leaf's ``g``
  is ``(sum(nu) - b2 * sum(nu before)) / (1 - b2)``;
* ``update_gap``: the same for the change of the master weights over the
  checked steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move under Adam by rounding
  alone).
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import time

import numpy as np

from chipbench import flops, harness, spec

# Published config key -> the system's ModelConfig field.
MODEL_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "hidden_act": "act",
}

# The system's parameter leaves -> the reference's.
LEAVES = {
    "embed": "embed",
    "final_norm": "final_norm",
    "lm_head.w": "lm_head",
    "units.0.ln1": "layers.ln1",
    "units.0.ln2": "layers.ln2",
    "units.0.attn.wq.w": "layers.wq",
    "units.0.attn.wk.w": "layers.wk",
    "units.0.attn.wv.w": "layers.wv",
    "units.0.attn.wo.w": "layers.wo",
    "units.0.mlp.wi.w": "layers.wi",
    "units.0.mlp.wg.w": "layers.wg",
    "units.0.mlp.wo.w": "layers.w_down",
}


def model_config(cfg):
    """The system's ModelConfig: its registry entry with every size the
    configuration file states, and the file's ``program`` options."""
    from repro.configs import get_config

    return dataclasses.replace(
        get_config(cfg["registry"]),
        **{field: cfg[key] for key, field in MODEL_KEYS.items()},
        **cfg.get("program", {}))


@functools.lru_cache(maxsize=None)
def _leafwise(fn):
    import jax

    return jax.jit(lambda xs: [fn(x) for x in xs])


def _per_leaf(tree, fn):
    """{reference leaf name: fn(leaf)} of a pytree of the system's
    parameter leaves, computed on the device."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    values = _leafwise(fn)([x for _, x in flat])
    return {LEAVES[harness.leaf_name(p)]: float(v)
            for (p, _), v in zip(flat, values)}


def _sum(x):
    return x.sum()


def _norm(x):
    return (x * x).sum() ** 0.5


def _delta_norms(master, mcfg, key):
    """{reference leaf name: norm of master - initial weights}."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_params

    delta = jax.jit(lambda m, k: jax.tree.map(
        lambda a, b: a - b.astype(jnp.float32), m, init_params(mcfg, k)))(
        master, key)
    return _per_leaf(delta, _norm)


def grad_norms(nu_sums, b2):
    """Per-leaf norms of each step's gradient from the per-leaf sums of
    the second moment after each step (and zeros before the first)."""
    before = dict.fromkeys(nu_sums[0], 0.0)
    out = []
    for now in nu_sums:
        out.append({k: max(now[k] - b2 * before[k], 0.0) ** 0.5
                    / (1 - b2) ** 0.5 for k in now})
        before = now
    return out


def gaps(prog, ref, skip=()):
    """Largest gap over the leaves between the program's norm and the
    reference's, over the larger of the reference's leaf norm and its
    median leaf norm."""
    med = float(np.median(list(ref.values())))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med)
               for k in ref if k not in skip)


def readings(prog, ref, grad_step):
    """The three numbers compared, from the program's readings and the
    reference's; ``grad_step`` (from 1) is the step whose gradients are
    compared."""
    first = ref["grad_norms"][0]
    med = float(np.median(list(first.values())))
    still = {k for k, v in first.items() if v < 1e-3 * med}
    ref_g = ref["grad_norms"][grad_step - 1]
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"],
                                                    ref["losses"])),
        "grad_gap": gaps(prog["grad_norms"][grad_step - 1], ref_g),
        "update_gap": gaps(prog["delta_norms"], ref["delta_norms"], still),
    }


def reference_readings(cell, seed, quant=None, rows=None, block_rows=1):
    """The reference's readings over the cell's checked steps; ``rows``
    keeps only the first rows of every batch (a fault: half the batch)."""
    reference = spec.load_module(cell.root / cell.config["reference"])
    tr = cell.traffic
    feed = cell.generator()(tr, cell.config["vocab_size"], seed)
    batches = [next(feed)["tokens"][:rows] for _ in range(tr["checked_steps"])]
    return reference.train(cell.config, tr["optimizer"],
                           harness.seed_key(seed), batches, quant=quant,
                           block_rows=block_rows)


def run(cell, seed, seconds, trace, devices, t_start):
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.sharding import ShardingProfile
    from repro.train import AdamWConfig, TrainConfig, Trainer

    tr, cfg = cell.traffic, cell.config
    opt = tr["optimizer"]
    mcfg = model_config(cfg)
    mesh = make_host_mesh(shape=(len(devices), 1), devices=devices)
    trainer = Trainer(
        mcfg, mesh,
        ShardingProfile(dp_axes=("data",), tp_axis="model", fsdp_axes=None),
        TrainConfig(opt=AdamWConfig(**opt), grad_reduce=tr["grad_reduce"],
                    transport=tr["transport"]))
    key = harness.seed_key(seed)
    t = time.perf_counter()
    state = trainer.init_state(key)
    step = trainer.step_fn()
    feed = cell.generator()(tr, cfg["vocab_size"], seed)
    tokens_per_step = tr["batch"] * tr["seq_len"]

    prog, nu_sums = {"losses": []}, []
    phases = {"start_s": t - t_start, "init_s": time.perf_counter() - t}
    grad_step, compiled = 1, 0
    for i in range(tr["checked_steps"]):
        t = time.perf_counter()
        *state, loss, _ = step(*state, trainer.place_batch(next(feed)))
        prog["losses"].append(float(loss))
        phases[f"step{i + 1}_s"] = time.perf_counter() - t
        nu_sums.append(_per_leaf(state[1]["nu"], _sum))
        if step._cache_size() > compiled:
            grad_step, compiled = i + 1, step._cache_size()
    prog["grad_norms"] = grad_norms(nu_sums, opt["b2"])
    prog["delta_norms"] = _delta_norms(state[1]["master"], mcfg, key)

    steps = 0
    with harness.no_compiles(step, "the training step"), \
            harness.Window(trace) as win:
        with harness.span("bench.feed"):
            batch = trainer.place_batch(next(feed))
        while True:
            with harness.span("bench.dispatch"):
                *state, loss, _ = step(*state, batch)
            with harness.span("bench.feed"):
                batch = trainer.place_batch(next(feed))
            with harness.span("bench.wait"):
                loss.block_until_ready()
            steps += 1
            if win.elapsed() >= seconds:
                break
        win.end()
    setup_s = win.t0 - t_start
    peak = harness.memory_peak(devices)
    del state, loss, batch, trainer, step
    gc.collect()

    t = time.perf_counter()
    ref = reference_readings(cell, seed)
    got = readings(prog, ref, grad_step)
    limits = cell.limits
    return harness.Outcome(
        end_to_end={"setup_s": setup_s,
                    "train_tokens_per_s": steps * tokens_per_step
                    / win.seconds},
        checks={k: (float(v), float(limits[k])) for k, v in got.items()},
        attempted=steps, failed=0, memory_peak_bytes=peak, window=win,
        counters={"tokens": steps * tokens_per_step, "steps": steps,
                  "flops_per_token": flops.dense_lm_train_flops_per_token(
                      cfg, tr["seq_len"])},
        notes={**phases, "reference_s": time.perf_counter() - t,
               "grad_step": grad_step,
               "grad_gaps": [gaps(p, r) for p, r in zip(prog["grad_norms"],
                                                        ref["grad_norms"])],
               "losses": prog["losses"], "reference_losses": ref["losses"]},
    )
