"""Generator of language-model training traffic: endless batches of token
rows drawn from the seed.  A mix (``traffic/<mix>.json`` with
``"generator": "lm_tokens"``) gives ``batch``, ``seq_len``,
``pareto_shape``, ``pareto_scale`` and ``repeat_prob``."""
from __future__ import annotations

import itertools

import numpy as np


def generate(traffic, vocab_size, seed):
    """Endless batches ``{"tokens": int32 (batch, seq_len)}`` of a
    Zipf-like unigram stream with a bigram drift, so that a model has
    something to learn: each position keeps a heavy-tailed draw with
    probability ``1 - repeat_prob`` and otherwise follows its left
    neighbour by a fixed map.  Ids lie in [1, vocab_size - 2], so every
    position is a target.  Every batch has the same shape; step ``n`` of
    seed ``s`` is drawn from the stream ``(s, n)``."""
    B, S = traffic["batch"], traffic["seq_len"]
    V = vocab_size
    for step in itertools.count():
        rng = np.random.default_rng([seed, step])
        heavy = np.minimum(rng.pareto(traffic["pareto_shape"], (B, S))
                           * traffic["pareto_scale"], 1e6)
        base = heavy.astype(np.int64) % (V - 2) + 1
        follow = (np.roll(base, 1, axis=1) * 7 + 3) % (V - 2) + 1
        keep = rng.random((B, S)) >= traffic["repeat_prob"]
        yield {"tokens": np.where(keep, base, follow).astype(np.int32)}
