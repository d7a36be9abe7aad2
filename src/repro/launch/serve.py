"""Serving launcher: ``python -m repro.launch.serve --arch <id> ...``.

Initializes (random) weights for the selected config, starts the
continuous-batching engine (DESIGN.md §11), feeds it a synthetic request
stream with mixed prompt lengths, and reports decode throughput.

Measurement notes:

* a warmup round (one request per prompt bucket plus a decode step) runs
  *before* the timed region, so jit compilation is excluded from tok/s;
* tok/s counts **decode** tokens only — the prefill echo token is
  reported separately (prefill work scales with prompt length, decode
  throughput is the steady-state serving metric);
* if the engine truncates at ``max_steps`` the launcher says so and
  exits non-zero instead of reporting a rate over unfinished work.
"""
from __future__ import annotations

import argparse
import sys
import time
import warnings


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots per replica")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel replicas (each with its own pool)")
    ap.add_argument("--shards", default="1",
                    help="serve ranks per replica (slot pool sharding); "
                         "'auto' picks from the fitted serve sweep")
    ap.add_argument("--kv-layout", choices=("dense", "paged"),
                    default="dense")
    ap.add_argument("--page-size", type=int, default=4,
                    help="rows per KV page (paged layout)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page-pool size per rank (paged layout; default "
                         "is capacity parity with dense)")
    ap.add_argument("--plan", choices=("none", "auto"), default="none",
                    help="'auto' routes the decode liveness exchange "
                         "through the planner's rewrite rules")
    ap.add_argument("--max-steps", type=int, default=10_000)
    args = ap.parse_args(argv)
    shards = args.shards if args.shards == "auto" else int(args.shards)

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.launch.cache import enable_compilation_cache
    from repro.models import init_params
    from repro.serve import Request, ServeEngine

    enable_compilation_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, max_len=args.max_len,
                         num_slots=args.slots, num_replicas=args.replicas,
                         replica_shards=shards,
                         kv_layout=args.kv_layout, page_size=args.page_size,
                         num_pages=args.num_pages,
                         plan=None if args.plan == "none" else args.plan)

    rng = np.random.RandomState(0)

    def make(i, plen):
        plen = max(1, min(plen, args.max_len - args.max_new_tokens))
        return Request(rid=i,
                       prompt=rng.randint(1, cfg.vocab_size,
                                          (plen,)).astype(np.int32),
                       max_new_tokens=args.max_new_tokens)

    # Warmup: one request per prompt bucket the stream will hit, plus a
    # decode step each — compiles prefill/splice/decode outside the timed
    # region.
    lens = [max(1, args.prompt_len // 2), args.prompt_len]
    for j, plen in enumerate(dict.fromkeys(lens)):
        engine.submit(make(-1 - j, plen))
    engine.run_to_completion(max_steps=args.max_steps)
    engine.reset_stats()

    reqs = [make(i, lens[i % len(lens)]) for i in range(args.requests)]
    for r in reqs:
        engine.submit(r)
    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        done = engine.run_to_completion(max_steps=args.max_steps)
    dt = time.perf_counter() - t0

    decode_tokens = engine.counters["decode_tokens"]
    prefill_tokens = engine.counters["prefill_tokens"]
    steps = engine.counters["steps"]
    print(f"arch={cfg.name} replicas={args.replicas} "
          f"shards={engine.replica_shards} slots={args.slots} "
          f"layout={args.kv_layout} plan={args.plan}: served "
          f"{len(done)}/{len(reqs)} requests in "
          f"{dt:.2f}s over {steps} engine steps")
    print(f"  decode: {decode_tokens} tokens -> {decode_tokens/dt:.1f} tok/s "
          f"(prefill echo: {prefill_tokens} tokens, excluded)")
    if engine.paged:
        print(f"  pages: peak={engine.counters['pages_in_use_peak']}"
              f"/{engine.num_pages - 1} "
              f"deferrals={engine.counters['admission_deferrals']}")
    print("  phase seconds: " + ", ".join(
        f"{k}={v:.3f}" for k, v in engine.phase_seconds.items()))
    for r in done[:3]:
        print(f"  req {r.rid}: {r.generated[:8]}...")
    if engine.truncated:
        msgs = "; ".join(str(w.message) for w in caught
                         if issubclass(w.category, RuntimeWarning))
        print(f"TRUNCATED: {msgs}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
