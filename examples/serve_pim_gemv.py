"""Serving example (deliverable b): continuous-batching decode with
PIM-resident (bit-plane quantized) weights — the paper's GEMV engine as a
first-class serving feature.

Compares dense vs 8-bit bit-serial (group=1) vs 8-bit slice4-style
(group=2, Booth-radix-4 analogue) serving: same tokens, and the packed
fraction / HBM-byte reduction that sets decode speed on the target TPU.

Run:  PYTHONPATH=src python examples/serve_pim_gemv.py

Quickstart — paged serving (DESIGN.md §8). The block-paged KV cache
lifts the dense cache's shared-prompt-length restriction: requests with
different (unpadded) prompt lengths batch together, slots refill at any
tick, and finished requests' pages recycle through a free list.

    from repro.serve import ContinuousBatcher, Request, ServeConfig, ServeEngine

    # batch engine: flip ServeConfig.paged (dense path stays the default)
    eng = ServeEngine(cfg, params, ServeConfig(max_new_tokens=8, paged=True,
                                               block_size=16))
    tokens = eng.generate(prompts)           # same greedy tokens as dense

    # continuous batching over ragged prompts (no prompt_len needed)
    cb = ContinuousBatcher(cfg, params, n_slots=4, cache_len=64,
                           paged=True, block_size=16)
    cb.submit(Request(uid=0, prompt=short_prompt, max_new_tokens=8))
    cb.submit(Request(uid=1, prompt=long_prompt, max_new_tokens=8))
    results = cb.run_until_drained()

Shared-prefix sharing (DESIGN.md §9): with `prefix=True` the batcher
indexes every served prompt's full KV pages in a radix trie; requests
opening with the same tokens map those pages refcounted into their own
block table and prefill only the uncached suffix (bit-identical greedy
tokens, far fewer prefill tokens and page draws):

    cb = ContinuousBatcher(cfg, params, n_slots=4, cache_len=64,
                           paged=True, block_size=16, prefix=True)

Telemetry (DESIGN.md §13): attach a `ServeTelemetry` to trace the
request lifecycle (TTFT/TPOT/queue-delay percentiles), per-tick pool
gauges, and per-launch streamed-byte accounting — observation only,
tokens are bit-identical and the default telemetry=None path makes
zero registry calls:

    from repro.obs import ServeTelemetry
    tel = ServeTelemetry(events_path="events.jsonl")
    cb = ContinuousBatcher(cfg, params, n_slots=4, cache_len=64,
                           paged=True, block_size=16, telemetry=tel)
    ...
    cb.run_until_drained()
    tel.latency_summary()["ttft_s"]["p99"]   # exact percentiles
    tel.registry.prometheus()                # text snapshot

CLI:  PYTHONPATH=src python -m repro.launch.serve --smoke --paged --quantize
      PYTHONPATH=src python -m repro.launch.serve --smoke --paged --prefix --metrics
Bench: PYTHONPATH=src python -m benchmarks.serve_bench   (dense vs paged)
       PYTHONPATH=src python -m benchmarks.prefix_bench  (shared prefix)
"""

import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import init_lm
from repro.models.transformer import count_params
from repro.quant.bitplane import PimQuantConfig
from repro.serve import ContinuousBatcher, Request, ServeConfig, ServeEngine


def main():
    cfg = get_config("qwen2-1.5b", smoke=True)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    print(f"model: {cfg.name} (smoke), {count_params(params)/1e3:.0f}K params")
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, cfg.vocab_size)

    eng = ServeEngine(cfg, params, ServeConfig(max_cache_len=48, max_new_tokens=8))
    dense = eng.generate(prompts)
    print("dense tokens      :", dense[0].tolist())

    for n_bits, group, tag in [(8, 1, "bit-serial r2"), (8, 2, "slice4 / r4")]:
        e = ServeEngine(cfg, params, ServeConfig(max_cache_len=48, max_new_tokens=8))
        frac = e.quantize(PimQuantConfig(n_bits=n_bits, group=group, min_features=16))
        out = e.generate(prompts)
        agree = float(jnp.mean((out == dense).astype(jnp.float32)))
        print(f"{tag:14s} int{n_bits}: packed {frac:.0%} of param bytes, "
              f"token agreement {agree:.0%} -> {out[0].tolist()}")

    # continuous batching with quantized weights + paged KV cache:
    # ragged prompt lengths in one batch (impossible with the dense cache)
    eng.quantize(PimQuantConfig(n_bits=8, min_features=16))
    cb = ContinuousBatcher(cfg, eng.params, n_slots=2, cache_len=48,
                           paged=True, block_size=8)
    for uid, t in enumerate([8, 5, 11, 3, 8, 6]):
        cb.submit(Request(uid=uid, prompt=prompts[uid % 4][:t],
                          max_new_tokens=4))
    t0 = time.perf_counter()
    results = cb.run_until_drained()
    dt = time.perf_counter() - t0
    n_tok = sum(len(v) for v in results.values())
    print(f"\ncontinuous batching (paged): {len(results)} ragged requests, "
          f"{n_tok} tokens, {dt:.1f}s (2 slots, PIM-resident weights)")


if __name__ == "__main__":
    main()
