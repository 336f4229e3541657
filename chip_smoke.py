"""Bring-up check of paged serving on one TPU: ``python chip_smoke.py``.

Drives the normal serving path once at qwen2-1.5b's published widths
(28 layers, d=1536, 12 heads, 2 KV heads, d_ff=8960, vocab 151936) with
random bf16 weights from a seed, in one process on one chip:

1. refuse to run, with a non-zero exit, unless JAX's first device is a
   TPU — there is no CPU or oracle fallback;
2. serve 16 requests with ragged prompts through
   `launch.serve.run` — `ContinuousBatcher(paged=True,
   kernel_impl="pallas")`, 8 slots, 32 new tokens each — after an
   untimed warm-up drain of the same requests, so the timed drain runs
   on compiled steps; 16 requests over 8 slots exercise slot refill,
   page recycling and the bucketed kernel dispatch;
3. compare the logits of the compiled paged prefill and decode steps
   under the native kernels with the same steps under the jnp oracle;
4. compare the bit-plane GEMV (`ops.bitplane_matmul`, the paper's
   kernel) with its oracle at the model's two MLP projection shapes.

Every phase raises on failure, so any failure exits non-zero before the
last line. The last line of standard output is one JSON object naming
the device.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

#: pallas-vs-oracle logit tolerance, as a fraction of the largest oracle
#: logit. Both sides run the same bf16 weights and bf16 activations; the
#: kernels accumulate attention in f32 while the oracle's f32 einsums run
#: at the TPU's default matmul precision, and every layer rounds its
#: output to bf16 (relative step 2^-8), so one-ulp differences enter at
#: each of the 28 layers and compound through the residual stream.
LOGIT_TOL = 5e-2
#: bit-plane GEMV tolerance, as a fraction of the largest oracle output:
#: both sides return bf16 (relative step 2^-8); the kernel sums f32
#: digit-plane partial products and subtracts the offset term, the oracle
#: multiplies the unpacked integer weights in one f32 matmul.
BITPLANE_TOL = 2e-2

ARCH = "qwen2-1.5b"
N_REQUESTS, N_SLOTS, MAX_PROMPT, NEW_TOKENS = 16, 8, 512, 32


def log(msg: str) -> None:
    print(msg, flush=True)


def check_platform():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            f"chip_smoke: needs a TPU, but JAX's first device is on platform "
            f"{dev.platform!r} ({dev.device_kind}); the native kernels have "
            "no CPU fallback here"
        )
    return dev


def serve_phase(serve):
    args = serve.parse_args([
        "--arch", ARCH, "--paged", "--kernel-impl", "pallas",
        "--requests", str(N_REQUESTS), "--slots", str(N_SLOTS),
        "--prompt-len", str(MAX_PROMPT), "--new-tokens", str(NEW_TOKENS),
        "--warmup",
    ])
    out = serve.run(args)
    cfg, pc = out.cfg, out.batcher.pcache
    log(f"config: {cfg.name} n_layers={cfg.n_layers} d_model={cfg.d_model} "
        f"n_heads={cfg.n_heads} n_kv_heads={cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} dtype={cfg.dtype}")
    log(f"param bytes: {out.param_bytes}")
    lens = sorted(int(r.prompt.shape[0]) for r in serve.make_requests(args, cfg))
    log(f"served: {len(out.results)} requests, {out.tokens} tokens "
        f"(prompts {lens[0]}-{lens[-1]} tokens, {N_SLOTS} slots, block "
        f"size {args.block_size}, kernel_impl=pallas, bucket "
        f"strategy {args.bucket_strategy})")
    log(f"set-up s: {out.setup_s:.3f} (weights, batcher, warm-up drain); "
        f"served s: {out.served_s:.3f} "
        f"({out.tokens / out.served_s:.1f} output tok/s, one timed drain)")
    log(f"compiles: {out.compiles_setup} in set-up, "
        f"{out.compiles_served} while serving")
    pool = pc.pools[0]
    log(f"pages: {pc.pages_allocated} drawn from a pool of "
        f"{pool.n_blocks - 1}, {pool.n_free} free after the drain")
    want = N_REQUESTS * NEW_TOKENS
    if out.tokens != want or len(out.results) != N_REQUESTS:
        raise AssertionError(f"served {out.tokens} tokens, expected {want}")
    if pool.n_free != pool.n_blocks - 1:
        raise AssertionError("pages still held after the drain")
    if pc.pages_allocated <= pool.n_blocks - 1:
        raise AssertionError("no page was recycled")
    pc.check_invariants()
    return out, cfg


def logit_phase(cfg, params, n_decode: int = 4):
    """Prefill two ragged prompts into the page pools and decode a few
    tokens, once through the native kernels and once through the oracle,
    from the same inputs; return the largest logit difference over every
    step, relative to the largest oracle logit."""
    from repro.models import init_paged_pool
    from repro.serve.compiled import jit_paged_decode, jit_paged_prefill

    bs, lens = 16, (97, 106)          # ragged, one prefill width (112)
    pad = -(-max(lens) // bs) * bs
    mb = -(-(max(lens) + n_decode) // bs)
    b = len(lens)
    table = np.arange(1, 1 + b * mb, dtype=np.int32).reshape(b, mb)
    key = jax.random.PRNGKey(3)
    prompts = [
        jax.random.randint(jax.random.fold_in(key, i), (t,), 0,
                           cfg.vocab_size).astype(jnp.int32)
        for i, t in enumerate(lens)
    ]
    sides = {}
    for impl in ("pallas", "ref"):
        prefill = jit_paged_prefill(cfg, impl=impl)
        decode = jit_paged_decode(cfg, impl=impl)
        kp, vp = init_paged_pool(cfg, 1 + b * mb, bs)
        steps = []
        for i, (t, p) in enumerate(zip(lens, prompts)):
            toks = jnp.pad(p, (0, pad - t))[None, :]
            logits, kp, vp = prefill(
                params, toks, kp, vp, jnp.asarray(table[i:i + 1]),
                jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
                jnp.asarray([t], jnp.int32), jnp.asarray(t - 1, jnp.int32),
            )
            steps.append(logits[0, -1])
        sides[impl] = (decode, kp, vp, steps)

    # decode both sides on the oracle's greedy tokens
    tok = jnp.stack([jnp.argmax(s) for s in sides["ref"][3]])
    pos = np.asarray(lens, np.int32)
    bt = jnp.asarray(table)
    starts = jnp.zeros((b,), jnp.int32)
    for _ in range(n_decode):
        out = {}
        for impl, (decode, kp, vp, steps) in sides.items():
            logits, kp, vp = decode(
                params, tok[:, None].astype(jnp.int32), kp, vp, bt, starts,
                jnp.asarray(pos),
            )
            steps.extend(logits[:, -1])
            sides[impl] = (decode, kp, vp, steps)
            out[impl] = logits[:, -1]
        tok = jnp.argmax(out["ref"], axis=-1)
        pos = pos + 1
    got = jnp.stack(sides["pallas"][3]).astype(jnp.float32)
    want = jnp.stack(sides["ref"][3]).astype(jnp.float32)
    if not bool(jnp.all(jnp.isfinite(got))):
        raise AssertionError("non-finite logits from the native kernels")
    scale = float(jnp.max(jnp.abs(want)))
    return float(jnp.max(jnp.abs(got - want))) / scale, scale, got.shape


def bitplane_phase(cfg, batch: int = 8, n_bits: int = 8):
    """The paper's bit-plane GEMV against its oracle at the MLP's two
    projection shapes (d_model -> d_ff and back); returns the worst
    relative error and the shapes run."""
    from repro.kernels import ops

    key = jax.random.PRNGKey(5)
    worst, shapes = 0.0, []
    for k, m in ((cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)):
        kw, kx = jax.random.split(jax.random.fold_in(key, k))
        w = jax.random.normal(kw, (k, m), jnp.float32) / np.sqrt(k)
        x = jax.random.normal(kx, (batch, k), jnp.float32).astype(jnp.bfloat16)
        planes, scale = ops.quantize_and_pack(w, n_bits, impl="pallas")
        ref_planes, _ = ops.quantize_and_pack(w, n_bits, impl="ref")
        if not bool(jnp.array_equal(planes, ref_planes)):
            raise AssertionError(f"packed planes differ at {k}x{m}")
        got = ops.bitplane_matmul(x, planes, scale, n_bits=n_bits,
                                  impl="pallas").astype(jnp.float32)
        want = ops.bitplane_matmul(x, planes, scale, n_bits=n_bits,
                                   impl="ref").astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got - want))) / float(
            jnp.max(jnp.abs(want)))
        worst = max(worst, err)
        shapes.append(f"{k}->{m}")
    return worst, shapes


def main() -> int:
    dev = check_platform()
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "src"))
    from repro.launch import serve
    from repro.launch.compile_cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    log(f"device: {dev.platform} {dev.device_kind}, "
        f"{len(jax.devices())} device(s); compile cache {cache_dir}")
    t0 = time.perf_counter()
    out, cfg = serve_phase(serve)

    err, scale, shape = logit_phase(cfg, out.batcher.params)
    log(f"logit error vs ref: {err:.3e} of max |logit| {scale:.3f} over "
        f"{shape[0]} positions (2 prefills, 4 decode steps x 2 slots); "
        f"tolerance {LOGIT_TOL}")
    if not err <= LOGIT_TOL:
        raise AssertionError(f"logit error {err} exceeds {LOGIT_TOL}")

    bp_err, shapes = bitplane_phase(cfg)
    log(f"bit-plane GEMV error vs ref: {bp_err:.3e} at {', '.join(shapes)} "
        f"(8-bit, batch 8); tolerance {BITPLANE_TOL}")
    if not bp_err <= BITPLANE_TOL:
        raise AssertionError(f"bit-plane error {bp_err} exceeds {BITPLANE_TOL}")

    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    log(f"total s: {time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
