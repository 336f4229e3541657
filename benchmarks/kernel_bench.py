"""Kernel micro-benchmarks: bit-plane GEMV vs dense matmul, plus the
paged-attention gather.

Wall time on this CPU host is NOT the TPU story (interpret-mode Pallas is
a correctness tool); the `derived` column carries the quantity that
matters on the target: HBM bytes moved per GEMV and the bandwidth
amplification over bf16 (the paper's '100% useful bandwidth' objective),
and — for the paged kernels — the bytes the block walk actually streams
per call vs what the old whole-pool BlockSpec would have copied into
VMEM (the data-movement win of the scalar-prefetch rewrite, DESIGN §10).
"""

from __future__ import annotations

import json
import os
import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Row = Tuple[str, float, str]


def _bench(fn, *args, iters=3) -> float:
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def kernel_bench() -> List[Row]:
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    rows: List[Row] = []
    K, M, B = 2048, 2048, 8
    w = jnp.asarray(rng.normal(size=(K, M)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(B, K)), jnp.float32)

    dense_us = _bench(jax.jit(lambda x, w: x @ w), x, w)
    dense_bytes = K * M * 2  # bf16 resident weight
    rows.append((
        "kernel/dense_gemv_2048", dense_us,
        f"weight_bytes={dense_bytes};amplification=1.0x",
    ))

    for n_bits, group in [(8, 1), (4, 1), (2, 1), (8, 2), (8, 4)]:
        planes, scale = ops.quantize_and_pack(w, n_bits, group, impl="ref")
        fn = jax.jit(
            lambda x, p, s: ops.bitplane_matmul(
                x, p, s, n_bits=n_bits, group=group, impl="ref"
            )
        )
        us = _bench(fn, x, planes, scale)
        pbytes = ops.packed_bytes(K, M, n_bits, group)
        amp = dense_bytes / pbytes
        y = fn(x, planes, scale)
        rel = float(jnp.linalg.norm(y - x @ w) / jnp.linalg.norm(x @ w))
        rows.append((
            f"kernel/bitplane_gemv_2048_n{n_bits}g{group}", us,
            f"weight_bytes={pbytes};amplification={amp:.1f}x;rel_err={rel:.4f}",
        ))

    # pallas interpret-mode correctness spot check at bench shape
    from repro.kernels.bitplane_gemv import bitplane_gemv
    planes, scale = ops.quantize_and_pack(w[:256, :256], 8, 1, impl="ref")
    x_s = x[:, :256]
    x_r = ref.prepare_x_ref(x_s, 1)
    t0 = time.perf_counter()
    raw = bitplane_gemv(x_r, planes, n_bits=8, block_m=128, block_k8=16,
                        interpret=True)
    us = (time.perf_counter() - t0) * 1e6
    y = (raw - 128.0 * jnp.sum(x_s, -1, keepdims=True)) * scale[None]
    y_ref = ref.bitplane_matmul_ref(x_s, planes, scale, 8, 1)
    err = float(jnp.max(jnp.abs(y - y_ref)))
    rows.append((
        "kernel/pallas_interpret_256", us, f"allclose_err={err:.2e}",
    ))
    return rows


def paged_attention_bench() -> List[Row]:
    """Paged decode + prefill kernels (DESIGN.md §10-§11): interpret-mode
    parity error vs the jnp oracles, the per-call KV bytes the
    scalar-prefetch block walk streams (max_blocks pages per slot)
    against the whole-pool copy the pre-rewrite BlockSpec forced into
    every grid step, and a raggedness sweep of the length-bucketed
    dispatch (streamed bytes + interpret walltime vs the unbucketed
    walk). Asserts that on ragged (geometric-length) workloads the
    bucketed dispatch streams <= 50% of the unbucketed bytes with
    bit-identical valid-row outputs, and that the obs/perf analytic
    prediction matches the measured streamed pages within 1% (exact on
    plan-derived counts, DESIGN.md §14). Writes
    ``results/paged_kernel_bench.json``."""
    from repro.core.tpu_gold import TPU_V5E
    from repro.kernels import ops, ref
    from repro.obs import perf
    from repro.kernels.paged_attention import (
        paged_decode_attention,
        paged_decode_attention_bucketed,
    )
    from repro.kernels.paged_prefill import paged_prefill_attention

    rng = np.random.default_rng(0)
    B, T, H, KV, hd, bs, nb, mb = 4, 8, 8, 2, 16, 8, 32, 4
    # byte accounting derives from the modeled pool dtype, never a
    # hardcoded itemsize literal — the int8 leg below re-derives its own
    # page bytes from the actual quantized pools (DESIGN.md §16)
    kv_pool_dtype = jnp.bfloat16
    itemsize = jnp.dtype(kv_pool_dtype).itemsize
    q1 = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    qt = jnp.asarray(rng.normal(size=(B, T, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, KV, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, KV, bs, hd)), jnp.float32)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), jnp.int32
    )
    lengths = jnp.asarray([mb * bs, 13, 1, 0], jnp.int32)
    start = jnp.asarray([0, 8, 0, 8], jnp.int32)
    total = jnp.asarray([mb * bs, 13, 5, 9], jnp.int32)
    win = jnp.asarray(mb * bs, jnp.int32)

    page_bytes = bs * KV * hd * itemsize
    walk_bytes = 2 * mb * page_bytes            # K+V pages per slot
    pool_bytes = 2 * nb * page_bytes            # old whole-pool copy
    report = {
        "shape": {"slots": B, "suffix_t": T, "heads": H, "kv_heads": KV,
                  "head_dim": hd, "block_size": bs, "pool_blocks": nb,
                  "max_blocks_per_slot": mb},
        "kv_bytes_streamed_per_slot": walk_bytes,
        "kv_bytes_whole_pool_per_slot": pool_bytes,
        "gather_reduction": round(1.0 - walk_bytes / pool_bytes, 3),
    }
    rows: List[Row] = []
    for name, fn, oracle, args in (
        ("decode", paged_decode_attention, ref.paged_attention_ref,
         (q1, kp, vp, bt, lengths, win)),
        ("prefill", paged_prefill_attention, ref.paged_prefill_ref,
         (qt, kp, vp, bt, start, total, win)),
    ):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args, interpret=True))
        us = (time.perf_counter() - t0) * 1e6
        err = float(jnp.max(jnp.abs(out - oracle(*args))))
        assert err < 2e-5, (name, err)
        report[name] = {"interpret_us": round(us, 1), "max_abs_err": err}
        rows.append((
            f"kernel/paged_{name}_b{B}", us,
            f"max_abs_err={err:.2e};walk_bytes={walk_bytes};"
            f"whole_pool_bytes={pool_bytes};"
            f"gather_reduction={report['gather_reduction']:.0%}",
        ))

    # -- length-bucketed dispatch raggedness sweep (DESIGN.md §11) --------
    bB, bbs, bmb, bnb = 8, 8, 32, 64
    bq = jnp.asarray(rng.normal(size=(bB, H, hd)), jnp.float32)
    bkp = jnp.asarray(rng.normal(size=(bnb, KV, bbs, hd)), jnp.float32)
    bvp = jnp.asarray(rng.normal(size=(bnb, KV, bbs, hd)), jnp.float32)
    bbt = jnp.asarray(
        rng.integers(1, bnb, size=(bB, bmb)), jnp.int32
    )
    cap = bmb * bbs
    profiles = {
        # every slot at capacity: the plan degenerates and falls back to
        # the single launch — bucketing must never stream MORE
        "uniform_full": np.full((bB,), cap, np.int64),
        # the acceptance workload: geometric lengths, most slots hold a
        # page or two of a 32-page-deep table
        "geometric": np.minimum(rng.geometric(0.08, size=bB), cap),
        # half long, half short — the mixed continuous-batching shape
        "mixed": np.where(np.arange(bB) % 2 == 0, cap,
                          rng.integers(1, 3 * bbs, size=bB)),
    }
    bwin = jnp.asarray(cap, jnp.int32)
    page_b = bbs * KV * hd * itemsize
    unbucketed_pages = bB * bmb
    report["bucketed"] = {
        "shape": {"slots": bB, "block_size": bbs, "table_depth": bmb,
                  "pool_blocks": bnb},
        "kv_bytes_unbucketed": 2 * unbucketed_pages * page_b,
        "profiles": {},
    }
    model_error_max = 0.0
    for pname, lens in profiles.items():
        lens_j = jnp.asarray(lens, jnp.int32)
        plan, perm = ops.make_bucket_plan(lens, bbs, bmb)
        streamed = ops.plan_streamed_pages(plan, bB, bmb)
        # predicted-vs-measured (DESIGN.md §14): obs/perf re-derives the
        # dispatch's streamed pages from the walk-entry needs alone; on
        # plan-derived byte counts the prediction must be EXACT
        needs = -(-np.maximum(lens.astype(np.int64), 1) // bbs)
        predicted = perf.predict_streamed_pages(needs, bB, bmb)
        model_error = (
            abs(predicted - streamed) / streamed if streamed else 0.0
        )
        model_error_max = max(model_error_max, model_error)
        assert model_error <= 0.01, (
            f"bucketed/{pname}: predicted {predicted} pages vs "
            f"measured {streamed} — model error {model_error} > 1%"
        )
        single_us = _bench(
            lambda q_, l_: paged_decode_attention(
                q_, bkp, bvp, bbt, l_, bwin, interpret=True
            ), bq, lens_j,
        )
        if plan is None:
            buck_us, exact = single_us, True
        else:
            buck_us = _bench(
                lambda q_, l_: paged_decode_attention_bucketed(
                    q_, bkp, bvp, bbt, l_, bwin, plan, perm, interpret=True
                ), bq, lens_j,
            )
            a = np.asarray(paged_decode_attention(
                bq, bkp, bvp, bbt, lens_j, bwin, interpret=True
            ))
            b = np.asarray(paged_decode_attention_bucketed(
                bq, bkp, bvp, bbt, lens_j, bwin, plan, perm, interpret=True
            ))
            exact = bool(np.array_equal(a[lens > 0], b[lens > 0]))
        frac = streamed / unbucketed_pages
        kv_bytes = 2 * streamed * page_b
        report["bucketed"]["profiles"][pname] = {
            "lengths": [int(x) for x in lens],
            "plan": list(plan) if plan is not None else None,
            "kv_pages_streamed": streamed,
            "kv_bytes_streamed": kv_bytes,
            "streamed_fraction": round(frac, 3),
            "kv_pages_predicted": int(predicted),
            "model_error": model_error,
            # HBM-bound launch-time estimate at the device spec — the
            # quantity the roofline autotuner will score candidates by
            "roofline_us_tpu_v5e": round(
                kv_bytes / TPU_V5E.hbm_bandwidth * 1e6, 4
            ),
            "interpret_us_bucketed": round(buck_us, 1),
            "interpret_us_single": round(single_us, 1),
            "valid_rows_bit_exact": exact,
        }
        assert exact, f"bucketed/{pname}: valid rows diverged"
        assert streamed <= unbucketed_pages, pname
        if pname == "geometric":
            # the acceptance bound: ragged decode must stream <= 50%
            assert frac <= 0.5, (pname, frac)
        if pname == "mixed":
            # CI smoke bound: STRICTLY fewer bytes on any ragged load
            assert streamed < unbucketed_pages, (pname, streamed)
        rows.append((
            f"kernel/paged_bucketed_{pname}", buck_us,
            f"streamed_pages={streamed}/{unbucketed_pages};"
            f"fraction={frac:.0%};single_us={single_us:.0f};"
            f"bit_exact={exact};predicted_pages={predicted};"
            f"model_error={model_error:g}",
        ))
    report["bucketed"]["model_error_max"] = model_error_max

    # -- int8 quantized pools (DESIGN.md §16) -----------------------------
    # Quantize the same fp pools to int8 codes + per-page scales, run the
    # SAME kernels (the scale rows ride the double-buffered page walk and
    # dequantize in-register), and pin two headline quantities: the
    # per-page resident/streamed byte ratio vs bf16 (codes at itemsize 1
    # plus a KV-wide f32 scale row) and the end-to-end error vs the fp
    # oracle (the tolerance-parity contract: int8 is lossy by design, the
    # kernel must stay tight against the QUANTIZED oracle).
    from repro.kernels.paged_common import quantize_pages

    kq, ks = quantize_pages(kp)
    vq, vs = quantize_pages(vp)
    int8_page_bytes = (
        bs * KV * hd * jnp.dtype(kq.dtype).itemsize
        + KV * jnp.dtype(ks.dtype).itemsize
    )
    resident_ratio = int8_page_bytes / page_bytes
    report["quantized"] = {
        "pool_dtype": "int8",
        "page_bytes_bf16": page_bytes,
        "page_bytes_int8": int8_page_bytes,
        "resident_bytes_ratio": round(resident_ratio, 4),
    }
    # the §16 acceptance bound: quantized pages stream <= 55% of bf16
    assert resident_ratio <= 0.55, report["quantized"]
    q_err_max = 0.0
    for name, fn, oracle, fp_args, q_args in (
        ("decode", paged_decode_attention, ref.paged_attention_ref,
         (q1, kp, vp, bt, lengths, win),
         (q1, kq, vq, bt, lengths, win)),
        ("prefill", paged_prefill_attention, ref.paged_prefill_ref,
         (qt, kp, vp, bt, start, total, win),
         (qt, kq, vq, bt, start, total, win)),
    ):
        t0 = time.perf_counter()
        out = jax.block_until_ready(
            fn(*q_args, k_scales=ks, v_scales=vs, interpret=True)
        )
        us = (time.perf_counter() - t0) * 1e6
        # tight vs the quantized oracle (same codes, same dequant)
        err_q = float(jnp.max(jnp.abs(
            out - oracle(*q_args, k_scales=ks, v_scales=vs)
        )))
        assert err_q < 2e-5, (name, err_q)
        # tolerance-based vs the fp oracle (pinned int8 budget, §16)
        err_fp = float(jnp.max(jnp.abs(out - oracle(*fp_args))))
        assert err_fp <= 5e-2, (name, err_fp)
        q_err_max = max(q_err_max, err_fp)
        report["quantized"][name] = {
            "interpret_us": round(us, 1),
            "max_abs_err_vs_quantized_oracle": err_q,
            "max_abs_err_vs_fp_oracle": err_fp,
        }
        rows.append((
            f"kernel/paged_{name}_int8_b{B}", us,
            f"err_vs_fp={err_fp:.2e};err_vs_qoracle={err_q:.2e};"
            f"page_bytes={int8_page_bytes}/{page_bytes};"
            f"resident_ratio={resident_ratio:.2%}",
        ))
    report["quantized"]["max_abs_err_vs_fp_oracle"] = q_err_max

    # -- window-aware bucketing on a mixed global/window stack (§12) ------
    # The gemma3-27b geometry: 5:1 local(window 1024):global layers. A
    # length-only plan (DESIGN.md §11) walks a windowed layer's FULL
    # occupancy even though only the trailing ceil(window/bs) blocks are
    # live; per-group plans bucket windowed layers by live trailing
    # pages (their retired head is skipped via the kernels' block_start).
    # Streamed pages are counted across the whole 62-layer stack for one
    # decode tick — the data-movement quantity the layer-major refactor
    # buys on the serving hot path. Asserts a strict stack-level win AND
    # bit-identical valid rows for the walk-start dispatch at a small
    # kernel shape.
    from repro.configs.gemma3_27b import config as gemma3_config
    from repro.models import layer_attn_groups
    from repro.serve.paged_cache import LayerPagePool

    gcfg = gemma3_config()
    wbs, wmb = 64, 64                       # 4096-token table
    cap = wbs * wmb
    groups = layer_attn_groups(gcfg, cap)
    # near-capacity ragged decode lengths: the long-context steady state
    wlens = np.asarray([cap, cap - 700, 3000, 2048, cap, 1500, 2600, cap])
    nslots = wlens.shape[0]
    length_needs = -(-wlens // wbs)
    length_plan, _ = ops.make_bucket_plan(wlens, wbs, wmb)
    streamed_len_only = 0
    streamed_grouped = 0
    per_group = {}
    for window, layers in groups:
        # derive the retired head from the SAME bookkeeping the serving
        # pools use (q_min = length - 1 is the newest query position) so
        # per-group sizing can't silently skew the byte denominators
        gpool = LayerPagePool(0, layers, window, n_slots=1, mb=wmb,
                              n_blocks=2, block_size=wbs, retire=True)
        first = np.asarray([gpool.first_live_block(int(n) - 1)
                            for n in wlens])
        live = np.maximum(length_needs - first, 1)
        gplan, _ = ops.make_bucket_plan(None, wbs, wmb, needs=live)
        g_pages = ops.plan_streamed_pages(gplan, nslots, wmb)
        l_pages = ops.plan_streamed_pages(length_plan, nslots, wmb)
        streamed_grouped += len(layers) * g_pages
        streamed_len_only += len(layers) * l_pages
        per_group[f"window_{window}"] = {
            "n_layers": len(layers),
            "live_pages_per_tick": g_pages,
            "length_only_pages_per_tick": l_pages,
        }
    page_b64 = wbs * KV * hd * itemsize
    win_frac = streamed_grouped / streamed_len_only
    report["windowed"] = {
        "config": "gemma3-27b 5:1 local:global, window 1024",
        "shape": {"slots": nslots, "block_size": wbs, "table_depth": wmb,
                  "n_layers": gcfg.n_layers},
        "lengths": [int(x) for x in wlens],
        "per_group": per_group,
        "stack_pages_per_tick_length_only": int(streamed_len_only),
        "stack_pages_per_tick_window_aware": int(streamed_grouped),
        "kv_bytes_per_tick_length_only": int(2 * streamed_len_only * page_b64),
        "kv_bytes_per_tick_window_aware": int(2 * streamed_grouped * page_b64),
        "streamed_fraction": round(win_frac, 3),
    }
    # the §12 acceptance: window-aware plans must stream strictly fewer
    # bytes than the length-only §11 plans on the mixed stack (5/6 of the
    # layers walk ~window/bs live blocks instead of their full length)
    assert streamed_grouped < streamed_len_only, report["windowed"]
    assert win_frac <= 0.5, report["windowed"]
    # bit-exactness of the walk-start dispatch at a checkable shape: the
    # bucketed windowed launch (live-need plan + block_start) matches the
    # full-depth single launch on every valid row
    sW = 2 * bbs                             # small window: 2 live blocks
    slens = np.minimum(rng.geometric(0.05, size=bB) + sW, bmb * bbs)
    spool = LayerPagePool(0, (0,), sW, n_slots=1, mb=bmb, n_blocks=2,
                          block_size=bbs, retire=True)
    sfirst = np.asarray([spool.first_live_block(int(n) - 1)
                         for n in slens])
    sbt = np.asarray(rng.integers(1, bnb, size=(bB, bmb)), np.int32)
    for i in range(bB):
        sbt[i, : sfirst[i]] = 0              # retired head -> scratch
    live = np.maximum(-(-slens // bbs) - sfirst, 1)
    splan, sperm = ops.make_bucket_plan(None, bbs, bmb, needs=live)
    assert splan is not None
    sargs = (bq, bkp, bvp, jnp.asarray(sbt), jnp.asarray(slens, jnp.int32),
             jnp.asarray(sW, jnp.int32))
    full = np.asarray(paged_decode_attention(*sargs, interpret=True))
    walked = np.asarray(paged_decode_attention_bucketed(
        *sargs, splan, sperm, block_start=jnp.asarray(sfirst, jnp.int32),
        interpret=True,
    ))
    assert np.array_equal(full, walked), "windowed walk-start diverged"
    report["windowed"]["walk_start_bit_exact"] = True
    # the §16 acceptance on the gemma3-27b windowed stack: int8 pages
    # (codes at their true itemsize plus the f32 scale row per page)
    # stream <= 55% of the bf16 page bytes on a decode tick — byte math
    # derived from the actual quantized pool dtypes, not a literal
    int8_page_b64 = (
        wbs * KV * hd * jnp.dtype(kq.dtype).itemsize
        + KV * jnp.dtype(ks.dtype).itemsize
    )
    int8_tick_bytes = int(2 * streamed_grouped * int8_page_b64)
    int8_ratio = int8_tick_bytes / (2 * streamed_grouped * page_b64)
    report["windowed"]["kv_bytes_per_tick_int8"] = int8_tick_bytes
    report["windowed"]["int8_streamed_bytes_ratio"] = round(int8_ratio, 4)
    assert int8_ratio <= 0.55, report["windowed"]
    rows.append((
        "kernel/paged_windowed_stack", 0.0,
        f"stack_pages={streamed_grouped}/{streamed_len_only};"
        f"fraction={win_frac:.0%};walk_start_bit_exact=True;"
        f"int8_bytes_ratio={int8_ratio:.2%}",
    ))

    os.makedirs("results", exist_ok=True)
    with open(os.path.join("results", "paged_kernel_bench.json"), "w") as f:
        json.dump(report, f, indent=1)
    return rows


def bucketed_serve_smoke() -> List[Row]:
    """End-to-end CI guard for the bucketed dispatch (DESIGN.md §11):
    drain one ragged trace through the continuous batcher twice with the
    kernels forced through the Pallas interpreter — bucketed dispatch vs
    the single-launch walk — and assert the generated tokens are
    IDENTICAL while the bucketed plan streams strictly fewer KV pages.
    A deliberately tiny model: the point is the dispatch layer, not the
    math (the kernels' parity matrix lives in tests/)."""
    from repro.configs.base import ModelConfig
    from repro.kernels import ops
    from repro.models import init_lm
    from repro.obs import ServeTelemetry
    from repro.serve import ContinuousBatcher, Request

    cfg = ModelConfig(
        name="bucket-smoke", family="dense", n_layers=2, d_model=16,
        n_heads=2, n_kv_heads=1, d_ff=32, vocab_size=64, dtype="float32",
    )
    params = init_lm(jax.random.PRNGKey(0), cfg)
    bs, cache_len, prompt_lens = 4, 64, [3, 21, 5, 13]

    def drain(strategy):
        # telemetry does the streamed-page accounting (DESIGN.md §13) —
        # `account_paged_launch` derives it from the same bucket plans
        # the dispatch uses, so the bench carries no forked counters
        tel = ServeTelemetry()
        cb = ContinuousBatcher(
            cfg, params, n_slots=2, cache_len=cache_len, paged=True,
            block_size=bs, kernel_impl="pallas_interpret",
            bucket_strategy=strategy, telemetry=tel,
        )
        for uid, t in enumerate(prompt_lens):
            p = jax.random.randint(
                jax.random.fold_in(jax.random.PRNGKey(5), uid), (t,), 0,
                cfg.vocab_size,
            ).astype(jnp.int32)
            cb.submit(Request(uid=uid, prompt=p, max_new_tokens=4))
        t0 = time.perf_counter()
        out = cb.run_until_drained()
        return out, time.perf_counter() - t0, tel

    buck, t_buck, tel_buck = drain("pow2")
    single, t_single, tel_single = drain("none")
    assert buck == single, "bucketed serving diverged from single-launch"
    # the structural win, end-to-end: the pow2 drain's telemetry-counted
    # streamed bytes must undercut the single launch's ("none" builds no
    # plans, so its prefill accounting IS the full walk; decode copies
    # only live pages under either strategy)
    sb_buck = tel_buck.streamed_bytes_total
    sb_single = tel_single.streamed_bytes_total
    assert sb_buck < sb_single, (sb_buck, sb_single)
    # and the per-tick decode quantity, from the shared plan helper:
    # pages one decode tick streams for a ragged 2-slot batch
    mb = cache_len // bs
    plan, _ = ops.make_bucket_plan([4, 22], bs, mb)
    streamed = ops.plan_streamed_pages(plan, 2, mb)
    assert streamed < 2 * mb, (streamed, 2 * mb)
    return [(
        "kernel/bucketed_serve_smoke", t_buck * 1e6,
        f"tokens_equal=True;single_us={t_single * 1e6:.0f};"
        f"tick_pages={streamed}/{2 * mb};"
        f"streamed_bytes={sb_buck}/{sb_single}",
    )]


def reduction_schedule_bench() -> List[Row]:
    """Collective-bytes napkin model per schedule (validated in dist tests)."""
    from repro.core.reduction import collective_bytes_per_device

    rows = []
    shard_mb = 64 * 1024 * 1024
    for p in (16, 256, 512):
        for sched in ("linear", "binary-hopping", "tree"):
            t0 = time.perf_counter()
            b = collective_bytes_per_device(sched, shard_mb, p)
            us = (time.perf_counter() - t0) * 1e6
            rows.append((
                f"reduction/{sched}/P{p}", us,
                f"bytes_per_dev={b/1e6:.0f}MB;vs_tree={b / collective_bytes_per_device('tree', shard_mb, p):.2f}x",
            ))
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--paged-only", action="store_true",
                    help="run just the paged-attention cases (CI smoke: "
                         "kernel parity + bucketed sweep + serve smoke)")
    args = ap.parse_args()
    sections = (
        [paged_attention_bench, bucketed_serve_smoke] if args.paged_only
        else [
            kernel_bench, paged_attention_bench, bucketed_serve_smoke,
            reduction_schedule_bench,
        ]
    )
    print("name,us_per_call,derived")
    for fn in sections:
        for name, us, derived in fn():
            print(f"{name},{us:.1f},{derived}")
