"""§Perf hillclimb harness: hypothesis -> change -> re-lower -> record.

Each iteration compiles a VARIANT of one of the three chosen cells and
reports the roofline-term deltas vs its baseline artifact. Variants are
expressed as (rules override, config override, quantized flag) so every
change is reproducible from this file.

Run:  PYTHONPATH=src python -m benchmarks.perf_iterations --cell <name>
Cells:
  whisper-train   worst useful-flops ratio (sharding pathology)
  qwen-train      most collective-bound (TP vs FSDP schedule)
  llama4-decode   most technique-representative (PIM bit-plane serving)
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import dataclasses
import json
import time

import jax

from repro.configs import SHAPES, get_config
from repro.dist.sharding import SERVE_RULES, TRAIN_RULES, sharding_rules
from repro.launch import specs as S
from repro.launch.dryrun import build_lowered
from repro.launch.mesh import make_mesh, make_production_mesh, mesh_chips
from repro.launch.roofline import (
    analyze_compiled,
    analytic_bytes_for_cell,
    model_flops_for_cell,
)


def run_variant(arch, shape_name, *, rules=None, cfg_override=None,
                quantized=False, n_microbatches=2, label="variant",
                analytic_mem=False, mesh_shape=None):
    cfg = get_config(arch)
    if cfg_override:
        cfg = dataclasses.replace(cfg, **cfg_override)
    shape = SHAPES[shape_name]
    if mesh_shape is not None:  # same 256 chips, different logical split
        mesh = make_mesh(mesh_shape, ("data", "model"))
    else:
        mesh = make_production_mesh()
    base_rules = TRAIN_RULES if shape.kind == "train" else SERVE_RULES
    use_rules = {**base_rules, **(rules or {})}
    t0 = time.time()
    with mesh, sharding_rules(mesh, use_rules):
        lowered = build_lowered(cfg, shape, mesh, n_microbatches=n_microbatches,
                                quantized=quantized)
        compiled = lowered.compile()
        params_shapes = S.abstract_params(
            cfg, quantized=quantized and shape.kind != "train")
        mf = model_flops_for_cell(cfg, shape, params_shapes)
        ab = analytic_bytes_for_cell(cfg, shape, params_shapes)
        terms, detail = analyze_compiled(
            f"{arch}|{shape_name}|{label}", compiled, mesh_chips(mesh), mf,
            analytic_bytes=ab, kernel_true_bytes=quantized or analytic_mem,
        )
    out = {
        "label": label,
        "compile_s": round(time.time() - t0, 1),
        **{k: v for k, v in terms.as_dict().items()},
        "collectives": {k: round(v / 1e9, 2)
                        for k, v in detail["collectives_by_kind"].items()},
        "temp_gb": round(
            detail["memory_analysis"].get("temp_size_in_bytes", 0) / 1e9, 2),
    }
    print(json.dumps(out, indent=1, default=str), flush=True)
    return out


CELLS = {
    # (arch, shape, list of (label, kwargs))
    "whisper-train": ("whisper-medium", "train_4k", [
        ("baseline(post-sharding-fix)", {}),
        ("vocab-pad-51872", {"cfg_override": {"vocab_size": 51872}}),
        # small model (d=1024): wide TP starves — same 256 chips, 64x4
        ("vocab-pad+mesh-64x4",
         {"cfg_override": {"vocab_size": 51872}, "mesh_shape": (64, 4)}),
    ]),
    "qwen-train": ("qwen2-1.5b", "train_4k", [
        ("baseline-tp16-fsdp16", {}),
        ("pure-fsdp-batch-over-model",
         {"rules": {"batch": ("pod", "data", "model"), "ff": None,
                    "heads": None, "kv_heads": None, "vocab": None,
                    "experts": None}}),
        ("fsdp-embed-model-tp-data",
         {"rules": {"batch": ("pod", "data"), "embed": "model",
                    "ff": "data", "heads": "data", "kv_heads": "data",
                    "vocab": "data"}}),
        ("micro4", {"n_microbatches": 4}),
        # same 256 chips, fewer TP ways: tokens/device (and thus the
        # per-layer activation psum bytes) drop with data-axis width
        ("mesh-32x8", {"mesh_shape": (32, 8)}),
        ("mesh-64x4", {"mesh_shape": (64, 4)}),
        ("mesh-64x4-micro1", {"mesh_shape": (64, 4), "n_microbatches": 1}),
    ]),
    "llama4-decode": ("llama4-scout-17b-a16e", "decode_32k", [
        # analytic_mem on the dense baseline too: all variants accounted
        # with the same first-principles byte model (kernel-true)
        ("baseline-dense-f32", {"analytic_mem": True}),
        ("pim-int8-bitserial", {"quantized": True}),
        ("pim-int4",
         {"quantized": True, "cfg_override": {"quant_bits": 4}}),
        ("pim-int8-slice4",
         {"quantized": True, "cfg_override": {"quant_group": 2}}),
        # after quantization the bound moves to collectives: try keeping
        # decode activations replicated over model (no ff row-parallel
        # psum; experts still sharded) — contraction dims unsharded
        ("pim-int8+ff-model-only",
         {"quantized": True,
          "rules": {"ff": "model", "embed": None}}),
    ]),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=list(CELLS) + ["all"], default="all")
    args = ap.parse_args()
    cells = list(CELLS) if args.cell == "all" else [args.cell]
    results = {}
    for c in cells:
        arch, shape, variants = CELLS[c]
        print(f"\n##### {c}: {arch} x {shape}")
        results[c] = [
            run_variant(arch, shape, label=label, **kw)
            for label, kw in variants
        ]
    out = os.path.join("results", "perf_iterations.json")
    os.makedirs("results", exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1, default=str)
    print(f"\nwrote {out}")


if __name__ == "__main__":
    main()
