"""Configurations and weights, made by the benchmark from a seed.

A configuration file under `bench/configs/` holds the published
`config.json` keys of the model as it is run. `program_config` turns it
into the program's `ModelConfig`; `make_weights` draws the weights on
the device in one jitted call, in bfloat16, in the benchmark's own
layout (named after the published checkpoint). `program_params` hands
the same buffers to the program in its layout, without a copy, the way a
checkpoint loader would; the reference reads the benchmark's layout.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

#: leaves of one decoder layer: name -> shape builder from the config
_LAYER_SHAPES = {
    "input_layernorm": lambda c: (c["hidden_size"],),
    "q_proj_w": lambda c: (c["hidden_size"], _heads(c) * _hd(c)),
    "q_proj_b": lambda c: (_heads(c) * _hd(c),),
    "k_proj_w": lambda c: (c["hidden_size"], _kv(c) * _hd(c)),
    "k_proj_b": lambda c: (_kv(c) * _hd(c),),
    "v_proj_w": lambda c: (c["hidden_size"], _kv(c) * _hd(c)),
    "v_proj_b": lambda c: (_kv(c) * _hd(c),),
    "o_proj_w": lambda c: (_heads(c) * _hd(c), c["hidden_size"]),
    "post_attention_layernorm": lambda c: (c["hidden_size"],),
    "gate_proj_w": lambda c: (c["hidden_size"], c["intermediate_size"]),
    "up_proj_w": lambda c: (c["hidden_size"], c["intermediate_size"]),
    "down_proj_w": lambda c: (c["intermediate_size"], c["hidden_size"]),
}


def _heads(c):
    return c["num_attention_heads"]


def _kv(c):
    return c["num_key_value_heads"]


def _hd(c):
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def load_config(name: str, root: str = BENCH_DIR) -> dict:
    with open(os.path.join(root, "configs", f"{name}.json")) as f:
        return json.load(f)


def program_config(hf: dict, name: str):
    """The program's `ModelConfig` for a published Qwen2 config, with
    every value the program takes set as the file states it."""
    from repro.configs.base import ModelConfig

    if hf.get("use_sliding_window"):
        raise ValueError("sliding-window Qwen2 layers are not mapped")
    return ModelConfig(
        name=name, family="dense", n_layers=hf["num_hidden_layers"],
        d_model=hf["hidden_size"], n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], d_ff=hf["intermediate_size"],
        vocab_size=hf["vocab_size"], head_dim=_hd(hf), qkv_bias=True,
        rope_theta=float(hf["rope_theta"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        norm_eps=float(hf["rms_norm_eps"]), act=hf["hidden_act"],
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            hf["torch_dtype"]],
        remat=False,
    )


def seed_key(seed: int, stream: int = 0):
    """A JAX key for any whole-number seed, 32 bits or wider."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


def weight_shapes(hf: dict) -> dict:
    """Every leaf of the benchmark's layout and its shape."""
    n = hf["num_hidden_layers"]
    shapes = {"embed_tokens": (hf["vocab_size"], hf["hidden_size"]),
              "norm": (hf["hidden_size"],)}
    if not hf["tie_word_embeddings"]:
        shapes["lm_head"] = (hf["hidden_size"], hf["vocab_size"])
    for k, f in _LAYER_SHAPES.items():
        shapes["layers." + k] = (n,) + f(hf)
    return shapes


def make_weights(hf: dict, key) -> dict:
    """Random bfloat16 weights in one jitted call on the default device:
    a flat dict `name -> array`, layer leaves stacked over layers."""
    shapes = weight_shapes(hf)
    std = float(hf.get("initializer_range", 0.02))
    # query and key projections at the scale that gives attention
    # scores of std `init_qk_score_std`: peaked as in a trained model,
    # so a position the cache loses or garbles changes the tokens
    score = float(hf.get("init_qk_score_std", 0.0))
    qk_std = (score / hf["hidden_size"]) ** 0.5 if score else std
    dt = jnp.bfloat16 if hf["torch_dtype"] == "bfloat16" else jnp.float32

    def init(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, jnp.float32)
            if name.endswith("layernorm") or name == "norm":
                out[name] = (1.0 + 0.1 * z).astype(dt)
            elif name in ("layers.q_proj_w", "layers.k_proj_w"):
                out[name] = (qk_std * z).astype(dt)
            else:
                out[name] = (std * z).astype(dt)
        return out

    return jax.jit(init)(key)


def program_params(hf: dict, w: dict) -> dict:
    """The same buffers in the program's parameter tree."""
    lay = {k.split(".", 1)[1]: v for k, v in w.items()
           if k.startswith("layers.")}
    p = {
        "embed": w["embed_tokens"],
        "final_norm": {"g": w["norm"]},
        "layers": {
            "ln1": {"g": lay["input_layernorm"]},
            "ln2": {"g": lay["post_attention_layernorm"]},
            "attn": {
                "wq": lay["q_proj_w"], "bq": lay["q_proj_b"],
                "wk": lay["k_proj_w"], "bk": lay["k_proj_b"],
                "wv": lay["v_proj_w"], "bv": lay["v_proj_b"],
                "wo": lay["o_proj_w"],
            },
            "mlp": {"w_gate": lay["gate_proj_w"], "w_up": lay["up_proj_w"],
                    "w_down": lay["down_proj_w"]},
        },
    }
    if not hf["tie_word_embeddings"]:
        p["lm_head"] = w["lm_head"]
    return p


def smoke_config(hf: dict, **over) -> dict:
    """A tiny copy of a configuration for tests on the CPU."""
    small = dict(hf, num_hidden_layers=4, hidden_size=128,
                 num_attention_heads=4, num_key_value_heads=2,
                 intermediate_size=128, vocab_size=256)
    small.update(over)
    return small


__all__ = [
    "BENCH_DIR", "load_config", "program_config", "seed_key",
    "weight_shapes", "make_weights", "program_params", "smoke_config",
]
