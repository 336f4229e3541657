"""Qwen2 (`model_type` "qwen2"): a stack of identical decoder layers,
grouped-query attention with bias on q, k and v over every earlier
position, a SwiGLU MLP, RMSNorm before each; the head tied to the
embedding or not. The benchmark's layout is named after the published
checkpoint, with layer leaves stacked over layers.
"""

from __future__ import annotations

from bench.arch import Layer, swiglu_flops

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


def init_kind(name: str) -> str:
    if name.endswith("layernorm") or name == "norm":
        return "norm"
    if name in ("layers.q_proj_w", "layers.k_proj_w"):
        return "qk"
    return "plain"


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


def layers(hf: dict) -> list:
    """Every layer alike: full causal attention and a dense MLP."""
    d, h, kv, hd = hf["hidden_size"], _heads(hf), _kv(hf), _hd(hf)
    one = Layer(heads=h, kv_heads=kv, qk_dim=hd, v_dim=hd, window=0,
                proj_flops_per_token=2 * (d * (h + 2 * kv) * hd
                                          + h * hd * d),
                ffn_flops_per_token=swiglu_flops(
                    d, hf["intermediate_size"]))
    return [one] * hf["num_hidden_layers"]


def head_flops(hf: dict) -> float:
    return 2.0 * hf["hidden_size"] * hf["vocab_size"]
