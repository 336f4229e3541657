"""A test architecture (`model_type` "swa_example"), not a published
model: a dense decoder of grouped-query attention with no bias on q, k
and v and a SwiGLU MLP, whose layers attend over a sliding window or
over every earlier position as the configuration's `layer_types` lists
them. `test_discovery.py` adds it, with its reference, configuration,
cell and mix, to a copy of the benchmark by new files alone.
"""

from __future__ import annotations

from bench.arch import Layer, swiglu_flops

_LAYER_SHAPES = {
    "input_layernorm": lambda c: (c["hidden_size"],),
    "q_proj_w": lambda c: (c["hidden_size"], _heads(c) * _hd(c)),
    "k_proj_w": lambda c: (c["hidden_size"], _kv(c) * _hd(c)),
    "v_proj_w": lambda c: (c["hidden_size"], _kv(c) * _hd(c)),
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


def windows(hf: dict) -> list:
    """Each layer's window: `sliding_window` keys, or 0 for all."""
    if len(hf["layer_types"]) != hf["num_hidden_layers"]:
        raise ValueError("layer_types has to list every layer")
    return [hf["sliding_window"] if k == "sliding_attention" else 0
            for k in hf["layer_types"]]


def _local_global_ratio(hf: dict) -> int:
    """The program's pattern: r windowed layers, then one full layer."""
    full = [w == 0 for w in windows(hf)]
    for r in range(1, len(full)):
        if full == [i % (r + 1) == r for i in range(len(full))]:
            return r
    raise ValueError(f"the program has no layer pattern {hf['layer_types']}")


def program_config(hf: dict, name: str):
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=name, family="dense", n_layers=hf["num_hidden_layers"],
        d_model=hf["hidden_size"], n_heads=_heads(hf), n_kv_heads=_kv(hf),
        d_ff=hf["intermediate_size"], vocab_size=hf["vocab_size"],
        head_dim=_hd(hf), qkv_bias=bool(hf["attention_bias"]),
        sliding_window=hf["sliding_window"],
        local_global_ratio=_local_global_ratio(hf),
        rope_theta=float(hf["rope_theta"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        norm_eps=float(hf["rms_norm_eps"]), act=hf["hidden_act"],
        dtype=hf["torch_dtype"], remat=False,
    )


def weight_shapes(hf: dict) -> dict:
    n = hf["num_hidden_layers"]
    out = {"embed_tokens": (hf["vocab_size"], hf["hidden_size"]),
           "norm": (hf["hidden_size"],)}
    if not hf["tie_word_embeddings"]:
        out["lm_head"] = (hf["hidden_size"], hf["vocab_size"])
    for k, f in _LAYER_SHAPES.items():
        out["layers." + k] = (n,) + f(hf)
    return out


def init_kind(name: str) -> str:
    if name.endswith("layernorm") or name == "norm":
        return "norm"
    if name in ("layers.q_proj_w", "layers.k_proj_w"):
        return "qk"
    return "plain"


def program_params(hf: dict, w: dict) -> dict:
    lay = {k.split(".", 1)[1]: v for k, v in w.items()
           if k.startswith("layers.")}
    p = {
        "embed": w["embed_tokens"],
        "final_norm": {"g": w["norm"]},
        "layers": {
            "ln1": {"g": lay["input_layernorm"]},
            "ln2": {"g": lay["post_attention_layernorm"]},
            "attn": {"wq": lay["q_proj_w"], "wk": lay["k_proj_w"],
                     "wv": lay["v_proj_w"], "wo": lay["o_proj_w"]},
            "mlp": {"w_gate": lay["gate_proj_w"], "w_up": lay["up_proj_w"],
                    "w_down": lay["down_proj_w"]},
        },
    }
    if not hf["tie_word_embeddings"]:
        p["lm_head"] = w["lm_head"]
    return p


def smoke_config(hf: dict, **over) -> dict:
    """Four layers of the pattern, small widths, a 24-key window."""
    small = dict(hf, num_hidden_layers=4, layer_types=hf["layer_types"][:4],
                 hidden_size=128, num_attention_heads=4,
                 num_key_value_heads=2, intermediate_size=128,
                 vocab_size=256, sliding_window=24)
    small.pop("head_dim", None)
    small.update(over)
    return small


def layers(hf: dict) -> list:
    d, h, kv, hd = hf["hidden_size"], _heads(hf), _kv(hf), _hd(hf)
    proj = 2 * (d * (h + 2 * kv) * hd + h * hd * d)
    ffn = swiglu_flops(d, hf["intermediate_size"])
    return [Layer(heads=h, kv_heads=kv, qk_dim=hd, v_dim=hd, window=w,
                  proj_flops_per_token=proj, ffn_flops_per_token=ffn)
            for w in windows(hf)]


def head_flops(hf: dict) -> float:
    return 2.0 * hf["hidden_size"] * hf["vocab_size"]
