"""Configurations and weights, made by the benchmark from a seed.

A configuration file under `bench/configs/` holds the published
`config.json` keys of the model as it is run. Its `model_type` names
the architecture's module, `bench/arch/<model_type>.py`, which knows
the layout of the weights, the program's `ModelConfig` and the work of
each layer (`bench/arch/__init__.py`); this module forwards to it.
`make_weights` draws the weights on the device in one jitted call, in
the configuration's dtype, in the benchmark's own layout (named after
the published checkpoint). `program_params` hands the same buffers to
the program in its layout, without a copy, the way a checkpoint loader
would; the reference reads the benchmark's layout.
"""

from __future__ import annotations

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def load_config(name: str, root: str = BENCH_DIR) -> dict:
    with open(os.path.join(root, "configs", f"{name}.json")) as f:
        return json.load(f)


def arch(hf: dict):
    """The architecture's module, `bench/arch/<model_type>.py`."""
    return importlib.import_module(f"bench.arch.{hf['model_type']}")


def program_config(hf: dict, name: str):
    """The program's `ModelConfig`, every value as the file states it."""
    return arch(hf).program_config(hf, name)


def weight_shapes(hf: dict) -> dict:
    """Every leaf of the benchmark's layout and its shape."""
    return arch(hf).weight_shapes(hf)


def program_params(hf: dict, w: dict) -> dict:
    """The same buffers in the program's parameter tree."""
    return arch(hf).program_params(hf, w)


def smoke_config(hf: dict, **over) -> dict:
    """A tiny copy of a configuration for tests on the CPU, with the
    layer pattern kept."""
    return arch(hf).smoke_config(hf, **over)


def layers(hf: dict) -> list:
    """The work of each layer (`bench.arch.Layer`)."""
    return arch(hf).layers(hf)


def head_flops(hf: dict) -> float:
    return arch(hf).head_flops(hf)


def seed_key(seed: int, stream: int = 0):
    """A JAX key for any whole-number seed, 32 bits or wider."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


def make_weights(hf: dict, key) -> dict:
    """Random weights in one jitted call on the default device: a flat
    dict `name -> array`, layer leaves stacked over layers. Leaf i of
    the sorted names draws from `fold_in(key, i)`, as its architecture's
    `init_kind` says."""
    a = arch(hf)
    shapes = a.weight_shapes(hf)
    kinds = {name: a.init_kind(name) for name in shapes}
    std = float(hf.get("initializer_range", 0.02))
    # query and key projections at the scale that gives attention
    # scores of std `init_qk_score_std`: peaked as in a trained model,
    # so a position the cache loses or garbles changes the tokens
    score = float(hf.get("init_qk_score_std", 0.0))
    qk_std = (score / hf["hidden_size"]) ** 0.5 if score else std
    dt = jnp.bfloat16 if hf["torch_dtype"] == "bfloat16" else jnp.float32
    draw = {"norm": lambda z: 1.0 + 0.1 * z, "qk": lambda z: qk_std * z,
            "plain": lambda z: std * z}

    def init(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = draw[kinds[name]](z).astype(dt)
        return out

    return jax.jit(init)(key)


__all__ = [
    "BENCH_DIR", "load_config", "arch", "program_config", "weight_shapes",
    "program_params", "smoke_config", "layers", "head_flops", "seed_key",
    "make_weights",
]
