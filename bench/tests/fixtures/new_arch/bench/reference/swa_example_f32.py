"""Plain float32 forward pass of the `swa_example` test architecture,
for the `correct` check: token embedding; per layer an RMSNorm,
grouped-query attention without bias (rotate-half rotary embedding,
softmax at 1/sqrt(head_dim)), an output projection and a residual add,
then an RMSNorm, a SwiGLU MLP and a residual add; a final RMSNorm and
the head. In a layer that `layer_types` marks `sliding_attention` a
query at position p reads only the keys at positions
(p - sliding_window, p], in the others every earlier position.
Every matrix product runs at `jax.lax.Precision.HIGHEST`; weights are
the layout of `bench/arch/swa_example.py`. It imports nothing from the
program. `control=True` rounds every weight matrix to float8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common
from .common import HI, block, fp8, mat, rms, rope


def _layer(hf, control, qb, rb, x, lw, window):
    """One decoder layer over a whole sequence x [T, d] (float32);
    `window` 0 reads every earlier position."""
    h, kv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // h
    eps, theta = float(hf["rms_norm_eps"]), float(hf["rope_theta"])
    t = x.shape[0]
    pos = jnp.arange(t)
    xa = rms(x, lw["input_layernorm"], eps)

    def proj(name):
        return jnp.dot(xa, mat(lw[name + "_w"], control), precision=HI)

    q = rope(proj("q_proj").reshape(t, h, hd), pos, theta)
    k = rope(proj("k_proj").reshape(t, kv, hd), pos, theta)
    v = proj("v_proj").reshape(t, kv, hd)
    q = q.reshape(t // qb, qb, kv, h // kv, hd)

    def attend(args):
        i, qi = args
        qpos = (i * qb + jnp.arange(qb))[:, None]
        kpos = pos[None, :]
        seen = (kpos <= qpos) & ((window == 0) | (kpos > qpos - window))
        s = jnp.einsum("qgrd,kgd->grqk", qi, k, precision=HI) / np.sqrt(hd)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI)

    o = jax.lax.map(attend, (jnp.arange(t // qb), q)).reshape(t, h * hd)
    x = x + jnp.dot(o, mat(lw["o_proj_w"], control), precision=HI)
    wg, wu = mat(lw["gate_proj_w"], control), mat(lw["up_proj_w"], control)
    wd = mat(lw["down_proj_w"], control)

    def mlp(rows):
        xm = rms(rows, lw["post_attention_layernorm"], eps)
        g = jnp.dot(xm, wg, precision=HI)
        u = jnp.dot(xm, wu, precision=HI)
        return rows + jnp.dot(jax.nn.silu(g) * u, wd, precision=HI)

    return jax.lax.map(mlp, x.reshape(t // rb, rb, -1)).reshape(t, -1)


@functools.partial(jax.jit, static_argnames=("hf_items", "control"))
def _hidden(w, tokens, hf_items, control):
    hf = dict(hf_items)
    rows = w["embed_tokens"][tokens].astype(jnp.float32)
    x = fp8(rows.T).T if control else rows
    lays = {k.split(".", 1)[1]: v for k, v in w.items()
            if k.startswith("layers.")}
    windows = jnp.asarray([hf["sliding_window"] if kind == "sliding_attention"
                           else 0 for kind in hf["layer_types"]], jnp.int32)
    t = tokens.shape[0]
    qb = block(t, 64 << 20, 4 * t * hf["num_attention_heads"])
    rb = block(t, 64 << 20, 4 * 2 * hf["intermediate_size"])

    def body(x, xs):
        lw, window = xs
        return _layer(hf, control, qb, rb, x, lw, window), None

    x, _ = jax.lax.scan(body, x, (lays, windows))
    return rms(x, w["norm"], float(hf["rms_norm_eps"]))


def _hidden_of(hf):
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "hidden_size", "intermediate_size", "rms_norm_eps",
            "rope_theta", "sliding_window")
    hfk = tuple((k, hf[k]) for k in keys if k in hf) + (
        ("layer_types", tuple(hf["layer_types"])),)
    return lambda w, tokens, control: _hidden(w, tokens, hfk, control)


def served_gaps(w, hf, tokens, n_prompt: int, n_served: int, *,
                pad_to: int, control: bool = False, rows: int = 256):
    return common.served_gaps(_hidden_of(hf), w, hf, tokens, n_prompt,
                              n_served, pad_to=pad_to, control=control,
                              rows=rows)


def logits(w, hf, tokens):
    return common.logits(_hidden_of(hf), w, hf, tokens)
