"""Plain Qwen2 decoder forward pass in float32, for the `correct` check.

Written from the published description of Qwen2 (arXiv:2407.10671 and
the `Qwen2ForCausalLM` definition of its public `config.json`): token
embedding; per layer an RMSNorm, grouped-query attention with bias on
q, k and v, rotary position embedding of the rotate-half form over the
whole head (`rope_theta`), a causal softmax at scale 1/sqrt(head_dim),
an output projection without bias and a residual add; then an RMSNorm
and a SwiGLU MLP (`silu(x W_gate) * (x W_up)`, `W_down`) with a residual
add; a final RMSNorm and the head (the embedding, transposed, when the
weights are tied). Query head h reads key/value head
h // (heads / kv_heads). Every matrix product runs at
`jax.lax.Precision.HIGHEST`. Weights are the benchmark's layout
(`bench/model.py`), kept as stored and widened to float32 where used.

It imports nothing from the program. Memory stays bounded at long
contexts: one sequence at a time, keys and values of a layer for all
positions, and queries, attention, MLP and head in blocks of rows.

`control=True` computes the same pass with every weight matrix rounded
to float8 (e4m3, one scale per output column): the precision one step
below the bfloat16 that the configurations state.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fp8(w):
    """Round a weight matrix [.., K, M] to e4m3 with a per-column scale."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mat(w, control: bool):
    return _fp8(w) if control else w.astype(jnp.float32)


def _rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [T, H, hd], pos [T]: rotate-half rotary embedding."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    emb = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def _dims(hf):
    h, kv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or hf["hidden_size"] // h
    return h, kv, hd


def _layer(hf, control, qb, rb, x, lw):
    """One decoder layer over a whole sequence x [T, d] (float32)."""
    h, kv, hd = _dims(hf)
    eps, theta = float(hf["rms_norm_eps"]), float(hf["rope_theta"])
    t = x.shape[0]
    pos = jnp.arange(t)
    f32 = jnp.float32
    xa = _rms(x, lw["input_layernorm"], eps)

    def proj(name):
        y = jnp.dot(xa, _mat(lw[name + "_w"], control), precision=HI)
        return y + lw[name + "_b"].astype(f32)

    q = _rope(proj("q_proj").reshape(t, h, hd), pos, theta)
    k = _rope(proj("k_proj").reshape(t, kv, hd), pos, theta)
    v = proj("v_proj").reshape(t, kv, hd)
    q = q.reshape(t // qb, qb, kv, h // kv, hd)

    def attend(args):
        i, qi = args
        qpos = i * qb + jnp.arange(qb)
        s = jnp.einsum("qgrd,kgd->grqk", qi, k, precision=HI) / np.sqrt(hd)
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI)

    o = jax.lax.map(attend, (jnp.arange(t // qb), q)).reshape(t, h * hd)
    x = x + jnp.dot(o, _mat(lw["o_proj_w"], control), precision=HI)
    wg, wu = _mat(lw["gate_proj_w"], control), _mat(lw["up_proj_w"], control)
    wd = _mat(lw["down_proj_w"], control)

    def mlp(rows):
        xm = _rms(rows, lw["post_attention_layernorm"], eps)
        g = jnp.dot(xm, wg, precision=HI)
        u = jnp.dot(xm, wu, precision=HI)
        return rows + jnp.dot(jax.nn.silu(g) * u, wd, precision=HI)

    return jax.lax.map(mlp, x.reshape(t // rb, rb, -1)).reshape(t, -1)


def _block(t: int, limit: int, per_row: int) -> int:
    """The largest power-of-two divisor of t, at most 1024, whose block
    of rows takes at most `limit` bytes at `per_row` bytes a row."""
    b = 1024
    while b > 1 and (t % b or b * per_row > limit):
        b //= 2
    return b


@functools.partial(jax.jit, static_argnames=("hf_items", "control"))
def _hidden(w, tokens, hf_items, control):
    hf = dict(hf_items)
    rows = w["embed_tokens"][tokens].astype(jnp.float32)
    x = _fp8(rows.T).T if control else rows
    lays = {k.split(".", 1)[1]: v for k, v in w.items()
            if k.startswith("layers.")}
    t = tokens.shape[0]
    # one block of attention scores, or of MLP activations, near 64 MiB
    qb = _block(t, 64 << 20, 4 * t * hf["num_attention_heads"])
    rb = _block(t, 64 << 20, 4 * 2 * hf["intermediate_size"])

    def body(x, lw):
        return _layer(hf, control, qb, rb, x, lw), None

    x, _ = jax.lax.scan(body, x, lays)
    return _rms(x, w["norm"], float(hf["rms_norm_eps"]))


@functools.partial(jax.jit, static_argnames=("tied", "cols"))
def _score_rows(head, h_ref, h_ctl, served, tied, cols=8192):
    """Per row: the reference's best logit, its logit of the served
    token, and its logit of the token the control puts first (the
    served token's when there is no control). The head is read in
    blocks of `cols` vocabulary columns, widened one block at a time."""
    vocab = head.shape[0] if tied else head.shape[1]
    cols = min(cols, vocab)
    n = h_ref.shape[0]
    neg = jnp.full((n,), -jnp.inf, jnp.float32)
    zero = jnp.zeros((n,), jnp.float32)

    def step(carry, i):
        best, got, cbest, alt = carry
        lo = jnp.minimum(i * cols, vocab - cols)
        if tied:
            wc = jax.lax.dynamic_slice_in_dim(head, lo, cols, axis=0).T
        else:
            wc = jax.lax.dynamic_slice_in_dim(head, lo, cols, axis=1)
        ref = jnp.dot(h_ref, wc.astype(jnp.float32), precision=HI)
        best = jnp.maximum(best, jnp.max(ref, axis=-1))
        j = served - lo
        inside = (j >= 0) & (j < cols)
        hit = jnp.take_along_axis(ref, jnp.clip(j, 0, cols - 1)[:, None],
                                  axis=-1)[:, 0]
        got = jnp.where(inside, hit, got)
        if h_ctl is not None:
            ctl = jnp.dot(h_ctl, _fp8(wc), precision=HI)
            cm = jnp.max(ctl, axis=-1)
            pick = jnp.argmax(ctl, axis=-1)
            here = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
            alt = jnp.where(cm > cbest, here, alt)
            cbest = jnp.maximum(cbest, cm)
        return (best, got, cbest, alt), None

    (best, got, _, alt), _ = jax.lax.scan(
        step, (neg, zero, neg, zero), jnp.arange(-(-vocab // cols)))
    return best, got, (got if h_ctl is None else alt)


def _hashable(hf: Dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "hidden_size", "intermediate_size", "rms_norm_eps",
            "rope_theta", "tie_word_embeddings")
    return tuple((k, hf[k]) for k in keys if k in hf)


def served_gaps(w, hf, tokens, n_prompt: int, n_served: int, *,
                pad_to: int, control: bool = False, rows: int = 256):
    """Logit gaps of one served sequence.

    `tokens` is the prompt followed by the served tokens. The served
    token j was chosen at position n_prompt - 1 + j. Returns numpy
    arrays `(gap, control_gap)`: for each served token, the reference's
    best logit less its logit of that token, and (with `control`) less
    its logit of the token the float8 pass puts first (else None). The
    sequence is padded to `pad_to` positions, so every sequence of a
    cell runs one compiled program."""
    hfk = _hashable(hf)
    seq = np.zeros((pad_to,), np.int32)
    seq[: len(tokens)] = np.asarray(tokens, np.int32)
    seq = jnp.asarray(seq)
    with jax.default_matmul_precision("highest"):
        h_ref = _hidden(w, seq, hfk, False)
        h_ctl = _hidden(w, seq, hfk, True) if control else None
        tied = bool(hf["tie_word_embeddings"])
        head = w["embed_tokens"] if tied else w["lm_head"]
        pos = n_prompt - 1 + np.arange(n_served)
        served = np.asarray(tokens, np.int32)[n_prompt: n_prompt + n_served]
        gaps, cgaps = [], []
        for lo in range(0, n_served, rows):
            idx = np.zeros((rows,), np.int32)
            tgt = np.zeros((rows,), np.int32)
            m = min(rows, n_served - lo)
            idx[:m], tgt[:m] = pos[lo: lo + m], served[lo: lo + m]
            best, got, alt = _score_rows(
                head, h_ref[idx], None if h_ctl is None else h_ctl[idx],
                jnp.asarray(tgt), tied)
            best, got, alt = (np.asarray(a)[:m] for a in (best, got, alt))
            gaps.append(best - got)
            cgaps.append(best - alt)
    gap = np.concatenate(gaps) if gaps else np.zeros((0,))
    cgap = np.concatenate(cgaps) if control and cgaps else None
    return gap, cgap


def logits(w, hf, tokens):
    """Every position's logits [T, vocab] (float32): small sizes only."""
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = _hidden(w, tokens, _hashable(hf), False)
        head = (w["embed_tokens"].T if hf["tie_word_embeddings"]
                else w["lm_head"])
        return np.asarray(jnp.dot(h, head.astype(jnp.float32), precision=HI))
