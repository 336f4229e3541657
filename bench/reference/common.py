"""What the plain float32 references share, whatever the architecture:
float8 rounding for the control, RMSNorm, rotary embedding of the
rotate-half form, blocks of rows that bound memory, and the scoring of
served tokens against the head.

An architecture's reference (`bench/reference/<name>.py`) writes its
layers and a `hidden(w, tokens, control)` function, the final-normed
hidden rows [T, d] in float32 of a padded sequence, and hands it to
`served_gaps` and `logits` here. It imports nothing from the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def fp8(w):
    """Round a weight matrix [.., K, M] to e4m3 with a per-column scale."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mat(w, control: bool):
    return fp8(w) if control else w.astype(jnp.float32)


def rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def rope(x, pos, theta):
    """x [T, H, hd], pos [T]: rotate-half rotary embedding."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    emb = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(emb) + rot * jnp.sin(emb)


def block(t: int, limit: int, per_row: int) -> int:
    """The largest power-of-two divisor of t, at most 1024, whose block
    of rows takes at most `limit` bytes at `per_row` bytes a row."""
    b = 1024
    while b > 1 and (t % b or b * per_row > limit):
        b //= 2
    return b


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
            ctl = jnp.dot(h_ctl, fp8(wc), precision=HI)
            cm = jnp.max(ctl, axis=-1)
            pick = jnp.argmax(ctl, axis=-1)
            here = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
            alt = jnp.where(cm > cbest, here, alt)
            cbest = jnp.maximum(cbest, cm)
        return (best, got, cbest, alt), None

    (best, got, _, alt), _ = jax.lax.scan(
        step, (neg, zero, neg, zero), jnp.arange(-(-vocab // cols)))
    return best, got, (got if h_ctl is None else alt)


def served_gaps(hidden, w, hf, tokens, n_prompt: int, n_served: int, *,
                pad_to: int, control: bool = False, rows: int = 256):
    """Logit gaps of one served sequence.

    `tokens` is the prompt followed by the served tokens. The served
    token j was chosen at position n_prompt - 1 + j. Returns numpy
    arrays `(gap, control_gap)`: for each served token, the reference's
    best logit less its logit of that token, and (with `control`) less
    its logit of the token the float8 pass puts first (else None). The
    sequence is padded to `pad_to` positions, so every sequence of a
    cell runs one compiled program."""
    seq = np.zeros((pad_to,), np.int32)
    seq[: len(tokens)] = np.asarray(tokens, np.int32)
    seq = jnp.asarray(seq)
    with jax.default_matmul_precision("highest"):
        h_ref = hidden(w, seq, False)
        h_ctl = hidden(w, seq, True) if control else None
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


def logits(hidden, w, hf, tokens):
    """Every position's logits [T, vocab] (float32): small sizes only."""
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        h = hidden(w, tokens, False)
        head = (w["embed_tokens"].T if hf["tie_word_embeddings"]
                else w["lm_head"])
        return np.asarray(jnp.dot(h, head.astype(jnp.float32), precision=HI))
