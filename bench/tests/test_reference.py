"""The plain float32 reference against the program's paged prefill and
decode, at a small size on the CPU, in float32 so the two agree to
rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import smoke
from bench import check, model


@pytest.mark.parametrize("name", smoke.CONFIGS)
def test_paged_prefill_and_decode_logits_match_reference(name):
    from repro.models import decode_step_paged, init_paged_pool, \
        prefill_paged

    hf = model.smoke_config(model.load_config(name), torch_dtype="float32")
    ref = check.reference(hf)
    cfg = model.program_config(hf, name)
    w = model.make_weights(hf, model.seed_key(2**33 + 1))
    params = model.program_params(hf, w)
    bs, t, n_dec, chunk = 16, 37, 5, 16
    mb = -(-(t + n_dec) // bs)
    kp, vp = init_paged_pool(cfg, 1 + mb, bs)
    table = jnp.arange(1, 1 + mb, dtype=jnp.int32)[None]
    seq = np.random.default_rng(0).integers(0, hf["vocab_size"], t + n_dec)
    got = []
    for start in range(0, t, chunk):            # chunked prefill
        end = min(start + chunk, t)
        toks = jnp.pad(jnp.asarray(seq[start:end], jnp.int32),
                       (0, chunk - (end - start)))[None]
        lg, kp, vp = prefill_paged(
            params, toks, kp, vp, table, jnp.asarray([start], jnp.int32),
            jnp.asarray([end], jnp.int32), cfg,
            last_pos=jnp.asarray(end - start - 1, jnp.int32),
            impl="pallas_interpret")
    got.append(lg[0, -1])
    for j in range(n_dec):                       # paged decode
        lg, kp, vp = decode_step_paged(
            params, jnp.asarray([[seq[t + j]]], jnp.int32), kp, vp, table,
            jnp.asarray([t + j], jnp.int32), cfg, impl="pallas_interpret")
        got.append(lg[0, -1])
    got = np.stack([np.asarray(g) for g in got])
    want = ref.logits(w, hf, seq)[t - 1: t + n_dec]
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale


@pytest.mark.parametrize("name", smoke.CONFIGS)
def test_served_gaps_read_zero_on_the_references_own_tokens(name):
    hf = model.smoke_config(model.load_config(name), torch_dtype="float32")
    ref = check.reference(hf)
    w = model.make_weights(hf, model.seed_key(7))
    seq = list(np.random.default_rng(1).integers(0, hf["vocab_size"], 20))
    for _ in range(12):                          # greedy continuation
        seq.append(int(np.argmax(ref.logits(w, hf, seq)[-1])))
    gap, cgap = ref.served_gaps(w, hf, seq, 20, 12, pad_to=64,
                                control=True)
    assert gap.shape == (12,) and gap.max() <= 1e-5
    assert cgap.shape == (12,) and cgap.min() >= 0.0
