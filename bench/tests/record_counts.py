"""Records `fixtures/counts.json`, what `test_recorded_counts.py` holds
the harness to: for every configuration of BENCHMARK.json, a hash of
the weights `model.make_weights` draws at the smoke size, the program
config and parameter shapes made from them, and every `bench/shapes.py`
count over a fixed list of lengths.

    JAX_PLATFORMS=cpu python3 bench/tests/record_counts.py

Re-record only where a change of these numbers is meant.
"""

import dataclasses
import hashlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SEEDS = (7, 2**33 + 1)
BLOCK = 16
LENGTHS = (1, 2, 15, 16, 17, 31, 512, 1020, 4096, 6137, 10233)
#: (start, end) of prefill launches: first chunks, later chunks, a tail
CHUNKS = ((0, 4), (0, 16), (0, 512), (512, 520), (512, 1024),
          (3584, 4096), (9728, 10233))


def weights_digest(hf: dict, seed: int) -> str:
    import jax
    import numpy as np

    from bench import model

    w = model.make_weights(hf, model.seed_key(seed))
    h = hashlib.sha256()
    for name in sorted(w):
        a = np.asarray(jax.device_get(w[name]))
        h.update(f"{name}:{a.dtype}:{a.shape};".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def counts(hf: dict) -> dict:
    from bench import shapes

    return {
        "linear_flops_per_token": shapes.linear_flops_per_token(hf),
        "head_flops": shapes.head_flops(hf),
        "kv_bytes_per_token": shapes.kv_bytes_per_token(hf),
        "attention_flops": [shapes.attention_flops(hf, k) for k in LENGTHS],
        "decode_kernel_bytes": [
            shapes.decode_kernel_bytes(hf, [e], BLOCK) for e in LENGTHS]
        + [shapes.decode_kernel_bytes(hf, LENGTHS, BLOCK)],
        "decode_model_flops": [
            shapes.decode_model_flops(hf, [e]) for e in LENGTHS]
        + [shapes.decode_model_flops(hf, LENGTHS)],
        "prefill_kernel_work": [
            list(shapes.prefill_kernel_work(hf, s, e, BLOCK))
            for s, e in CHUNKS],
        "prefill_model_flops": [
            shapes.prefill_model_flops(hf, s, e) for s, e in CHUNKS],
    }


def record(name: str) -> dict:
    import jax

    from bench import model

    hf = model.load_config(name)
    small = model.smoke_config(hf)
    cfg = model.program_config(hf, name)
    w = model.make_weights(small, model.seed_key(SEEDS[0]))
    params = model.program_params(small, w)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    return {
        "program_config": dataclasses.asdict(cfg),
        "weight_shapes": {k: list(v) for k, v in
                          sorted(model.weight_shapes(hf).items())},
        "smoke_weights_sha256": {str(s): weights_digest(small, s)
                                 for s in SEEDS},
        "smoke_program_params": {jax.tree_util.keystr(p): list(a.shape)
                                 for p, a in leaves},
        "counts": counts(hf),
    }


def main():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [c["name"] for c in json.load(f)["configs"]]
    out = {n: record(n) for n in names}
    with open(os.path.join(HERE, "fixtures", "counts.json"), "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")


if __name__ == "__main__":
    main()
