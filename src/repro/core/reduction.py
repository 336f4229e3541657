"""Reduction-network schedules: the paper's eqn (1) dataflows on a mesh.

The paper compares array-level reduction networks (Table IV): linear NEWS
shift-add (SPAR-2), binary-add, a global adder tree (CCB/CoMeFa), and
PiCaSO's binary-hopping. On TPU the "array" is the device mesh and a
"hop" is a `lax.ppermute`; we implement the same schedules as shard_map
collectives so the Gold Standard model can be fitted against *real*
lowered programs, and so the framework can pick a schedule per workload
(latency- vs bandwidth-bound).

Each schedule reduces a per-device shard along a named mesh axis and
leaves the total on every device (all-reduce semantics), plus a
`*_to_zero` variant leaving it on index 0 (the engine's west column).

Step-count models (for eqn (1) fitting; one "step" moves one shard over
one link):

  linear        : P-1 sequential hops          -> a=0-ish, b ~ hop cost
  binary-hopping: log2(P) hops of 2^h distance -> aN log P + (P-1) pattern
  tree (psum)   : XLA's native all-reduce      -> the 'global adder tree'
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# step-count models (cycles in units of one hop + one add)
# ---------------------------------------------------------------------------

def steps_linear(p: int) -> int:
    return max(0, p - 1)


def steps_binary_hopping(p: int) -> int:
    return int(math.ceil(math.log2(p))) if p > 1 else 0


def movement_linear(p: int) -> int:
    return max(0, p - 1)


def movement_binary_hopping(p: int) -> int:
    # sum of 2^h hop distances = P - 1 (paper Table IV, binary-hopping)
    return max(0, p - 1)


def reduction_latency_model(
    schedule: str, n_bits: int, p: int, add_cycles_per_bit: float = 1.0,
    hop_cycles: float = 1.0,
) -> float:
    """Cycles for array-level reduction under a schedule — instantiates
    eqn (1) with schedule-specific (a, b, c) structure."""
    if schedule == "linear":
        return (add_cycles_per_bit * n_bits + hop_cycles) * steps_linear(p)
    if schedule == "binary-hopping":
        return (
            add_cycles_per_bit * n_bits * steps_binary_hopping(p)
            + hop_cycles * movement_binary_hopping(p)
        )
    if schedule == "tree":
        # fully-pipelined global adder tree: log P latency, no serial moves
        return add_cycles_per_bit * steps_binary_hopping(p) + 2.0
    raise ValueError(f"unknown schedule {schedule!r}")


# ---------------------------------------------------------------------------
# shard_map collective implementations
# ---------------------------------------------------------------------------

def allreduce_linear(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """SPAR-2-style linear shift-add ring: P-1 sequential permute+add.

    Deliberately latency-suboptimal (the paper's 'Very Slow' row) — kept as
    the baseline the Gold Standard fit must flag as out-of-range.
    """
    p = lax.axis_size(axis)
    acc = x
    buf = x
    perm = [(i, (i + 1) % p) for i in range(p)]
    for _ in range(p - 1):
        buf = lax.ppermute(buf, axis, perm)
        acc = acc + buf
    return acc


def allreduce_binary_hopping(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """PiCaSO binary-hopping == recursive doubling: log2(P) hops of
    stride 2^h. Every device ends with the full sum."""
    p = lax.axis_size(axis)
    if p & (p - 1):
        raise ValueError("binary-hopping needs a power-of-two axis size")
    acc = x
    h = 1
    while h < p:
        perm = [(i, i ^ h) for i in range(p)]
        acc = acc + lax.ppermute(acc, axis, perm)
        h <<= 1
    return acc


def reduce_to_zero_binary_hopping(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """East->west accumulation onto index 0 (IMAGine's west column):
    at level h, device j receives from j + 2^h for j % 2^(h+1) == 0.
    Other devices keep garbage partials (masked out by caller)."""
    p = lax.axis_size(axis)
    if p & (p - 1):
        raise ValueError("binary-hopping needs a power-of-two axis size")
    acc = x
    idx = lax.axis_index(axis)
    h = 1
    while h < p:
        # send j -> j - h for odd multiples of h
        perm = [(j, j - h) for j in range(p) if (j % (2 * h)) == h]
        moved = lax.ppermute(acc, axis, perm)
        take = (idx % (2 * h)) == 0
        acc = jnp.where(take, acc + moved, acc)
        h <<= 1
    return acc


def allreduce_tree(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """The 'global adder tree': XLA's native psum."""
    return lax.psum(x, axis)


def reduce_scatter_then_gather(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Bandwidth-optimal all-reduce = reduce-scatter + all-gather, written
    explicitly so the dry-run can compare collective bytes against psum.
    Operates on the flattened (padded) tensor so any shard shape works."""
    p = lax.axis_size(axis)
    shape = x.shape
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % p
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    scattered = lax.psum_scatter(flat, axis, scatter_dimension=0, tiled=True)
    full = lax.all_gather(scattered, axis, axis=0, tiled=True)
    if pad:
        full = full[: -pad]
    return full.reshape(shape)


SCHEDULES: Dict[str, Callable[[jnp.ndarray, str], jnp.ndarray]] = {
    "linear": allreduce_linear,
    "binary-hopping": allreduce_binary_hopping,
    "tree": allreduce_tree,
    "rs-ag": reduce_scatter_then_gather,
}


def make_sharded_allreduce(mesh: jax.sharding.Mesh, axis: str, schedule: str):
    """Return a jit-able f(x_global) -> allreduce over `axis` shards using
    the chosen schedule, built with shard_map."""
    from jax.sharding import PartitionSpec as P
    fn = SCHEDULES[schedule]
    spec = P(axis)

    @jax.jit
    def reduced(x):
        return jax.shard_map(
            lambda s: fn(s, axis), mesh=mesh, in_specs=spec, out_specs=spec
        )(x)

    return reduced


def collective_bytes_per_device(
    schedule: str, shard_bytes: float, p: int
) -> float:
    """Bytes each device moves over ICI for one all-reduce of a `shard_bytes`
    shard — the napkin model behind the §Perf collective-term hypotheses."""
    if p <= 1:
        return 0.0
    if schedule == "linear":
        return shard_bytes * (p - 1)
    if schedule == "binary-hopping":
        return shard_bytes * math.ceil(math.log2(p))
    if schedule == "tree":
        # XLA lowers to ring reduce-scatter + all-gather: 2(P-1)/P shards
        return shard_bytes * 2.0 * (p - 1) / p
    if schedule == "rs-ag":
        return shard_bytes * 2.0 * (p - 1) / p
    raise ValueError(schedule)
