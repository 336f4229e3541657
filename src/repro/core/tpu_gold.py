"""TPU-side Gold Standard: roofline constants and term math.

The paper's "ideal clocking" objective ("the BRAM is the frequency limit;
nothing else may degrade it") translates on TPU to: *the roofline term of
the limiting hardware unit is the step-time lower bound; nothing else may
dominate it*.  For bandwidth-bound GEMV/decode the limiting unit is HBM
(the TPU's "BRAM"); for training GEMM it is the MXU.

Terms (seconds), per the assignment spec:

    compute    = HLO_FLOPs        / (chips * peak_flops)
    memory     = HLO_bytes        / (chips * hbm_bw)
    collective = collective_bytes / (chips * ici_bw)

The constants are published peaks, looked up by the device's
`device_kind` in `CHIPS_BY_DEVICE_KIND`; the terms are derived from
compiled artifacts or byte counts, never wall-clock, so they are
predictions wherever they are printed.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Hardware constants of one accelerator chip."""

    name: str
    peak_flops_bf16: float  # FLOP/s
    hbm_bandwidth: float    # bytes/s
    hbm_capacity: float     # bytes
    ici_bandwidth: float    # bytes/s per link
    ici_links: int          # usable links per chip (2D torus -> 4)
    vmem_bytes: float = 128 * 1024 * 1024 / 2  # ~64 MiB usable VMEM

    @property
    def flops_per_byte(self) -> float:
        """Machine balance: arithmetic intensity at the roofline ridge."""
        return self.peak_flops_bf16 / self.hbm_bandwidth


# Hardware constants mandated by the assignment (TPU v5e-like).
TPU_V5E = ChipSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    hbm_bandwidth=819e9,
    hbm_capacity=16 * 1024**3,
    ici_bandwidth=50e9,
    ici_links=4,
)


#: roofline constants by `jax.Device.device_kind` — the one table every
#: roofline prediction is priced from. A TPU kind missing here is an
#: error, never a silent default.
CHIPS_BY_DEVICE_KIND: Dict[str, ChipSpec] = {
    "TPU v5 lite": TPU_V5E,
    "TPU v5e": TPU_V5E,
}


def chip_for_device(device) -> ChipSpec:
    """The `ChipSpec` of a `jax.Device`. A non-TPU device (a CPU test
    run) prices its predictions against `TPU_V5E`, the deployment
    target; an unknown TPU kind raises."""
    if device.platform != "tpu":
        return TPU_V5E
    kind = device.device_kind
    if kind not in CHIPS_BY_DEVICE_KIND:
        raise ValueError(
            f"no roofline constants for TPU device kind {kind!r}; add it to "
            "core.tpu_gold.CHIPS_BY_DEVICE_KIND with its published peaks"
        )
    return CHIPS_BY_DEVICE_KIND[kind]


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    """Three-term roofline for one (arch x shape x mesh) cell."""

    cell: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float  # summed operand bytes of all collectives
    model_flops: float       # 6*N*D (train) or 2*N_active*tokens (serve)
    compute_s: float
    memory_s: float
    collective_s: float
    bytes_per_device: float = 0.0

    @property
    def bound(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — fraction of compiled compute that is
        algorithmically necessary (catches remat / redundancy waste)."""
        if self.hlo_flops <= 0:
            return float("nan")
        return self.model_flops / self.hlo_flops

    @property
    def roofline_fraction(self) -> float:
        """How close the *useful* work is to the step-time lower bound.

        = (time to do the per-device MODEL_FLOPS share at peak) / (max
        roofline term). 1.0 means the dominant term is fully useful
        compute — the TPU equivalent of "clocking at BRAM Fmax with 100%
        BRAMs as PIMs". For memory-bound cells the gold state is instead
        `gold_memory_fraction` == 1 with memory_s at its analytic floor.
        """
        if self.bound_s <= 0:
            return float("nan")
        # model_flops is stored per-device (divided by chips at build time)
        ideal = self.model_flops / TPU_V5E.peak_flops_bf16
        return ideal / self.bound_s

    @property
    def gold_memory_fraction(self) -> float:
        """memory_term / bound — 1.0 when HBM is the limit (the paper's
        gold state for GEMV-like workloads)."""
        if self.bound_s <= 0:
            return float("nan")
        return self.memory_s / self.bound_s

    def as_dict(self) -> Dict[str, object]:
        return {
            "cell": self.cell,
            "chips": self.chips,
            "hlo_flops": self.hlo_flops,
            "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bound": self.bound,
            "bound_s": self.bound_s,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "bytes_per_device": self.bytes_per_device,
        }


def roofline_terms(
    cell: str,
    chips: int,
    hlo_flops: float,
    hlo_bytes: float,
    collective_bytes: float,
    model_flops: float,
    chip: ChipSpec = TPU_V5E,
    bytes_per_device: float = 0.0,
) -> RooflineTerms:
    """Build the three-term roofline from compiled-artifact statistics.

    `hlo_flops`/`hlo_bytes` come from ``compiled.cost_analysis()`` and are
    *per-device* numbers under SPMD (XLA reports the per-partition module),
    so the per-chip denominators use a single chip's peak.
    """
    compute_s = hlo_flops / chip.peak_flops_bf16
    memory_s = hlo_bytes / chip.hbm_bandwidth
    # Collectives move `collective_bytes` per device through `ici_links`
    # links; a ring all-reduce moves 2x the shard, which is already
    # reflected in the per-op operand sizes we sum from the HLO.
    collective_s = collective_bytes / (chip.ici_bandwidth * chip.ici_links)
    return RooflineTerms(
        cell=cell,
        chips=chips,
        hlo_flops=hlo_flops,
        hlo_bytes=hlo_bytes,
        collective_bytes=collective_bytes,
        model_flops=model_flops / max(chips, 1),
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        bytes_per_device=bytes_per_device,
    )


def model_flops_train(n_params: float, tokens: float) -> float:
    """Standard 6*N*D training FLOPs (fwd 2ND + bwd 4ND)."""
    return 6.0 * n_params * tokens


def model_flops_serve(n_active_params: float, tokens: float) -> float:
    """2*N_active*D forward FLOPs for inference."""
    return 2.0 * n_active_params * tokens


def bitplane_bandwidth_amplification(weight_bits: int, dense_bits: int = 16) -> float:
    """The paper's "100% of BRAM bandwidth is useful" objective, TPU form:

    storing weights as packed bit-planes moves `weight_bits` bits per
    element instead of `dense_bits`, amplifying effective operand bandwidth
    by dense_bits/weight_bits for bandwidth-bound GEMV.
    """
    if weight_bits <= 0:
        raise ValueError("weight_bits must be positive")
    return dense_bits / weight_bits


def decode_step_lower_bound_s(
    param_bytes_per_chip: float,
    kv_bytes_per_chip: float,
    chip: ChipSpec = TPU_V5E,
) -> float:
    """Gold lower bound for one decode step: every weight + KV byte crosses
    HBM exactly once (the GEMV is memory bound). This is the TPU analogue
    of the paper's 'BRAM Fmax' clock: you cannot decode faster than HBM
    lets you stream the operands."""
    return (param_bytes_per_chip + kv_bytes_per_chip) / chip.hbm_bandwidth


def ridge_batch_for_gemm(chip: ChipSpec = TPU_V5E, bytes_per_el: int = 2) -> int:
    """Batch (tokens) at which a weight-stationary matmul crosses from
    memory-bound to compute-bound: B* = peak/bw * bytes_per_el / 2."""
    return int(math.ceil(chip.flops_per_byte * bytes_per_el / 2.0))
