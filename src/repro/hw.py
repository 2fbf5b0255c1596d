"""Hardware specs: the chips the Pallas kernels are tiled for.

The serving path runs on a TPU; tests and planning also run on the CPU,
where the Pallas kernels execute in interpret mode.  A run on a chip takes
its spec from the device itself (:func:`spec_for_device`, keyed by the
``device_kind`` JAX reports); planning off-chip names a spec
(:func:`get_spec`, ``launch.serve --hw-spec``) or uses :data:`DEFAULT`.
An unknown device kind is an error, never a default.

All roofline arithmetic in :mod:`repro.launch.roofline` and all DSE cost
models in :mod:`repro.core.dse` read their constants from here.  The TPU
v5e peaks are the published ones (Google Cloud, "TPU v5e"): 197 TFLOP/s
bf16 and 394 TOP/s int8 per chip, 16 GB of HBM at 819 GB/s.  VMEM is
what the chip's compiler reports (128 MiB); :func:`vmem_budget` is the
share of it one kernel asks Mosaic for (``vmem_limit_bytes``) and the
ceiling the DSE sizes tiles against.  The memory hierarchy (HBM -> VMEM ->
VREG) replaces the paper's (DRAM -> scratchpad/PMU -> pipeline
register/PCU) hierarchy on Plasticine.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """A single accelerator chip plus its interconnect."""

    name: str
    # --- compute ---------------------------------------------------------
    peak_bf16_flops: float      # FLOP/s, MXU bf16 multiply / f32 accumulate
    peak_int8_ops: float        # OP/s, MXU int8 multiply / i32 accumulate
    # --- memory ----------------------------------------------------------
    hbm_bytes: float            # per-chip HBM capacity
    hbm_bw: float               # bytes/s HBM <-> VMEM
    vmem_bytes: float           # on-chip vector memory (the paper's scratchpad)
    vmem_bw: float              # bytes/s VMEM <-> VREG (approximate)
    # --- interconnect ----------------------------------------------------
    ici_link_bw: float          # bytes/s per ICI link (one direction)
    ici_links: int              # links per chip (2D torus on v5e)
    dcn_bw: float               # bytes/s per host for cross-pod (DCN) traffic
    # --- micro-architecture ----------------------------------------------
    mxu_dim: int = 128          # systolic array edge: matmul dims should be
                                # multiples of this for full utilization
    vreg_lanes: int = 8         # (8, 128) native vector registers
    vreg_sublanes: int = 128
    # --- energy model (approximate, for the paper's power analysis) ------
    pj_per_flop_bf16: float = 0.25     # pJ per bf16 FLOP, MXU
    pj_per_byte_hbm: float = 120.0     # pJ per byte moved HBM<->VMEM
    pj_per_byte_vmem: float = 6.0      # pJ per byte moved VMEM<->VREG
    pj_per_byte_ici: float = 40.0      # pJ per byte over ICI
    idle_watts: float = 70.0           # static power per chip

    @property
    def peak_flops(self) -> float:
        return self.peak_bf16_flops

    def matmul_time(self, flops: float, dtype_bits: int = 16) -> float:
        """Roofline compute time for `flops` at the given precision."""
        peak = self.peak_int8_ops if dtype_bits <= 8 else self.peak_bf16_flops
        return flops / peak

    def hbm_time(self, nbytes: float) -> float:
        return nbytes / self.hbm_bw

    def ici_time(self, nbytes: float) -> float:
        """Time to move `nbytes` off-chip over all links (best case)."""
        return nbytes / (self.ici_link_bw * self.ici_links)


TPU_V5E = HardwareSpec(
    name="tpu-v5e",
    peak_bf16_flops=197e12,
    peak_int8_ops=394e12,
    hbm_bytes=16e9,
    hbm_bw=819e9,
    vmem_bytes=128 * 2**20,
    vmem_bw=10e12,
    ici_link_bw=50e9,
    ici_links=4,
    dcn_bw=25e9,
)

# The paper's comparison targets, kept for the DeepBench benchmark tables
# (Section 5, Tables 4-6).  Only the fields used by the benchmark report are
# meaningful; others are order-of-magnitude placeholders.
PLASTICINE = HardwareSpec(
    name="plasticine-rnn-variant",
    peak_bf16_flops=12.5e12,     # peak 32-bit from Table 4; 8-bit peak = 49T
    peak_int8_ops=49e12,
    hbm_bytes=16e9,
    hbm_bw=100e9,
    vmem_bytes=int(384 * 84e3),  # 384 PMUs x 84 kB scratchpads (Table 3)
    vmem_bw=4e12,
    ici_link_bw=0.0,
    ici_links=0,
    dcn_bw=0.0,
)

# the planning target when no spec is named and no chip is attached
DEFAULT = TPU_V5E

# name -> spec, for CLI flags (launch.serve --hw-spec) and plan provenance
SPECS = {spec.name: spec for spec in (TPU_V5E, PLASTICINE)}

# device_kind, as jax.devices()[i].device_kind reports it -> spec
DEVICE_KINDS = {"TPU v5 lite": TPU_V5E}

# share of VMEM one kernel claims; Mosaic keeps the rest for its own
# internal scratch and spills
VMEM_FRACTION = 0.75


def get_spec(name: str) -> HardwareSpec:
    if name not in SPECS:
        raise KeyError(f"unknown hardware spec {name!r}; "
                       f"known: {sorted(SPECS)}")
    return SPECS[name]


def spec_for_device(device) -> HardwareSpec:
    """The spec of an attached device, by its ``device_kind``."""
    kind = device.device_kind
    if kind not in DEVICE_KINDS:
        raise KeyError(f"no hardware spec for device_kind {kind!r} "
                       f"(platform {device.platform}); known kinds: "
                       f"{sorted(DEVICE_KINDS)}")
    return DEVICE_KINDS[kind]


def vmem_budget(hw: HardwareSpec = DEFAULT) -> int:
    """VMEM bytes one Pallas kernel may use: every kernel passes this as
    ``vmem_limit_bytes`` and the DSE only calls a tile resident when its
    working set fits under it."""
    return int(hw.vmem_bytes * VMEM_FRACTION)
