"""Hardware constants.

Two distinct targets live here and must not be conflated:

* ``TPU_V5E`` — the *runtime* target for the JAX/Pallas layers and the
  roofline analysis (197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s ICI link),
  looked up by device kind through :func:`chip_spec`.
* ``SISA_ASIC`` — the paper's 28 nm 1 GHz accelerator instance (Table 3),
  used only by the cycle/energy simulator in ``repro.core``.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-chip roofline constants for the runtime target."""

    name: str
    peak_flops_bf16: float      # FLOP/s
    hbm_bytes: int              # capacity
    hbm_bw: float               # bytes/s
    ici_link_bw: float          # bytes/s per link, per direction
    ici_links: int              # links per chip (2D torus: 4)
    vmem_bytes: int             # VMEM per core
    mxu_dim: int                # systolic array dimension


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (4 links).
TPU_V5E = ChipSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    hbm_bytes=16 * 1024**3,
    hbm_bw=819e9,
    ici_link_bw=50e9,
    ici_links=4,
    vmem_bytes=128 * 1024**2,
    mxu_dim=128,
)

#: The one peaks table, keyed by ``jax.Device.device_kind``.
CHIP_SPECS = {"TPU v5 lite": TPU_V5E}


def chip_spec(device_kind: str) -> ChipSpec:
    """Peaks of the chip JAX reports as ``device_kind``; a chip with no
    entry in :data:`CHIP_SPECS` is an error, never a default."""
    try:
        return CHIP_SPECS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind "
                       f"{device_kind!r}; known: {sorted(CHIP_SPECS)}"
                       ) from None


@dataclasses.dataclass(frozen=True)
class AsicSpec:
    """Paper Table 3 + §4.2 constants for the SISA ASIC instance.

    Static (leakage) energies are nJ/cycle at 1 GHz; dynamic energies are
    pJ/byte (SRAM/DRAM) or pJ/MAC.  The paper reports the static numbers
    exactly (Table 3) and says dynamic SRAM/DRAM energies are "modeled
    separately using per-access energy parameters" without printing them —
    the values below are CACTI-scale estimates calibrated (see
    EXPERIMENTS.md §Calibration) so that the headline EDP claims
    (-93 % best case, +8.47 % worst case) are reproduced.
    """

    freq_hz: float = 1e9
    elem_bytes: int = 2                      # BF16 datapath

    # --- Table 3: per-cycle static energy (nJ/cycle) ---
    sa_static_nj: float = 21.60              # full 128x128 PE array
    global_buf_static_nj: float = 5.22       # 8 MB activation+weight
    slab_buf_static_nj: float = 0.12         # 8 KB + 64 KB per-slab buffers
    out_buf_static_nj: float = 1.25          # 2 MB output buffer

    # --- Table 3: area (mm^2) ---
    sa_area_mm2: float = 192.91
    global_buf_area_mm2: float = 22.45
    slab_buf_area_mm2: float = 0.30
    out_buf_area_mm2: float = 5.61

    # --- capacities ---
    global_buf_bytes: int = 8 * 1024**2
    out_buf_bytes: int = 2 * 1024**2
    slab_act_buf_bytes: int = 8 * 1024
    slab_wgt_buf_bytes: int = 64 * 1024

    # --- §4.2: off-chip ---
    dram_bw_bytes_per_s: float = 2.8e12      # HBM4-class

    # --- dynamic per-access energies (calibrated, see docstring) ---
    e_mac_pj: float = 0.8                    # per BF16 MAC
    e_global_sram_pj_per_byte: float = 4.0   # 8 MB banked, wide-port global buffer
    e_slab_sram_pj_per_byte: float = 2.5     # slab buffer access + bypass-mux datapath
    e_out_sram_pj_per_byte: float = 1.5     # 2 MB output buffer
    e_dram_pj_per_byte: float = 22.0         # HBM access energy

    @property
    def total_static_nj(self) -> float:
        return (self.sa_static_nj + self.global_buf_static_nj
                + self.slab_buf_static_nj + self.out_buf_static_nj)

    @property
    def total_area_mm2(self) -> float:
        return (self.sa_area_mm2 + self.global_buf_area_mm2
                + self.slab_buf_area_mm2 + self.out_buf_area_mm2)

    @property
    def dram_bytes_per_cycle(self) -> float:
        return self.dram_bw_bytes_per_s / self.freq_hz


SISA_ASIC = AsicSpec()

# The TPU-like monolithic baseline of §4.2: same SA, same total SRAM
# budget (two 4 MB buffers + 2 MB output), no slab buffers.  Streaming
# from the (smaller, two-ported) buffers is slightly cheaper per byte
# than SISA's banked 8 MB global buffer, but SISA's slab-local hop is
# what actually costs extra (modelled in repro.core.energy).
# Area/static derivation: §4.3 reports SISA's PE array carries a 3 %
# power-gating overhead (2.7 % of total chip area) and its SRAM layout an
# extra 2.74 % of total, for +5.44 % overall.  Inverting from SISA's
# Table 3 totals gives the baseline below.
TPU_BASELINE_ASIC = dataclasses.replace(
    SISA_ASIC,
    sa_static_nj=21.60 / 1.03,               # no gating transistors
    slab_buf_static_nj=0.0,
    sa_area_mm2=192.91 / 1.03,
    global_buf_area_mm2=16.95,               # 2x4 MB, narrow ports
    slab_buf_area_mm2=0.0,
    out_buf_area_mm2=5.61,
    slab_act_buf_bytes=0,
    slab_wgt_buf_bytes=0,
    e_global_sram_pj_per_byte=2.8,
)
