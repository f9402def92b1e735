from repro.hw.specs import (AsicSpec, chip_spec, ChipSpec, SISA_ASIC,
                            TPU_BASELINE_ASIC, TPU_V5E)

__all__ = ["TPU_V5E", "chip_spec", "SISA_ASIC", "TPU_BASELINE_ASIC",
           "ChipSpec", "AsicSpec"]
