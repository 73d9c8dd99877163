from repro.roofline.analysis import (
    PEAKS,
    TPU_V5E,
    HWSpec,
    analyze_hlo,
    hw_spec,
    roofline_terms,
)

__all__ = ["analyze_hlo", "roofline_terms", "HWSpec", "TPU_V5E", "PEAKS",
           "hw_spec"]
