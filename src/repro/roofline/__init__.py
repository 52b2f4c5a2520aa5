"""Roofline analysis: compute / memory / collective terms derived from
compiled XLA programs (no real hardware)."""
from repro.roofline.analysis import (
    HW,
    HardwareSpec,
    collective_bytes,
    roofline_terms,
)

__all__ = ["HW", "HardwareSpec", "collective_bytes", "roofline_terms"]
