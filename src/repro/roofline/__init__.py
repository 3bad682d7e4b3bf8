from repro.roofline.analysis import (
    HW,
    PEAKS,
    V5E,
    hw_for,
    collective_bytes,
    roofline_report,
    model_flops,
)
