# Roofline analysis: compiled-artifact cost extraction + 3-term model,
# plus the analytic SHT cost model that drives make_plan's dispatch, the
# persistent per-hardware characterization DB behind mode="auto", and the
# serving engine's latency-target admission control.
from repro.roofline import admission, chardb  # noqa: F401
from repro.roofline.analysis import (  # noqa: F401
    BACKEND_MODELS, BackendModel, HW_HOST, HW_V5E, PEAKS, Hardware,
    Roofline, analyze_compiled, collective_bytes, hardware_for,
    parse_hlo_collectives,
    predict_sht_time, sht_work,
)
