"""Share of the roofline of the whole transform (Legendre and phase
stages): the least time the chip could take for one call, the larger of
counted flops over the published bf16 peak and counted bytes over the HBM
bandwidth (``bench/work.py``, ``bench/peaks.json``), over the device's
busy time per call in the traced window."""


def read(record):
    t, peaks = record.get("trace"), record.get("peaks")
    calls = record["window"].get("calls")
    if not t or not peaks or not calls or t["busy_s"] <= 0:
        return None
    w = record["work"]
    least = max(w["total_flops"] / peaks["bf16_flops_per_s"],
                w["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (t["busy_s"] / calls)
