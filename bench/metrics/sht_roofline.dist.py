"""Share of the roofline of the whole transform over the chips it runs
on: the least time of one call over all of them, the larger of counted
flops over their summed bf16 peaks and counted bytes over their summed
HBM bandwidth (``bench/work.py``, ``bench/peaks.json``), over the chips'
mean busy time per call in the traced window."""


def read(record):
    t, peaks = record.get("trace"), record.get("peaks")
    calls = record["window"].get("calls")
    if not t or not peaks or not calls or t["busy_s"] <= 0 \
            or not t["n_devices"]:
        return None
    w, n = record["work"], t["n_devices"]
    least = max(w["total_flops"] / (n * peaks["bf16_flops_per_s"]),
                w["bytes"] / (n * peaks["hbm_bytes_per_s"]))
    return 100.0 * least / (t["busy_s"] / calls)
