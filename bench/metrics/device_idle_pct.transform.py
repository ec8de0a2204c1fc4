"""Share of the traced window in which no operation ran on the device,
in the library cells (1 - union of device-op intervals / window)."""


def read(record):
    t = record.get("trace")
    if not t or t["window_s"] <= 0 or not t["n_devices"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
