"""Maps per device batch that the engine formed inside the window (the
engine's coalescing counters, differenced across the window)."""


def read(record):
    co = record["window"].get("coalescing")
    if not co or co["batches"] <= 0:
        return None
    return co["maps"] / co["batches"]
