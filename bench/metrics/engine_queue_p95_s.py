"""95th percentile of the engine's queue time (submit to the start of the
batch's execution, ``ShtFuture.timing["queue_s"]``) over the window's
requests."""

from common import percentile


def read(record):
    q = record["window"].get("queue_s")
    if not q:
        return None
    return percentile(q, 95)
