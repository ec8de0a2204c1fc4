"""Device seconds per call under the distributed plan's exchange scope
(``repro.tracing.EXCHANGE``: each chunk's all_to_all of the Delta block
with the packing and unpacking of its channels), averaged over the
chips, over the window's calls."""

import dist_trace
import program_trace as pt


def read(record):
    per = dist_trace.seconds_per_call(record, __file__)
    return (per.get(pt.names().EXCHANGE) or None) if per else None
