"""Device seconds per call in the ops under the program's phase scope
(``repro.tracing.PHASE``: the ring FFTs, the phase rotation and the
quadrature weights), over the window's calls.  A time per call, so that
a change to another stage does not move it."""

import program_trace as pt


def read(record):
    per_call = pt.scopes_per_call(pt.of_reader(__file__),
                                  record["window"].get("calls"))
    return None if per_call is None else per_call.get(pt.names().PHASE, 0.0)
