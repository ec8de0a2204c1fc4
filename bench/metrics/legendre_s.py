"""Device seconds per call in the ops under the program's Legendre scope
(``repro.tracing.LEGENDRE``: the jnp scan, or the staged or fused Pallas
kernels, whichever backend the plan chose), over the window's calls."""

import program_trace as pt


def read(record):
    per_call = pt.scopes_per_call(pt.of_reader(__file__),
                                  record["window"].get("calls"))
    return None if per_call is None else per_call.get(pt.names().LEGENDRE,
                                                      0.0)
