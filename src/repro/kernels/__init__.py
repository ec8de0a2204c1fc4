"""Pallas TPU kernel layer for the Legendre-recurrence hot spot.

``legendre_pallas`` holds the kernels (VPU broadcast-FMA and MXU panel
matmul variants, paper §4.2.2 translated to TPU), ``ops`` the jit'd
padding/layout wrappers and the ``stage1="pallas"`` adapters used by
``DistSHT``, and ``ref`` the bit-matched jnp oracles the kernels are
validated against.

Callers normally do not import this package directly: ``repro.make_plan``
dispatches into it when a plan selects a ``pallas_*`` backend.
"""

from repro.kernels import ops  # noqa: F401
from repro.kernels import pack  # noqa: F401
from repro.kernels.ops import (  # noqa: F401
    alm_from_delta_auto, anal, delta_from_alm_auto, pick_layout,
    pick_variant, should_interpret, synth,
)
