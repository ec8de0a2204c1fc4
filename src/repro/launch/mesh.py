"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax initialisation).

Topology: TPU v5e pods of 256 chips as a (16, 16) (data, model) mesh;
multi-pod adds a leading "pod" axis (pure DP across pods -> the cross-pod
collective traffic is one gradient all-reduce per step, the right shape
for DCI-connected pods).  `elastic_mesh` builds degraded topologies for
the fault-tolerance path.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "elastic_mesh", "sht_axis_names"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the model code shards through GSPMD sharding constraints,
    # which jax.make_mesh's default Explicit axes reject
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def elastic_mesh(n_devices: int, *, model: int = 16):
    """Degraded-topology mesh after losing hosts (n_devices multiple of
    ``model``); used by the elastic-restore tests."""
    assert n_devices % model == 0
    return jax.make_mesh((n_devices // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def sht_axis_names(mesh) -> tuple:
    """The SHT flattens every mesh axis into one S^2HAT process ring."""
    return tuple(mesh.axis_names)
