"""repro: Parallel Spherical Harmonic Transforms as a multi-pod JAX framework.

Implements Szydlarski et al. (INRIA RR-7635) -- the two-stage distributed SHT
with intra-node acceleration -- adapted to TPU (shard_map + Pallas), together
with the assigned 10-architecture LM model zoo, training/serving substrate,
multi-pod dry-run and roofline tooling.  See DESIGN.md.

Importing the package changes no JAX configuration.  Device code runs in
float32 (a TPU has no float64); the float64 reference engine
(``dtype="float64"``) needs ``jax_enable_x64``, which a caller turns on
itself on the CPU -- plans and engine requests refuse float64 otherwise.
"""

__version__ = "1.1.0"


def __getattr__(name):
    """Lazy top-level API: ``repro.make_plan`` / ``repro.Plan``.

    Imported on first use so ``import repro`` stays light (the transform
    layer pulls in the SHT engine; the Pallas kernels are only imported if
    a plan actually selects them).
    """
    if name in ("make_plan", "Plan", "available_backends",
                "backend_eligibility", "clear_plan_cache"):
        from repro.core import transform
        return getattr(transform, name)
    raise AttributeError(f"module 'repro' has no attribute {name!r}")
