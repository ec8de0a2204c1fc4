"""Check that a change leaves the benchmark cells' compiled programs alone.

Compiles, for a described TPU v5e (no chip needed), the programs the
three benchmark cells run and writes each optimized HLO text with its
metadata stripped: the ``metadata={...}`` attributes and the
FileNames/FunctionNames/FileLocations/StackFrames tables, which hold
source paths and line numbers and so differ between any two checkouts.

    # the programs of the source tree on PYTHONPATH
    PYTHONPATH=<tree>/src JAX_PLATFORMS=cpu \\
        python scripts/hlo_identity.py dump <out_dir>
    # identical / DIFFER per program, with sha256 prefixes
    python scripts/hlo_identity.py compare <out_dir_a> <out_dir_b>

Dump the parent (``git archive <rev> | tar -x -C <dir>``) and the change
each with its own ``src`` on PYTHONPATH, then compare.  The programs:
``make_plan("gl", l_max=4096, K=4, float32, mode="jnp")`` analysis
(``cmb4k.anal_k4``); the ``l_max=1024`` engine plans at K = 1, 2, 4, 8,
analysis (``mc1k.anal_open``); the four-chip ``DistSHT`` analysis at
``l_max=4096``, K=4, C=1 (``cmb4k.dist_anal_k4``).
"""

import hashlib
import inspect
import os
import re
import sys

SOURCE_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames")


def strip(text: str) -> str:
    """HLO text without metadata attributes or source-location tables."""
    text = re.sub(r",?\s*metadata=\{[^}]*\}", "", text)
    out, skip = [], False
    for line in text.splitlines():
        if line in SOURCE_TABLES:
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            out.append(line)
    return "\n".join(out)


def _digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def dump(out_dir: str) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    import repro
    from repro.core import grids
    from repro.core.dist_sht import DistSHT
    from repro.core.plan import SHTPlan

    os.makedirs(out_dir, exist_ok=True)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    mesh = Mesh(np.array(topo.devices), ("sht",))

    def save(name, compiled):
        text = strip(compiled.as_text())
        with open(os.path.join(out_dir, name + ".hlo"), "w") as f:
            f.write(text)
        print(name, _digest(text), len(text), flush=True)

    def anal(plan, name):
        arg = jax.ShapeDtypeStruct(plan._maps_shape, jnp.float32,
                                   sharding=one)
        save(name, plan._anal_fn("jnp").lower(arg).compile())

    with jax.enable_x64(False):
        anal(repro.make_plan("gl", l_max=4096, K=4, dtype="float32",
                             mode="jnp", cache="off"), "cmb4k_anal_k4")
        for K in (1, 2, 4, 8):
            anal(repro.make_plan("gl", l_max=1024, m_max=1024, K=K,
                                 dtype="float32", mode="jnp", cache="off"),
                 f"mc1k_anal_k{K}")
        sp = SHTPlan(grids.make_grid("gl", l_max=4096), 4096, 4096, 4)
        kw = dict(dtype="float32", fold=False, comm_chunks=1)
        if "stage1" in inspect.signature(DistSHT).parameters:
            kw["stage1"] = "jnp"        # older trees chose the stage 1
        _, an, c = DistSHT(sp, mesh, ("sht",), **kw)._build(4)
        sh = NamedSharding(mesh, P("sht"))

        def sds(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

        save("dist4_anal_k4", an.lower(
            sds((sp.r_pad, 2 * 4096 + 2, 4), jnp.float32),
            sds(c["m_flat"].shape, jnp.int32),
            sds(c["phi0"].shape, jnp.float32),
            sds(c["w"].shape, jnp.float32)).compile())


def compare(dir_a: str, dir_b: str) -> int:
    names = sorted(set(os.listdir(dir_a)) | set(os.listdir(dir_b)))
    differ = 0
    for name in names:
        texts = []
        for d in (dir_a, dir_b):
            path = os.path.join(d, name)
            texts.append(open(path).read() if os.path.exists(path) else None)
        same = texts[0] is not None and texts[0] == texts[1]
        differ += not same
        print(name, "identical" if same else "DIFFER",
              *(_digest(t) if t is not None else "missing" for t in texts))
    return 1 if differ else 0


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "dump":
        dump(sys.argv[2])
    elif len(sys.argv) == 4 and sys.argv[1] == "compare":
        sys.exit(compare(sys.argv[2], sys.argv[3]))
    else:
        sys.exit(__doc__)
