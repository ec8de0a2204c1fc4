"""Compiles for a described TPU v5e chip: no chip needed, nothing runs.

Every kernel chain ``make_plan`` dispatches to on a TPU is lowered through
Mosaic (not interpret mode) and compiled by the TPU compiler installed
here, for a chip that is described, not attached.  This catches what
interpret mode cannot: block shapes off the (8, 128) tiling, programs
that do not fit HBM, and float64 reaching the device (the 64-bit mode the
package once switched on globally made the compiler abort even for a
float32 plan).

Width: the kernels are compiled at ``l_max=1024``, not the paper's 4096.
At 4096 one fused analysis compile takes ~35 s; 1024 keeps the ring axis
several blocks deep in both variants -- 9 MXU ring blocks (1025 rings in
blocks of 128: more than one, not a multiple of 8, like the 33 blocks at
4096) and 2 VPU ring blocks (rings padded to 2048 in blocks of 1024) --
which is what the compile failures at 4096 depended on.  A chip run
(``chip_smoke.py``) covers the full width.

The topology is described inside a module fixture, so only the worker
that runs this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro
from repro.kernels import ops

L_MAX = 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def tpu_like(monkeypatch):
    """Trace as on a TPU backend: Pallas kernels for Mosaic, and JAX's
    64-bit mode off (the test session turns it on for the float64
    oracle; the package itself never does)."""
    monkeypatch.setattr(ops, "should_interpret", lambda: False)
    with jax.enable_x64(False):
        yield


def _compile(plan, direction, backend, layout, device):
    """Lower + compile one plan direction for ``device``; returns the
    compiled executable.  The plan's precomputed tables are arguments of
    the jitted function (`transform._bind`), given here as shapes."""
    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=device)
    if direction == "synth":
        fn = plan._synth_fn(backend, layout)
        arg = jax.ShapeDtypeStruct(plan._alm_shape, jnp.complex64,
                                   sharding=device)
    else:
        fn = plan._anal_fn(backend, layout)
        arg = jax.ShapeDtypeStruct(plan._maps_shape, jnp.float32,
                                   sharding=device)
    if hasattr(fn, "func"):                 # bound pallas plan function
        consts = jax.tree.map(spec, fn.keywords["consts"])
        lowered = fn.func.lower(arg, consts=consts)
    else:
        lowered = fn.lower(arg)
    return lowered.compile()


def _plan(K, mode, l_max=L_MAX):
    # cache="off": a plan memoised by another test under 64-bit mode
    # would carry float64 tables
    return repro.make_plan("gl", l_max=l_max, K=K, dtype="float32",
                           mode=mode, cache="off")


@pytest.mark.parametrize("direction", ["synth", "anal"])
@pytest.mark.parametrize("variant", ["vpu", "mxu"])
@pytest.mark.parametrize("K", [1, 4])
def test_fused_kernels_compile(K, variant, direction, one_chip, tpu_like):
    plan = _plan(K, f"pallas_{variant}")
    compiled = _compile(plan, direction, f"pallas_{variant}", "fused",
                        one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    # lane-dense operands: no K-minor tile padding blows the temp buffers
    # up (it was 64x at K=1: 13 GB at l_max=4096)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 8 * mem.argument_size_in_bytes


@pytest.mark.parametrize("direction", ["synth", "anal"])
@pytest.mark.parametrize("layout", ["packed", "plain"])
def test_staged_mxu_kernels_compile(layout, direction, one_chip, tpu_like):
    plan = _plan(1, "pallas_mxu")
    compiled = _compile(plan, direction, "pallas_mxu", layout, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("direction", ["synth", "anal"])
def test_float32_jnp_plan_compiles(direction, one_chip, tpu_like):
    """The x64 regression: with 64-bit mode on, even this plan made the
    TPU compiler abort (f64 -> c128 conversion)."""
    plan = _plan(2, "jnp", l_max=64)
    compiled = _compile(plan, direction, "jnp", None, one_chip)
    assert "f64" not in compiled.as_text()


@pytest.mark.parametrize("direction", ["synth", "anal"])
def test_jnp_plan_is_lane_dense_at_k4(direction, one_chip, tpu_like):
    """The jnp loops keep K off the minor dimension: with (L, M, K) scan
    stacks and (M, R, K) accumulators the TPU pads K=4 to 128 lanes (the
    analysis needed 16.7 GB of the chip's 15.75 GB at l_max=4096)."""
    plan = _plan(4, "jnp")
    mem = _compile(plan, direction, "jnp", None, one_chip).memory_analysis()
    assert mem.temp_size_in_bytes < 8 * mem.argument_size_in_bytes


def test_blocked_jnp_analysis_compiles_at_k4(one_chip, tpu_like, monkeypatch):
    """The jnp analysis in row blocks (each run from its first non-zero l)
    compiles for the chip with no more temp memory than the single full
    loop: its carries shrink to one block's rows."""
    from repro.core import legendre
    full = _compile(_plan(4, "jnp"), "anal", "jnp", None,
                    one_chip).memory_analysis()
    row_bytes = legendre.row_step_bytes("anal", fold=False,
                                        n_rings=L_MAX + 1, K=4,
                                        dtype="float32")
    monkeypatch.setattr(legendre, "BLOCK_STEP_BYTES",
                        (L_MAX + 1) * row_bytes // 4)
    plan = _plan(4, "jnp")
    assert plan.describe()["legendre"]["jnp_blocks"]["anal"]["blocks"] == 4
    blocked = _compile(plan, "anal", "jnp", None, one_chip).memory_analysis()
    assert blocked.temp_size_in_bytes <= full.temp_size_in_bytes
