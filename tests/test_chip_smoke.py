"""chip_smoke.py between chip runs: its phases at a tiny size on the CPU
(Pallas in interpret mode), its float64 numpy reference against the
library's oracle, its refusal to run without a TPU, and the compile-cache
helper its entry points share."""

import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

import repro
from repro import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_script()


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="module")
def clock():
    return cs.CompileClock()


def test_numpy_reference_matches_oracle(rng):
    l_max = 12
    plan = repro.make_plan("gl", l_max=l_max, K=2, dtype="float64",
                           mode="jnp")
    alm = cs.random_alm(rng, l_max, 2).astype(np.complex128)
    geo = cs.gl_geometry(l_max)
    cs.check_grid(plan.grid, geo)
    maps = cs.numpy_synth(alm, geo)
    want = np.asarray(plan.alm2map(alm))
    np.testing.assert_allclose(maps, want, rtol=0,
                               atol=1e-12 * np.abs(want).max())
    back = cs.numpy_anal(maps, geo, l_max)
    np.testing.assert_allclose(back, alm, rtol=0, atol=1e-12)
    np.testing.assert_allclose(back, np.asarray(plan.map2alm(want)),
                               rtol=0, atol=1e-12)


@pytest.mark.parametrize("field", ["cos_theta", "weights", "phi0"])
def test_check_grid_refuses_a_different_grid(field):
    """The reference is fed from its own geometry: a library grid that
    differs from it in any table fails the phase."""
    geo = cs.gl_geometry(8)
    grid = repro.make_plan("gl", l_max=8, K=1, dtype="float64",
                           mode="jnp").grid
    cs.check_grid(grid, geo)
    setattr(geo, field, getattr(geo, field) + 1e-9)
    with pytest.raises(AssertionError, match="leggauss"):
        cs.check_grid(grid, geo)


@pytest.mark.parametrize("direction", ["synth", "anal"])
def test_fused_phase_tiny(direction, rng, clock, capsys):
    """Both variants run; the MXU kernel in the one given direction."""
    cs.phase_fused("tiny", 16, 2, direction, rng, clock)
    out = capsys.readouterr().out
    assert "[fused/tiny/vpu]" in out and "[fused/tiny/mxu]" in out
    assert f"direction={direction}" in out and "'fused'" in out


def test_reference_phase_tiny(rng, clock, capsys):
    cs.phase_reference(12, 1, rng, clock)
    out = capsys.readouterr().out
    assert "[reference/lmax12/vpu]" in out and "[reference/lmax12/mxu]" in out


def test_auto_phase_tiny(rng, clock, capsys):
    cs.phase_auto(8, 1, rng, clock)
    assert "[auto/lmax8_k1]" in capsys.readouterr().out


def test_engine_phase_tiny(rng, clock, capsys):
    cs.phase_engine(8, 4, "pallas_mxu", rng, clock)
    out = capsys.readouterr().out
    assert "bit_identical_to_batch_plan=True" in out


def test_dist_phase_on_host_devices():
    """The --chips 4 path on 4 simulated host devices (subprocess)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    code = ("import sys, numpy as np; sys.path.insert(0, %r); "
            "import chip_smoke as cs; "
            "cs.phase_dist(16, 2, np.random.default_rng(0), "
            "cs.CompileClock())" % ROOT)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    # the plan's default C (1 here, so C = 1 is not run a second time)
    assert "/chunks=auto]" in r.stdout and "/chunks=1]" not in r.stdout
    assert "comm_chunks={'synth': 1, 'anal': 1}" in r.stdout
    assert "stage1=jnp" in r.stdout
    assert "operand_shards=[(0," in r.stdout


def test_failed_phase_is_reported_and_the_rest_run(rng, clock, capsys):
    ran = []

    def bad(r, c):
        cs.check("bad value", 2.0, 1.0)

    failed = cs.run_phases([("bad", bad),
                            ("good", lambda r, c: ran.append(True))],
                           rng, clock)
    assert failed == ["bad"] and ran == [True]
    assert "[FAILED bad]" in capsys.readouterr().out


def test_main_refuses_without_tpu(capsys):
    assert cs.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "needs 1 TPU chip" in captured.err


def test_script_alone_refuses(tmp_path):
    """Copied away from the package, the script exits non-zero and prints
    no result line."""
    script = tmp_path / "chip_smoke.py"
    script.write_text(open(os.path.join(ROOT, "chip_smoke.py")).read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=120, env=env, cwd=tmp_path)
    assert r.returncode != 0 and '"ok"' not in r.stdout


def test_compile_cache_dir(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    monkeypatch.delenv(compile_cache.ENV)
    first = compile_cache.cache_dir()
    assert first == compile_cache.cache_dir()
    assert first == os.path.join(ROOT, ".jax_cache")
