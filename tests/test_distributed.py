"""Multi-device equivalence tests (subprocess: 8 host-platform devices;
this process stays single-device per the dry-run isolation rule)."""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(helper, *args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tests", "helpers", helper),
         *args], capture_output=True, text=True, timeout=560, env=env)
    assert r.returncode == 0, f"{helper} failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout


def test_dist_sht_matches_serial():
    out = _run("dist_sht_check.py")
    assert out.count("OK") == 11   # incl. the 2 shard_map gradcheck lines


def test_dist_chunked_exchange_matches_monolithic():
    # chunked pipelined all_to_all (C=2,4) vs the monolithic C=1 path:
    # bit-identical synthesis, <1e-12 analysis, spin 0 + spin 2, K-axis
    # and m-axis schedules, grad through the chunked pipeline, and the
    # fail-fast mesh ValueError (4 simulated devices).
    out = _run("dist_chunk_check.py")
    assert out.count("OK") == 10
    assert "bit-identical=True" in out


@pytest.mark.parametrize("case", ["float32", "float64", "blocks"])
def test_dist_plan_matches_serial(case):
    # make_plan(mode="dist") on 4 simulated devices against the serial
    # float64 transform, both directions: jnp stage 1 with no layout
    # warning, one exchange chunk, and (case "blocks") each shard's rows in
    # two row blocks; the exchange and reshard scopes in its program.
    out = _run("dist_plan_check.py", case)
    assert out.count("OK") == 4, out


def test_moe_expert_parallel_matches_local():
    out = _run("moe_dist_check.py")
    assert "a2a_err" in out


def test_ulysses_attention_matches_mea():
    out = _run("ulysses_check.py")
    assert "ulysses_err" in out
