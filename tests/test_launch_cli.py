"""CLI smoke tests: the launch drivers run end to end (reduced widths)."""

import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=540, cache_dir=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    if cache_dir is not None:       # keep the CLI's compile cache out of
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)   # the checkout
    r = subprocess.run([sys.executable] + args, capture_output=True,
                       text=True, timeout=timeout, env=env, cwd=_ROOT)
    assert r.returncode == 0, f"{args} failed:\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    return r.stdout


def test_train_cli_smoke(tmp_path):
    out = _run(["-m", "repro.launch.train", "--arch", "qwen2-0.5b",
                "--smoke", "--steps", "6", "--global-batch", "2",
                "--seq", "32", "--ckpt", str(tmp_path), "--ckpt-every", "3"])
    assert "training complete" in out
    assert any(d.startswith("step_") for d in os.listdir(tmp_path))


def test_serve_cli_smoke(tmp_path):
    out = _run(["-m", "repro.launch.serve", "--smoke", "--requests", "4"],
               cache_dir=tmp_path)
    assert "completed 4/4" in out
    assert "coalescing" in out


def test_quickstart_example():
    out = _run(["examples/quickstart.py", "--lmax", "32"])
    assert "D_err" in out
