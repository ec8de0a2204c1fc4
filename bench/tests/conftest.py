"""CPU tests of the harness: small cells in a copy of the checkout.

``tiny_root`` builds a checkout in a temporary directory: ``bench/`` copied,
``src`` linked, and a ``BENCHMARK.json`` whose cells are the real traffic
mixes on a configuration cut to l_max 16 (runs in seconds on a CPU).
"""

from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(REPO, "src"))

TINY_L = 16

#: the tiny cells: name -> (traffic mix, limits)
TINY_CELLS = {
    "tiny.synth_k1": "synth_k1",
    "tiny.anal_k4": "anal_k4",
    "tiny.serve_open": "serve_open",
    "tiny.serve_closed": "serve_closed",
    "tiny.anal_open": "anal_open",
    "tiny.anal_closed": "anal_closed",
}
#: float32 on the CPU reads ~1e-6 here; a wrong answer reads ~1e-1
TINY_LIMITS = {"row_rel_max": 1e-4}
#: rows a block of the check's sample: at l_max 16, five blocks
TINY_BLOCK = 4
#: the open mixes' rate here (their files carry the chip's)
TINY_RATE = 16.0


def tiny_config(name: str, engine: bool) -> dict:
    cfg = {"name": name, "source": "test", "grid": "gl", "l_max": TINY_L,
           "m_max": TINY_L, "n_rings": TINY_L + 1,
           "n_phi": 2 * TINY_L + 2, "spin": 0, "dtype": "float32",
           "mode": "jnp", "reduced": ["l_max"]}
    if engine:
        cfg["engine"] = {"max_k": 8, "max_queue": 4096}
    return cfg


def build_root(path: str) -> str:
    """A checkout at ``path`` with the tiny cells (see module docstring)."""
    shutil.copytree(BENCH, os.path.join(path, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(path, "src"))
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    configs = []
    for name, engine in (("tiny_lib", False), ("tiny_engine", True)):
        f = f"bench/configs/{name}.json"
        with open(os.path.join(path, f), "w") as fh:
            json.dump(tiny_config(name, engine), fh)
        configs.append({"name": name, "source": "test", "file": f,
                        "reduced": ["l_max"], "why": "test"})
    cells = []
    for cell, mix in TINY_CELLS.items():
        engine = "open" in mix or "closed" in mix
        cells.append({"name": cell, "config": "tiny_engine" if engine
                      else "tiny_lib", "traffic": mix, "chips": 1,
                      "why": "test"})
        with open(os.path.join(path, "bench", "checks", cell + ".json"),
                  "w") as fh:
            json.dump({"block": TINY_BLOCK, "limits": TINY_LIMITS}, fh)
    for mix in ("serve_open", "anal_open"):
        p = os.path.join(path, "bench", "traffic", mix + ".json")
        tr = json.load(open(p))
        json.dump(dict(tr, rate=TINY_RATE), open(p, "w"))
    eng = [c for c, mix in TINY_CELLS.items()
           if "open" in mix or "closed" in mix]
    lib = [c for c in TINY_CELLS if c not in eng]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = lib if any(w.startswith("cmb") for w in
                                        m["workloads"]) else eng
            if m["name"] == "engine_k_per_batch":
                m["workloads"] = ["tiny.serve_open", "tiny.anal_open"]
    bench.update(configs=configs, workloads=cells)
    with open(os.path.join(path, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh, indent=1)
    return path


@pytest.fixture
def tiny_root(tmp_path):
    return build_root(str(tmp_path))


def run_cell(root, cell, *, seed=5, seconds=1.0, trace=0, patch=None,
             capsys=None):
    """``bench/run.py`` in-process on the CPU; returns (rc, result)."""
    import run
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  require_tpu=False, patch=patch)
    out = capsys.readouterr().out if capsys is not None else ""
    lines = [ln for ln in out.splitlines() if ln.startswith("{")]
    return rc, (json.loads(lines[-1]) if lines else None)
