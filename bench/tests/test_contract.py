"""``BENCHMARK.json`` against the rules the harness relies on, and a run
without a TPU."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

from conftest import REPO

SPEC = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_names_and_units():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_named_file_exists():
    bench = os.path.join(REPO, "bench")
    for c in SPEC["configs"]:
        cfg = json.load(open(os.path.join(REPO, c["file"])))
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for w in SPEC["workloads"]:
        assert os.path.exists(os.path.join(bench, "traffic",
                                           w["traffic"] + ".json"))
        checks = json.load(open(os.path.join(bench, "checks",
                                             w["name"] + ".json")))
        assert checks["limits"]
    for m in SPEC["per_layer"]:
        assert os.path.exists(os.path.join(bench, "metrics",
                                           m["name"] + ".py"))


def test_every_cell_reports_setup_another_and_a_layer():
    from common import Cell
    for w in SPEC["workloads"]:
        cell = Cell(REPO, w["name"])
        e2e = {m["name"] for m in cell.end_to_end()}
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = cell.per_layer()
        assert layers and all(m["moves"] in e2e for m in layers)


def test_no_tpu_exits_nonzero_without_a_result(tiny_root, capsys):
    import run
    rc = run.main(["--workload", "tiny.synth_k1", "--seed", "1",
                   "--seconds", "1"], root=tiny_root)
    assert rc != 0
    assert not [ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("{")]


def test_only_benchmark_files_exits_nonzero(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ prints no result."""
    import shutil
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        SPEC["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
