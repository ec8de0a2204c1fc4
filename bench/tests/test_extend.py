"""A configuration, a traffic mix and a per-layer metric added as new
files and new entries only: the harness finds and runs them, and no file
that was there changes."""

from __future__ import annotations

import hashlib
import json
import os

from conftest import run_cell, tiny_config


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            out[p] = hashlib.sha256(open(p, "rb").read()).hexdigest()
    return out


def test_new_cell_from_new_files_only(tiny_root, capsys):
    root = tiny_root
    bench_path = os.path.join(root, "BENCHMARK.json")
    before = _digests(root)
    b = os.path.join(root, "bench")
    cfg = dict(tiny_config("tiny_other", False), l_max=12, m_max=12,
               n_rings=13, n_phi=26)
    json.dump(cfg, open(os.path.join(b, "configs", "tiny_other.json"), "w"))
    json.dump({"loop": "library_closed", "direction": "alm2map", "K": 2,
               "pool": 3}, open(os.path.join(b, "traffic", "synth_k2.json"),
                                "w"))
    json.dump({"block": 4, "limits": {"row_rel_max": 1e-4}},
              open(os.path.join(b, "checks", "tiny.synth_k2.json"), "w"))
    with open(os.path.join(b, "metrics", "calls_in_window.py"), "w") as f:
        f.write("def read(record):\n"
                "    return float(record['window']['calls'])\n")
    spec = json.load(open(bench_path))
    spec["configs"].append({"name": "tiny_other", "source": "test",
                            "file": "bench/configs/tiny_other.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.synth_k2", "config": "tiny_other",
                              "traffic": "synth_k2", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "transform_s":
            m["workloads"].append("tiny.synth_k2")
    spec["per_layer"].append({"name": "calls_in_window", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "transform_s",
                              "workloads": ["tiny.synth_k2"]})
    json.dump(spec, open(bench_path, "w"))

    rc, res = run_cell(root, "tiny.synth_k2", capsys=capsys)
    assert rc == 0 and res["correct"] is True
    assert set(res["metrics"]) == {"transform_s", "setup_s"}
    rc, res = run_cell(root, "tiny.synth_k2", trace=1, capsys=capsys)
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["calls_in_window"]["value"] >= 1
    after = _digests(root)
    assert {p: h for p, h in after.items() if p in before} == before
