"""A tiny four-device distributed cell through ``bench/run.py`` on the CPU
(a subprocess with four host devices), the distributed plan's readers on
a made-up four-chip trace, and the exchange's byte count by hand.

A CPU profiler trace has no device planes, so the traced run here checks
that the readers of the dist cell run and stay silent; what they read is
checked on made-up device events."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import program_trace as pt
from conftest import BENCH, build_root, tiny_config
from exchange_work import exchange_work
from repro import tracing

CELL = "tiny.dist_anal_k4"
DIST_METRICS = ("exchange_s", "exchange_roofline", "sht_roofline.dist")
S = 1e9  # ns per second


def _dist_root(path: str) -> str:
    """The tiny checkout plus a four-chip dist cell at l_max 16 on the real
    ``anal_k4_dist`` mix, reporting what the real dist cell reports."""
    root = build_root(path)
    b = os.path.join(root, "bench")
    json.dump(dict(tiny_config("tiny_dist", False), mode="dist"),
              open(os.path.join(b, "configs", "tiny_dist.json"), "w"))
    json.dump({"block": 4, "limits": {"row_rel_max": 1e-4}},
              open(os.path.join(b, "checks", CELL + ".json"), "w"))
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": "tiny_dist", "source": "test",
                            "file": "bench/configs/tiny_dist.json",
                            "reduced": ["l_max"], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny_dist",
                              "traffic": "anal_k4_dist", "chips": 4,
                              "why": "test"})
    real = json.load(open(os.path.join(os.path.dirname(BENCH),
                                       "BENCHMARK.json")))
    mine = {m["name"] for m in real["end_to_end"] + real["per_layer"]
            if "cmb4k.dist_anal_k4" in m.get("workloads", ())}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in mine:
            m["workloads"].append(CELL)
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root


def _run(root, trace):
    code = ("import sys; sys.path.insert(0, %r); import run; "
            "sys.exit(run.main(['--workload', %r, '--seed', '3000000019', "
            "'--seconds', '1', '--trace', %r], root=%r, require_tpu=False))"
            % (os.path.join(root, "bench"), CELL, str(trace), root))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=root)
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert p.returncode == 0 and lines, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(lines[-1]), p.stdout


def test_dist_cell_runs_on_four_host_devices(tmp_path):
    root = _dist_root(str(tmp_path))
    res, out = _run(root, 0)
    assert res["correct"] is True, res
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"transform_s", "setup_s"}
    assert "'anal': 'dist'" in out          # the [plan] line
    res, _ = _run(root, 1)
    assert res["correct"] is True, res
    assert not set(DIST_METRICS) & set(res["metrics"])


def _four_chip_trace():
    """Two calls on four chips: per call and chip 0.25 s of exchange ops,
    0.5 s of Legendre ops and 0.1 s of reshard ops."""
    ex = f"jit(anal_shard)/shard_map/{tracing.EXCHANGE}/all-to-all:"
    leg = f"jit(anal_shard)/shard_map/{tracing.LEGENDRE}/x:"
    rs = f"jit(fn)/{tracing.RESHARD}/gather:"
    ops = {}
    for i in range(4):
        ev = []
        for c in range(2):
            t0 = (1 + 2 * c) * S
            ev += [[t0, 0.25 * S, "all-to-all.1", ex],
                   [t0 + 0.3 * S, 0.5 * S, "fusion.2", leg],
                   [t0 + 0.9 * S, 0.1 * S, "fusion.3", rs]]
        ops[f"/device:TPU:{i}"] = ev
    return {"ops": ops, "modules": {}, "cut_at": dict.fromkeys(ops),
            "spans": [[0, 6 * S, "window", None, "python"]],
            "path": "made-up-dist"}


def test_dist_readers_on_a_four_chip_trace(monkeypatch):
    import importlib.util
    monkeypatch.setattr(pt, "of_reader", lambda f: _four_chip_trace())
    rec = {"window": {"calls": 2}, "config": {"m_max": 16, "n_rings": 17},
           "traffic": {"K": 4}, "peaks": {"ici_bits_per_s": 1600e9},
           "trace": {"n_devices": 4, "busy_s": 1.7, "window_s": 6.0}}

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            "m_" + name.replace(".", "_"),
            os.path.join(BENCH, "metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    assert reader("exchange_s")(rec) == pytest.approx(0.25)
    least = 8 * 17 * 17 * 4 / 4 * 3 / 4 / 200e9
    assert reader("exchange_roofline")(rec) == pytest.approx(
        100 * least / 0.25)


@pytest.mark.parametrize("n", [1, 2, 4])
def test_exchange_work_by_hand(n):
    # l_max 4096, K=4: Delta is 4097 m x 4097 rings x 4 maps of 8 bytes
    w = exchange_work(4096, 4097, 4, n)
    assert w["block_bytes"] == 8 * 4097 * 4097 * 4 == 537_133_088
    assert w["bytes_per_chip"] == pytest.approx(537_133_088 / n * (n - 1) / n)
    assert exchange_work(4096, 4097, 4, 4, spin=2)["block_bytes"] == \
        2 * w["block_bytes"]
