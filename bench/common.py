"""What every part of the harness shares: where things are, how a cell's
files are found by name, seeds, percentiles and log lines.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Everything
that belongs to it is found by name, so that a new cell is new files and
new entries, never an edit:

* its configuration: the ``file`` of the named entry of ``configs``;
* its traffic mix: ``bench/traffic/<traffic>.json``;
* its check: ``bench/checks/<cell>.json`` (the sample's ``block`` and
  the ``limits`` of the numbers compared, by name in ``reference``);
* each per-layer metric: ``bench/metrics/<metric>.py``, a module with
  ``read(record) -> float | None``.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import sys

import numpy as np

__all__ = ["BENCH", "ROOT", "Cell", "start", "percentile", "seed_words",
           "seed_rng", "make_driver", "log", "warn"]

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One workload with its configuration, traffic, limits and metrics."""

    def __init__(self, root: str, name: str):
        self.root = root
        self.bench = _json(os.path.join(root, "BENCHMARK.json"))
        by_name = {w["name"]: w for w in self.bench["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(by_name)}")
        self.name = name
        self.workload = by_name[name]
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config = _json(os.path.join(
            root, configs[self.workload["config"]]["file"]))
        here = os.path.join(root, "bench")
        self.traffic = _json(os.path.join(
            here, "traffic", self.workload["traffic"] + ".json"))
        self.checks = _json(os.path.join(here, "checks", name + ".json"))
        self.chips = int(self.workload["chips"])

    def _mine(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> list:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.bench["end_to_end"] if self._mine(m)]

    def per_layer(self) -> list:
        """The per-layer metrics read in this cell's traced runs: those
        that list it, and those without a list whose end-to-end metric
        it reports."""
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def reader(self, metric: str):
        """``read(record)`` of ``bench/metrics/<metric>.py``."""
        path = os.path.join(self.root, "bench", "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def start(root: str, chips: int, tag: str, require_tpu: bool = True):
    """Start-up shared by every script that drives a cell: JAX's
    persistent compilation cache at ``<root>/.jax_cache`` and the library
    under test (``<root>/src``) on the path, then JAX.  Returns the
    devices, or None (after a line on standard error) where JAX finds no
    TPU or fewer than ``chips`` chips."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root,
                                                           ".jax_cache")
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if require_tpu and (d0.platform != "tpu" or len(devices) < chips):
        warn(f"{tag}: needs {chips} TPU chip(s); JAX sees {len(devices)} "
             f"{d0.platform} device(s)")
        return None
    return devices


def make_driver(cell: "Cell", root: str):
    """The generic driver of the cell's traffic loop, its plans' decision
    and characterization caches at ``<root>/.bench_cache``."""
    from drivers import DRIVERS
    return DRIVERS[cell.traffic["loop"]](
        cell, os.path.join(root, ".bench_cache"))


def percentile(xs, q: float) -> float:
    """q-th percentile (0..100), linear between closest ranks (numpy's
    default); NaN for no samples.  Copied from the library's
    ``serve/metrics.py``."""
    n = len(xs)
    if n == 0:
        return float("nan")
    xs = sorted(float(v) for v in xs)
    pos = (q / 100.0) * (n - 1)
    lo, hi = math.floor(pos), math.ceil(pos)
    frac = pos - lo
    return xs[lo] * (1.0 - frac) + xs[hi] * frac


def seed_words(seed: int) -> np.ndarray:
    """Two uint32 words from any whole-number seed: a raw JAX PRNG key."""
    return np.random.SeedSequence(int(seed) % 2**64).generate_state(
        2, np.uint32)


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one purpose (``stream``) of one seed."""
    return np.random.default_rng([int(seed) % 2**64, stream])


def log(tag: str, **fields) -> None:
    """One ``[tag] k=v ...`` line on standard output."""
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def warn(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
