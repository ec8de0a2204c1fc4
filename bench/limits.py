#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from.

    python bench/limits.py --workload <cell> --seeds 101-112 \
        --control-seeds 101-103 [--seconds 0] [--out <file.json>]

One process, one set-up, then for each seed the cell's own timed path at
its own size (a window of ``--seconds``, 0 = one call or one request
burst as short as the driver allows) and the check's numbers against the
float64 reference: the program's readings, whose largest is the lower
reading of each number.  Each line also names the row (ring or m) that
read worst and its error.  For each control seed, the same numbers for
the reference computed in bfloat16 (``reference.py``, the nearest
precision below the configuration's float32) put in the program's place:
the upper reading is their smallest.  ``bench/checks/<cell>.json``
records both and the limit set between them.  Needs the chip, like
``run.py``; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

import reference  # noqa: E402
from common import ROOT, Cell, log, make_driver, start  # noqa: E402
from drivers import check_numbers  # noqa: E402


def seeds(spec: str) -> list:
    """``"101-112"`` or ``"5,9,11"`` -> list of ints."""
    if "-" in spec:
        lo, hi = spec.split("-")
        return list(range(int(lo), int(hi) + 1))
    return [int(s) for s in spec.split(",")]


def worst_row(driver, pairs) -> dict:
    """The row (ring or m) with the largest error over the pairs."""
    errs = np.max([reference.row_errors(*p) for p in pairs], axis=0)
    i = int(np.argmax(errs))
    return {"worst_row": int(driver.rows[i]), "worst_row_err": float(errs[i]),
            "median_row_err": float(np.median(errs))}


def control(driver) -> tuple:
    """The bfloat16 reference in the program's place, on the inputs and
    sample of the driver's last window: its pairs against float64."""
    if hasattr(driver, "host_inputs"):
        inputs = driver.host_inputs
    else:
        inputs = {0: np.stack(driver.payloads, axis=-1)}
    want = driver._reference(inputs, driver.rows)
    got = driver._reference(inputs, driver.rows, "bfloat16")
    return [(g[..., k], w[..., k]) for p in want
            for g, w in [(got[p], want[p])] for k in range(w.shape[-1])]


def main(argv=None, *, root: str = ROOT, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="101-112")
    ap.add_argument("--control-seeds", default="101-103")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = Cell(root, args.workload)
    if start(root, cell.chips, "limits", require_tpu) is None:
        return 3
    names = cell.checks["limits"]
    driver = make_driver(cell, root)
    driver.setup()
    ctrl_seeds = set(seeds(args.control_seeds))
    out = {"cell": cell.name, "program": {}, "control": {}}
    for s in seeds(args.seeds):
        driver.prepare(s)
        driver.window(args.seconds)
        driver.collect()
        pairs, attempted, failed = driver.pairs()
        out["program"][s] = dict(check_numbers(pairs, names),
                                 **worst_row(driver, pairs),
                                 attempted=attempted, failed=failed)
        log("program", seed=s, **{k: repr(v)
                                  for k, v in out["program"][s].items()})
        if s in ctrl_seeds:
            pairs = control(driver)
            out["control"][s] = dict(check_numbers(pairs, names),
                                     **worst_row(driver, pairs))
            log("control", seed=s, **{k: repr(v)
                                      for k, v in out["control"][s].items()})
    driver.release()
    for kind in ("program", "control"):
        for k in names:
            vals = [r[k] for r in out[kind].values()]
            if vals:
                log(f"{kind}/{k}", lowest=repr(min(vals)),
                    highest=repr(max(vals)), n=len(vals))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
