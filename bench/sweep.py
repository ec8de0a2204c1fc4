#!/usr/bin/env python3
"""Knee of an open-loop engine cell: the highest offered rate it serves.

    python bench/sweep.py --workload mc1k.serve_open --rates 8,12,16,24 \
        --seconds 20 [--seed 7]

One process, one set-up, then one window per rate (the cell's traffic with
its ``rate`` replaced).  Each line gives the offered rate, the maps served
inside the window per second, the share of requests due in the window that
were served inside it, the p95 latency of the first and the last third of
the window's requests, and the requests still pending at the close.  The
knee is the highest rate that serves at least 97% inside the window with
the last third's p95 no more than twice the first third's (no growing
backlog).  The cell's traffic file then gets 0.8 x the knee as its rate;
this script writes nothing.  Needs the chip, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import ROOT, Cell, log, make_driver, percentile, start  # noqa: E402


def main(argv=None, *, root: str = ROOT, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="mc1k.serve_open")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cell = Cell(root, args.workload)
    assert cell.traffic["loop"] == "engine_open", cell.traffic["loop"]
    if start(root, cell.chips, "sweep", require_tpu) is None:
        return 3
    driver = make_driver(cell, root)
    driver.setup()
    driver.prepare(args.seed)
    base = dict(cell.traffic)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        driver.tr = dict(base, rate=rate)
        e2e, rec = driver.window(args.seconds)
        lat = driver.latencies
        third = max(len(lat) // 3, 1)
        row = {"rate": rate, "requests": rec["requests"],
               "served_maps_per_s": e2e["served_maps_per_s"],
               "served_share": e2e["served_maps_per_s"] * args.seconds
               / max(rec["requests"], 1),
               "p95_s": e2e["request_p95_s"],
               "p95_first_third_s": percentile(lat[:third], 95),
               "p95_last_third_s": percentile(lat[-third:], 95),
               "k_per_batch": rec["coalescing"]["maps"]
               / max(rec["coalescing"]["batches"], 1)}
        rows.append(row)
        log("sweep", **{k: repr(v) for k, v in row.items()})
    driver.release()
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
