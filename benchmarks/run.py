"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Wall times are CPU-host
times (TPU projections live in the roofline analysis; EXPERIMENTS.md).

After the CSV, a machine-readable ``BENCH_<UTC-date>.json`` summary
(name -> us_per_call, plus git rev and jax version) is written to the
current directory so the perf trajectory is trackable across PRs.
"""

import argparse
import datetime
import json
import os
import subprocess
import sys
import traceback


def _git_rev() -> str:
    try:
        r = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        return r.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def write_summary(path=None, errors=None) -> str:
    """Dump the collected emit() rows as BENCH_<UTC-date>.json.

    ``errors`` (``{module_name: message}``) records benchmark modules that
    raised -- the harness keeps going, but the JSON carries the failures
    so scripts/check.sh can fail the gate loudly.
    """
    import jax
    from benchmarks import common
    now = datetime.datetime.now(datetime.timezone.utc)
    if path is None:
        path = f"BENCH_{now.strftime('%Y-%m-%d')}.json"
    payload = {
        "generated_utc": now.isoformat(timespec="seconds"),
        "git_rev": _git_rev(),
        "jax_version": jax.__version__,
        "smoke": bool(os.environ.get("REPRO_BENCH_SMOKE")),
        "us_per_call": {name: us for name, us, _ in common.ROWS},
        "derived": {name: d for name, _, d in common.ROWS if d},
        "errors": dict(errors or {}),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="one small size, one rep per bench "
                         "(same as REPRO_BENCH_SMOKE=1)")
    ap.add_argument("-o", "--out", default=None,
                    help="summary JSON path (default BENCH_<UTC-date>.json "
                         "in the current directory)")
    args = ap.parse_args(argv)
    from repro import compile_cache
    compile_cache.enable()
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
        # bounded CI runtime: plans built by the benches reuse chardb
        # corners but never one-shot time missing ones (cost-model
        # fallback instead) -- see repro.roofline.chardb
        os.environ["REPRO_CHARDB_SMOKE"] = "1"
    from benchmarks import (bench_accuracy, bench_recurrence,
                            bench_scaling_model, bench_fft, bench_speedup,
                            bench_dist_overlap, bench_dispatch, bench_spin,
                            bench_serve)
    print("name,us_per_call,derived")
    errors = {}
    for mod in (bench_accuracy, bench_recurrence, bench_scaling_model,
                bench_fft, bench_speedup, bench_dist_overlap,
                bench_dispatch, bench_spin, bench_serve):
        try:
            mod.main()
        except Exception as e:  # keep the harness going
            errors[mod.__name__] = f"{type(e).__name__}: {e}"
            print(f"{mod.__name__}/ERROR,0.0,{type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
    path = write_summary(args.out, errors)
    print(f"# summary: {path}", file=sys.stderr)


if __name__ == '__main__':
    main()
