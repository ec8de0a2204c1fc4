"""Record ``data/chip_program.xplane.pb``: a profiler trace on a TPU of the
program's own spans and scopes at a small size, for the tests of
``program_trace``.

    python bench/tests/make_program_fixture.py --out <dir>

Inside a ``window`` span: one library ``map2alm`` call (a ``call`` span)
at l_max 128, K=1, then two single-map ``map2alm`` requests through a
background ``ShtEngine``, one after another (``submit``, ``wait``), with a
20 ms ``generate`` sleep before each.  The jnp backend, so that nothing
is autotuned.  Prints where the trace is and what ``program_trace``
reads from it.
"""

from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

L = 128
#: engine requests inside the window
REQUESTS = 2


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    out = ap.parse_args().out
    import jax
    import numpy as np

    import repro
    from repro.serve import PlanSig, ShtEngine

    rng = np.random.default_rng(0)
    plan = repro.make_plan("gl", l_max=L, K=1, dtype="float32", mode="jnp",
                           cache="off")
    plan.warmup(("anal",))
    maps = rng.uniform(-1, 1, plan._maps_shape).astype(np.float32)
    eng = ShtEngine(max_k=2, mode="jnp", cache="off")
    eng.pool.warm(PlanSig(grid="gl", l_max=L, dtype="float32"), 1,
                  directions=("anal",))
    eng.start()
    eng.submit(direction="map2alm", payload=maps[..., 0], grid="gl",
               l_max=L, dtype="float32").result(timeout=600)
    trace_dir = os.path.join(out, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("call"):
            jax.block_until_ready(plan.map2alm(maps))
        for _ in range(REQUESTS):
            with jax.profiler.TraceAnnotation("generate"):
                time.sleep(0.02)
            with jax.profiler.TraceAnnotation("submit"):
                fut = eng.submit(direction="map2alm", payload=maps[..., 0],
                                 grid="gl", l_max=L, dtype="float32")
            with jax.profiler.TraceAnnotation("wait"):
                fut.result(timeout=600)
    jax.profiler.stop_trace()
    eng.stop()
    path = max(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    print("trace", path, os.path.getsize(path), "bytes")
    print("batch_log", [b["batch"] for b in eng.batch_log])

    import program_trace as pt
    t = pt.load(path)
    tfs = sorted({tf for ops in t["ops"].values() for *_, tf in ops})
    print("tf_op", len(tfs), tfs[:40])
    print("stage_seconds", pt.stage_seconds(t))
    print("engine_batches", pt.engine_batches(t))
    print("engine_host_s_per_batch", pt.engine_host_s_per_batch(t))
    print("engine_idle_s", pt.engine_idle_s(t))
    print("named_gaps", pt.named_gaps(t))


if __name__ == "__main__":
    main()
