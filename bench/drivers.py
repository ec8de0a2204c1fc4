"""The generic drivers, one per ``loop`` of a traffic mix.

Each driver is parametrised only by the cell's configuration and traffic
files, and goes through the library's own entry points, as a user does:
``repro.make_plan(..., mode=<config mode>)`` with the plan's
``alm2map`` / ``map2alm`` for the library, ``repro.serve.ShtEngine`` for
the service.  A run is

  ``setup()``    build the plans (autotune decisions and compiled
                 programs come from the checkout's caches after the
                 first run) and warm the cell's own shapes, nothing else;
  ``prepare(s)`` inputs made on the device from the seed, and the seed's
                 sample of the output that the check compares;
  ``window(t)``  the measured window: end-to-end numbers and the record
                 the per-layer readers read;
  ``collect()``  the window's sampled outputs and its inputs to the host;
  ``release()``  the program's device state freed;
  ``check()``    the sampled outputs against the float64 reference
                 (``pairs()`` gives them side by side).

The host spans the trace reduction names gaps by are opened here
(``jax.profiler.TraceAnnotation``: ``call``, ``submit``, ``wait``,
``generate``).
"""

from __future__ import annotations

import functools
import gc
import queue
import threading
import time

import numpy as np

import reference
import traffic as trafficlib
from common import log, percentile, seed_rng, seed_words

__all__ = ["DRIVERS", "LibraryClosed", "EngineOpen", "EngineClosed",
           "check_numbers"]

#: seed stream of the check's sample (see ``traffic`` for the others)
STREAM_SAMPLE = 3
#: seconds past the window's close that an answer may still come
LATE_S = 60.0


class _GcPauses:
    """Python's garbage-collector pauses while open: their count, total
    and longest seconds (every thread waits while one runs)."""

    def __enter__(self):
        self.n, self.total_s, self.max_s, self._t0 = 0, 0.0, 0.0, None
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            d = time.perf_counter() - self._t0
            self.n += 1
            self.total_s += d
            self.max_s = max(self.max_s, d)


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _gen_alm(key, pool: int, M: int, L: int, K):
    """``pool`` alm of shape (M, L[, K]), uniform in (-1, 1), m = 0 real,
    zero below the diagonal, complex64 (jitted with static sizes)."""
    import jax
    import jax.numpy as jnp
    shape = (pool, M, L) + ((K,) if K else ())
    kr, ki = jax.random.split(key)
    re = jax.random.uniform(kr, shape, jnp.float32, -1.0, 1.0)
    im = jax.random.uniform(ki, shape, jnp.float32, -1.0, 1.0)
    im = im.at[:, 0].set(0.0)
    m = jnp.arange(M)[:, None]
    l = jnp.arange(L)[None, :]
    keep = (l >= m).reshape((1, M, L) + ((1,) if K else ()))
    return jnp.where(keep, re + 1j * im, 0).astype(jnp.complex64)


def _gen_maps(key, pool: int, R: int, n_phi: int, K):
    """``pool`` maps (R, n_phi[, K]), uniform in (-1, 1), float32."""
    import jax
    import jax.numpy as jnp
    shape = (pool, R, n_phi) + ((K,) if K else ())
    return jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)


def _key(seed: int):
    import jax.numpy as jnp
    return jnp.asarray(seed_words(seed))


def _worst(values) -> float:
    """The largest of the values; NaN if any is NaN or there are none."""
    values = [float(v) for v in values]
    return float("nan") if any(v != v for v in values) \
        else max(values, default=float("nan"))


def check_numbers(pairs, names) -> dict:
    """The check's numbers (functions of ``reference`` by name) over
    (output rows, reference rows) pairs, one pair per call or request,
    each the worst over the pairs."""
    return {k: _worst(getattr(reference, k)(*g) for g in pairs)
            for k in names}


def _describe(tag: str, plan) -> None:
    d = plan.describe()
    log(tag, backends=d["backends"], layouts=d["layouts"],
        decision=d["cache"]["events"].get("decision", "-"),
        K=d["signature"]["K"])


class _Driver:
    def __init__(self, cell, cache_dir: str):
        self.cell = cell
        self.cfg = cell.config
        self.tr = cell.traffic
        self.cache_dir = cache_dir
        self.block = int(cell.checks["block"])
        self.synth = self.tr["direction"] == "alm2map"
        g = self.cfg
        self.M, self.L = g["m_max"] + 1, g["l_max"] + 1
        self.R, self.n_phi = g["n_rings"], g["n_phi"]

    def _sample(self, seed: int) -> np.ndarray:
        """Which output rows the check compares (rings of a map in
        synthesis, m rows of the a_lm in analysis): the first and the
        last, and one drawn from the seed in every block of ``block``
        rows among the others, so that every block and both hemispheres
        are seen.  The count is the same for every seed."""
        rows = self.R if self.synth else self.M
        lo = np.arange(0, rows, self.block)
        hi = np.minimum(lo + self.block, rows)
        lo, hi = np.maximum(lo, 1), np.minimum(hi, rows - 1)
        keep = hi > lo
        lo, hi = lo[keep], hi[keep]
        pick = lo + (seed_rng(seed, STREAM_SAMPLE).random(lo.size)
                     * (hi - lo)).astype(np.int64)
        return np.unique(np.concatenate([[0, rows - 1], pick]))

    def check(self) -> tuple:
        """(the check's numbers, answers attempted, answers failed)."""
        pairs, attempted, failed = self.pairs()
        return (check_numbers(pairs, self.cell.checks["limits"]),
                attempted, failed)

    def _reference(self, inputs: dict, rows, precision: str = "float64"):
        """{input index: reference rows} for the used inputs."""
        grid = reference.gl_grid(self.cfg["l_max"])
        fn = reference.synth_rings if self.synth else reference.anal_rows
        return {p: fn(x, rows, grid, precision) for p, x in inputs.items()}


class LibraryClosed(_Driver):
    """One caller of ``Plan.alm2map`` / ``Plan.map2alm``: whole calls,
    each blocked to completion; the window ends at the first call
    boundary after ``seconds``."""

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        import repro
        c, K = self.cfg, int(self.tr["K"])
        self.plan = repro.make_plan(
            c["grid"], l_max=c["l_max"], m_max=c["m_max"], K=K,
            dtype=c["dtype"], mode=c["mode"], cache="disk",
            cache_dir=self.cache_dir)
        _describe("plan", self.plan)
        if self.synth:
            self.fn = self.plan.alm2map
            self.gen = jax.jit(functools.partial(
                _gen_alm, pool=int(self.tr["pool"]), M=self.M, L=self.L,
                K=K))
            out = jax.ShapeDtypeStruct((self.R, self.n_phi, K), jnp.float32)
        else:
            self.fn = functools.partial(self.plan.map2alm,
                                        iters=int(self.tr.get("iters", 0)))
            self.gen = jax.jit(functools.partial(
                _gen_maps, pool=int(self.tr["pool"]), R=self.R,
                n_phi=self.n_phi, K=K))
            out = jax.ShapeDtypeStruct((self.M, self.L, K), jnp.complex64)
        self.plan.warmup(("synth",) if self.synth else ("anal",))
        self.take = jax.jit(lambda a, i: jnp.take(a, i, axis=0))
        n = self._sample(0).size
        jax.block_until_ready(self.take(jnp.zeros(out.shape, out.dtype),
                                        jnp.zeros(n, jnp.int32)))

    def prepare(self, seed: int) -> None:
        import jax
        import jax.numpy as jnp
        pool = self.gen(_key(seed))
        self.inputs = jax.block_until_ready(
            [pool[i] for i in range(pool.shape[0])])
        del pool
        self.rows = self._sample(seed)
        self.rows_dev = jnp.asarray(self.rows, jnp.int32)
        self.order = trafficlib.payload_order(self.tr, seed, 1 << 16)

    def window(self, seconds: float) -> tuple:
        import jax
        samples, calls = [], 0
        t0 = time.perf_counter()
        while True:
            p = int(self.order[calls])
            with _span("call"):
                out = jax.block_until_ready(self.fn(self.inputs[p]))
            samples.append((p, self.take(out, self.rows_dev)))
            del out
            calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.samples = samples
        return ({"transform_s": elapsed / calls},
                {"calls": calls, "elapsed_s": elapsed})

    def collect(self) -> None:
        used = sorted({p for p, _ in self.samples})
        self.host_inputs = {p: np.asarray(self.inputs[p]) for p in used}
        self.samples = [(p, np.asarray(s)) for p, s in self.samples]

    def release(self) -> None:
        from repro.core import transform
        transform.drop_plan(self.plan)
        self.inputs = self.plan = self.fn = None
        gc.collect()

    def pairs(self) -> tuple:
        """(output rows, reference rows) of every call of the window."""
        refs = self._reference(self.host_inputs, self.rows)
        pairs = [(s, refs[p]) for p, s in self.samples]
        return pairs, len(self.samples), 0


class _Engine(_Driver):
    """Shared set-up of the engine drivers: one ``ShtEngine`` in its
    background (double-buffered) mode, its pool warmed for the mix's K
    buckets in the mix's direction only.  Each request carries one map
    (``map2alm``) or one a_lm (``alm2map``) from a pool made from the
    seed."""

    def setup(self) -> None:
        import jax
        from repro.serve import ShtEngine
        from repro.serve.pool import PlanSig
        c, e = self.cfg, self.cfg["engine"]
        assert int(self.tr["K"]) == 1, "requests carry one map each"
        self.eng = ShtEngine(max_k=e["max_k"], max_queue=e["max_queue"],
                             mode=c["mode"], cache="disk",
                             cache_dir=self.cache_dir)
        self.sig = PlanSig(grid=c["grid"], l_max=c["l_max"],
                           m_max=c["m_max"], dtype=c["dtype"])
        d = "synth" if self.synth else "anal"
        for k in self.tr["k_buckets"]:
            _describe(f"plan/k{k}",
                      self.eng.pool.warm(self.sig, int(k), directions=(d,)))
        pool = int(self.tr["pool"])
        self.gen = jax.jit(functools.partial(
            _gen_alm, pool=pool, M=self.M, L=self.L, K=0) if self.synth
            else functools.partial(_gen_maps, pool=pool, R=self.R,
                                   n_phi=self.n_phi, K=0))
        self.eng.start()

    def _submit(self, payload):
        c = self.cfg
        return self.eng.submit(direction=self.tr["direction"],
                               payload=payload, grid=c["grid"],
                               l_max=c["l_max"], m_max=c["m_max"],
                               dtype=c["dtype"],
                               iters=int(self.tr.get("iters", 0)))

    def prepare(self, seed: int) -> None:
        pool = np.asarray(self.gen(_key(seed)))
        self.payloads = [pool[i] for i in range(pool.shape[0])]
        self.rows = self._sample(seed)
        self.seed = seed
        # one batch of each K bucket through the running engine
        for k in self.tr["k_buckets"]:
            futs = [self._submit(self.payloads[i % len(self.payloads)])
                    for i in range(int(k))]
            for f in futs:
                f.result(timeout=600)

    def _coalescing(self) -> dict:
        co = self.eng.stats()["coalescing"]
        n = co["batches"]
        return {"batches": n, "maps": co["k_per_batch"] * n if n else 0.0}

    def _finish(self, due, sent, done, order, results, queue_s, t_start,
                t_end, before, gcp) -> tuple:
        """End-to-end numbers and record of an engine window."""
        k = int(self.tr["K"])
        n = len(due)
        cutoff = t_end + LATE_S
        lat = [(d if d is not None else cutoff) - (t_start + u)
               for u, d in zip(due, done)]
        in_window = sum(1 for d in done if d is not None and d <= t_end)
        late = [s - (t_start + u) for u, s in zip(due, sent)
                if s is not None]
        after = self._coalescing()
        self.results = results
        self.latencies = lat
        self.order_used = order
        self.failed = sum(1 for d in done if d is None)
        log("generator", requests=n, late_p95_s=f"{percentile(late, 95)!r}",
            late_max_s=f"{float(max(late, default=float('nan')))!r}",
            unanswered=self.failed, gc_pauses=gcp.n,
            gc_total_s=f"{gcp.total_s!r}", gc_max_s=f"{gcp.max_s!r}")
        return ({"request_p95_s": percentile(lat, 95),
                 "served_maps_per_s": in_window * k / (t_end - t_start)},
                {"requests": n, "window_s": t_end - t_start,
                 "queue_s": [q for q in queue_s if q is not None],
                 "coalescing": {"batches": after["batches"]
                                - before["batches"],
                                "maps": after["maps"] - before["maps"]}})

    def collect(self) -> None:
        """Results are on the host already (sampled as they resolved)."""

    def release(self) -> None:
        from repro.core import transform
        self.eng.stop(drain=True)
        self.eng.close()
        self.eng = None
        transform.clear_plan_cache()
        gc.collect()

    def pairs(self) -> tuple:
        """(result rows, reference rows) of every answered request."""
        used = sorted({int(p) for p in self.order_used})
        if not used:
            return [], len(self.results), self.failed
        stack = np.stack([self.payloads[p] for p in used], axis=-1)
        ref = self._reference({0: stack}, self.rows)[0]
        col = {p: i for i, p in enumerate(used)}
        pairs = [(res, ref[..., col[int(p)]])
                 for p, res in zip(self.order_used, self.results)
                 if res is not None]
        return pairs, len(self.results), self.failed


class EngineOpen(_Engine):
    """Independent users: requests due on the mix's schedule, sent whether
    or not earlier ones have resolved; each timed from when it was due to
    when its future resolved, by a waiter thread that takes the futures
    in the order they were sent."""

    def window(self, seconds: float) -> tuple:
        from repro.serve import BackpressureError
        due = trafficlib.arrivals(self.tr, self.seed, seconds)
        order = trafficlib.payload_order(self.tr, self.seed, len(due))
        n = len(due)
        sent, done = [None] * n, [None] * n
        results, queue_s = [None] * n, [None] * n
        handed: queue.Queue = queue.Queue()
        rows = self.rows
        before = self._coalescing()
        t_start = time.perf_counter()
        t_end = t_start + seconds

        def wait():
            while True:
                item = handed.get()
                if item is None:
                    return
                i, fut = item
                try:
                    with _span("wait"):
                        res = fut.result(timeout=max(
                            0.0, t_end + LATE_S - time.perf_counter()))
                    done[i] = time.perf_counter()
                    results[i] = np.array(res[rows])
                    queue_s[i] = fut.timing.get("queue_s")
                except Exception:            # unanswered: counted missing
                    pass

        waiter = threading.Thread(target=wait, name="bench-waiter")
        gcp = _GcPauses().__enter__()
        waiter.start()
        try:
            for i in range(n):
                pause = t_start + due[i] - time.perf_counter()
                if pause > 0:
                    with _span("generate"):
                        time.sleep(pause)
                with _span("submit"):
                    try:
                        fut = self._submit(self.payloads[order[i]])
                    except BackpressureError:
                        continue
                sent[i] = time.perf_counter()
                handed.put((i, fut))
            pause = t_end - time.perf_counter()
            if pause > 0:
                with _span("generate"):
                    time.sleep(pause)
        finally:
            handed.put(None)
            waiter.join()
            gcp.__exit__()
        return self._finish(due, sent, done, order, results, queue_s,
                            t_start, t_end, before, gcp)


class EngineClosed(_Engine):
    """One caller that waits for each reply before it sends the next;
    the window ends at the first reply after ``seconds``."""

    def window(self, seconds: float) -> tuple:
        assert int(self.tr.get("clients", 1)) == 1, "one client"
        order = trafficlib.payload_order(self.tr, self.seed, 1 << 16)
        due, sent, done, results, queue_s = [], [], [], [], []
        before = self._coalescing()
        t_start = time.perf_counter()
        t = t_start
        with _GcPauses() as gcp:
            while t - t_start < seconds:
                i = len(due)
                due.append(t - t_start)
                with _span("submit"):
                    fut = self._submit(self.payloads[order[i]])
                sent.append(time.perf_counter())
                res = None
                try:
                    with _span("wait"):
                        res = np.array(fut.result(timeout=LATE_S)[self.rows])
                except Exception:            # unanswered: counted missing
                    pass
                t = time.perf_counter()
                done.append(t if res is not None else None)
                results.append(res)
                queue_s.append(fut.timing.get("queue_s"))
        return self._finish(due, sent, done, order[:len(due)], results,
                            queue_s, t_start, t, before, gcp)


DRIVERS = {"library_closed": LibraryClosed, "engine_open": EngineOpen,
           "engine_closed": EngineClosed}
