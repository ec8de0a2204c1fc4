"""The one traffic generator: reads a mix's parameters, gives the schedule.

A mix (``bench/traffic/<mix>.json``) names its ``loop``:

* ``library_closed``: one caller, each transform call blocked to
  completion before the next; inputs cycle through ``pool`` distinct
  inputs;
* ``engine_open``: independent users, requests due at ``rate`` per second
  with exponential gaps, sent whether or not earlier ones are done;
* ``engine_closed``: ``clients`` callers, each sending its next request
  when its previous one has resolved.

A mix may set ``trace_seconds``: a traced run's window is then no longer
than that (a mix of many small device programs makes a large trace).

Every seed gets the same set of gaps and the same set of payloads, each
in its own order: the seed changes the order of the work, never its
amount, so that runs of different seeds measure the same thing.
"""

from __future__ import annotations

import numpy as np

from common import seed_rng

__all__ = ["arrivals", "payload_order"]

#: seed streams: one per purpose, so that adding one moves no other
STREAM_ARRIVALS = 1
STREAM_PAYLOADS = 2


def arrivals(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start, ascending) of an open
    loop's requests: ``ceil(rate * seconds)`` gaps at the midpoint
    quantiles of an exponential distribution of mean 1 / rate, in an
    order drawn from the seed.  Requests due at or after ``seconds`` are
    not sent."""
    assert traffic["loop"] == "engine_open", traffic["loop"]
    rate = float(traffic["rate"])
    n = int(np.ceil(rate * seconds))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    due = np.cumsum(seed_rng(seed, STREAM_ARRIVALS).permutation(gaps))
    return due[due < seconds]


def payload_order(traffic: dict, seed: int, n: int) -> np.ndarray:
    """Which of the ``pool`` inputs each of ``n`` requests or calls
    sends: the pool cycled, in an order drawn from the seed."""
    pool = int(traffic["pool"])
    perm = seed_rng(seed, STREAM_PAYLOADS).permutation(pool)
    return perm[np.arange(n) % pool]
