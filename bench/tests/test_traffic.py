"""The traffic generator: one seed repeats exactly, seeds differ only in
the order of the same work."""

from __future__ import annotations

import numpy as np

import traffic

OPEN = {"loop": "engine_open", "rate": 20.0, "pool": 32}
BIG_SEED = 2**31 + 12345


def test_arrivals_repeat_for_one_seed():
    a = traffic.arrivals(OPEN, BIG_SEED, 30.0)
    b = traffic.arrivals(OPEN, BIG_SEED, 30.0)
    assert np.array_equal(a, b)


def test_arrivals_differ_across_seeds_in_order_only():
    a = traffic.arrivals(OPEN, BIG_SEED, 100.0)
    b = traffic.arrivals(OPEN, 7, 100.0)
    n = min(a.size, b.size)
    assert not np.array_equal(a[:n], b[:n])
    # one long window: the same multiset of gaps for every seed
    ga = np.sort(np.diff(np.r_[0.0, traffic.arrivals(OPEN, 1, 1e4)]))
    gb = np.sort(np.diff(np.r_[0.0, traffic.arrivals(OPEN, 2, 1e4)]))
    m = min(ga.size, gb.size)
    assert abs(ga.size - gb.size) <= 2
    assert np.allclose(ga[: m - 2], gb[: m - 2], rtol=1e-9)


def test_arrivals_rate_and_window():
    a = traffic.arrivals(OPEN, 3, 30.0)
    assert np.all(np.diff(a) > 0) and a[0] > 0 and a[-1] < 30.0
    assert abs(a.size - 20.0 * 30.0) < 0.1 * 600


def test_payload_order_cycles_the_pool():
    o = traffic.payload_order(OPEN, BIG_SEED, 96)
    assert np.array_equal(o, traffic.payload_order(OPEN, BIG_SEED, 96))
    assert sorted(o[:32]) == list(range(32))
    assert np.array_equal(o[:32], o[32:64])
    assert not np.array_equal(o, traffic.payload_order(OPEN, 8, 96))


def test_check_sample_covers_every_block_and_both_edges():
    """The rows the check compares: both edges, one in every block of
    ``block`` rows, the same count for every seed, drawn from the seed."""
    import drivers

    class Probe(drivers._Driver):
        def __init__(self, rows, block):
            self.R, self.synth, self.block = rows, True, block

    for rows in (17, 1025, 4097):
        d = Probe(rows, 128)
        samples = [d._sample(s) for s in (1, 2, 2**33 + 5)]
        assert len({s.size for s in samples}) == 1
        assert not np.array_equal(samples[0], samples[1])
        for s in samples:
            assert s[0] == 0 and s[-1] == rows - 1
            assert np.all(np.diff(s) > 0)
            assert set(range(0, rows, 128)) <= set(s // 128 * 128)
