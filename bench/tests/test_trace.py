"""The trace reduction, on made-up events and on a trace recorded on a
TPU v5e (``data/chip_small.xplane.pb``: three calls of a jitted FFT and
matmul inside a ``window`` span; the TPU shows no op named
``fft``, the transform is fused, a 20 ms ``generate`` sleep after each)."""

from __future__ import annotations

import os

import pytest

import trace_reduce as tr

S = 1e9  # ns per second
FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "chip_small.xplane.pb")


def made_up():
    ops = [[1 * S, 1 * S, "fusion.1", "loop fusion"],
           [1.5 * S, 1.5 * S, "fft.3", ""],
           [5 * S, 1 * S, "fusion.7", "loop fusion"],
           [11 * S, 1 * S, "copy.1", ""]]        # after the window
    spans = [[0, 10 * S, "window"], [0.5 * S, 3 * S, "call"],
             [3.5 * S, 6.5 * S, "generate"]]
    return {"devices": {"/device:TPU:0": ops}, "spans": spans}


def test_busy_is_the_union_inside_the_window():
    r = tr.reduce(made_up())
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(3.0)     # [1, 3] and [5, 6]
    assert r["n_devices"] == 1


def test_ops_grouped_by_name_without_suffix():
    ops = {name: (s, kind) for name, s, kind in tr.reduce(made_up())["ops"]}
    assert ops["fusion"] == (pytest.approx(2.0), "loop fusion")
    assert ops["fft"][0] == pytest.approx(1.5)
    assert "copy" not in ops


def test_gaps_longest_first_named_by_host_span():
    gaps = tr.reduce(made_up())["gaps"]
    assert [g[0] for g in gaps] == ["generate", "generate", "call"]
    assert [g[1] for g in gaps] == pytest.approx([4.0, 2.0, 1.0])


def test_union_of_nested_and_disjoint_intervals():
    iv = [(0, 4), (1, 2), (3, 6), (8, 9)]
    assert tr.union_s(iv, 0, 10) * 1e9 == pytest.approx(7.0)
    assert tr.union_s(iv, 5, 8.5) * 1e9 == pytest.approx(1.5)


def test_no_window_span_is_an_error():
    t = made_up()
    t["spans"] = [s for s in t["spans"] if s[2] != "window"]
    with pytest.raises(ValueError):
        tr.reduce(t)


def test_recorded_chip_trace():
    r = tr.reduce(tr.load(FIXTURE))
    assert r["n_devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    names = [o[0] for o in r["ops"]]
    assert "fusion" in names and all(" " not in n for n in names)
    assert r["gaps"] and r["gaps"][0][0] == "generate"
    assert r["gaps"][0][1] == pytest.approx(0.02, rel=0.5)
