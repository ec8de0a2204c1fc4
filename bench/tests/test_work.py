"""The benchmark's copy of the work counts equals the library's today."""

from __future__ import annotations

import pytest

from work import sht_work

#: (l_max, K) of the cells' shapes on the Gauss-Legendre grid
SHAPES = [(4096, 1), (4096, 4), (1024, 1), (1024, 2), (1024, 4), (1024, 8)]


@pytest.mark.parametrize("l_max,K", SHAPES)
def test_copy_equals_library(l_max, K):
    from repro.roofline.analysis import sht_work as lib
    args = (l_max, l_max, l_max + 1, 2 * l_max + 2, K)
    mine, theirs = sht_work(*args), lib(*args)
    for k, v in mine.items():
        assert v == pytest.approx(theirs[k], rel=1e-12), k


def test_synth_4k_counts():
    w = sht_work(4096, 4096, 4097, 8194, 1)
    assert w["recurrence_flops"] == pytest.approx(3.44e11, rel=1e-2)
    assert w["total_flops"] == pytest.approx(4.84e11, rel=1e-2)
