"""The harness end to end on the CPU, on the tiny cells."""

from __future__ import annotations

import pytest

from conftest import TINY_CELLS, run_cell


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_cell_runs_and_is_correct(tiny_root, cell, capsys):
    rc, res = run_cell(tiny_root, cell, capsys=capsys)
    assert rc == 0 and res is not None
    assert res["correct"] is True, res
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
