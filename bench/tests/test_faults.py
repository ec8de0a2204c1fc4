"""A run with its timed path broken underneath reads ``correct: false``.

The harness's look for a chip is skipped; everything else of a run is
driven as on the chip, on the tiny cells.  One test per fault a cell can
have: an answer altered where it is produced, in whole, in one block of
rows or in one hemisphere (every cell), and half of a batch left out (the
four-map analysis call, and the engine's coalesced batches).  These cells have no training state and no exchange between
chips."""

from __future__ import annotations

import time

import jax.numpy as jnp
import pytest

from conftest import run_cell


def _wrap(monkeypatch, method, change):
    from repro.core.transform import Plan
    orig = getattr(Plan, method)

    def broken(self, x, *a, **kw):
        return change(orig(self, x, *a, **kw))

    monkeypatch.setattr(Plan, method, broken)


def _altered(out):
    """The whole answer off by 1%."""
    return out * 1.01


def _block(out):
    """One block of rows (rings of a map, m of the a_lm) off by 1%: the
    second block of the check's sample, as a fault in one ring block of
    a kernel's grid would leave it."""
    from conftest import TINY_BLOCK
    b = slice(TINY_BLOCK, 2 * TINY_BLOCK)
    return out.at[b].set(out[b] * 1.01)


def _hemisphere(out):
    """The south half of the sphere made from the north: in synthesis
    the southern rings are the northern ones mirrored (the symmetric part
    alone); in analysis the a_lm with l + m odd, which only the
    antisymmetric part of a map gives, are zero."""
    if jnp.iscomplexobj(out):                      # analysis: (M, L, K)
        m, l = jnp.indices(out.shape[:2])
        return jnp.where(((l + m) % 2 == 1)[..., None], 0, out)
    R = out.shape[0]
    south = jnp.flip(out[: R // 2], axis=0)
    return out.at[R - R // 2:].set(south)


CELLS = [("tiny.synth_k1", "alm2map"), ("tiny.anal_k4", "map2alm"),
         ("tiny.serve_open", "alm2map"), ("tiny.serve_closed", "alm2map"),
         ("tiny.anal_open", "map2alm"), ("tiny.anal_closed", "map2alm")]


@pytest.mark.parametrize("fault", [_altered, _block, _hemisphere],
                         ids=["whole", "block", "hemisphere"])
@pytest.mark.parametrize("cell,method", CELLS)
def test_altered_answer_is_not_correct(tiny_root, monkeypatch, capsys,
                                       cell, method, fault):
    _wrap(monkeypatch, method, lambda out: fault(jnp.asarray(out)))
    rc, res = run_cell(tiny_root, cell, capsys=capsys)
    assert rc == 0 and res["correct"] is False
    c = res["checks"]["row_rel_max"]
    assert c["value"] > c["limit"]


def _half(out):
    """The upper half of the K axis left out (zeros)."""
    k = out.shape[-1]
    return out.at[..., k - k // 2:].set(0) if k > 1 else out


def test_half_batch_left_out_library(tiny_root, monkeypatch, capsys):
    _wrap(monkeypatch, "map2alm", _half)
    rc, res = run_cell(tiny_root, "tiny.anal_k4", capsys=capsys)
    assert rc == 0 and res["correct"] is False


@pytest.mark.parametrize("cell,method", [("tiny.serve_open", "alm2map"),
                                         ("tiny.anal_open", "map2alm")])
def test_half_batch_left_out_engine(tiny_root, monkeypatch, capsys, cell,
                                    method):
    """Slow batches make the engine coalesce; the upper half of each
    coalesced batch comes back as zeros."""
    def slow_half(out):
        time.sleep(0.15)
        return _half(jnp.asarray(out))

    _wrap(monkeypatch, method, slow_half)
    rc, res = run_cell(tiny_root, cell, seconds=2.0, capsys=capsys)
    assert rc == 0 and res["correct"] is False
