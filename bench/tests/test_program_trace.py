"""The program's spans and scopes in a trace (``program_trace``), on
made-up events, on a trace recorded on a TPU v5e
(``data/chip_program.xplane.pb``, made by ``make_program_fixture.py``:
one jnp ``map2alm`` call at l_max 128 and two engine requests inside a
``window`` span), and through the harness's readers on the CPU."""

from __future__ import annotations

import os
import sys

import pytest

import program_trace as pt
import trace_reduce as tr
from conftest import run_cell
from make_program_fixture import REQUESTS
from repro import tracing

S = 1e9  # ns per second
DATA = os.path.join(os.path.dirname(__file__), "data")
FIXTURE = os.path.join(DATA, "chip_program.xplane.pb")
SMALL = os.path.join(DATA, "chip_small.xplane.pb")

LEG = "jit(map2alm)/sht.legendre/jit(_alm_from_delta_impl)/while/body"


def made_up():
    ops = [[1 * S, 1 * S, "fusion.1", f"{LEG}/closed_call/recurrence/or:"],
           [2 * S, 0.5 * S, "fusion.2",
            f"{LEG}/closed_call/accumulate/mr,mrk->km/dot_general:"],
           [2.5 * S, 0.5 * S, "copy.3", f"{LEG}/dynamic_update_slice:"],
           [3 * S, 2 * S, "while.1", f"{LEG}:"],           # a container
           [4 * S, 0.25 * S, "fusion.4", "jit(map2alm)/sht.phase/jit(fft):"],
           [4.5 * S, 0.25 * S, "fusion.5",
            "jit(map2alm)/sht.fold/sht.phase/mul:"],       # innermost
           [5 * S, 0.5 * S, "copy.6", ""],
           [11 * S, 1 * S, "fusion.7", f"{LEG}/x:"]]       # after the window
    mods = [[1 * S, 4.5 * S], [7 * S, 1 * S]]
    spans = [[0, 10 * S, "window", None, "python"],
             [0.5 * S, 5 * S, "call", None, "python"],
             [5.5 * S, 4.5 * S, "generate", None, "python"],
             [5.6 * S, 0.2 * S, tracing.ENGINE_IDLE, None, "form"],
             [5.8 * S, 0.4 * S, tracing.ENGINE_FORM, 1, "form"],
             [6.2 * S, 0.3 * S, tracing.ENGINE_STACK, 1, "form"],
             [6.5 * S, 0.5 * S, tracing.ENGINE_UPLOAD, 1, "form"],
             [7.0 * S, 0.1 * S, tracing.ENGINE_HANDOFF, 1, "form"],
             [7.0 * S, 1.5 * S, tracing.ENGINE_EXECUTE, 1, "exec"],
             [8.5 * S, 0.5 * S, tracing.ENGINE_DOWNLOAD, 1, "exec"],
             [9.05 * S, 0.2 * S, tracing.ENGINE_SCATTER, 1, "exec"],
             [9.5 * S, 1 * S, tracing.ENGINE_EXECUTE, 2, "exec"]]  # past
    return {"ops": {"/device:TPU:0": ops}, "modules": {"/device:TPU:0": mods},
            "cut_at": {"/device:TPU:0": None}, "spans": spans,
            "path": "made-up"}


def test_stage_seconds_by_innermost_scope():
    sec = pt.stage_seconds(made_up())
    assert sec["ops"] == pytest.approx(3.0)        # no container, no late op
    assert sec[tracing.LEGENDRE] == pytest.approx(2.0)
    assert sec[f"{tracing.LEGENDRE}/{tracing.RECURRENCE}"] == \
        pytest.approx(1.0)
    assert sec[f"{tracing.LEGENDRE}/{tracing.ACCUMULATE}"] == \
        pytest.approx(0.5)
    assert sec[tracing.PHASE] == pytest.approx(0.5)
    assert tracing.FOLD not in sec
    assert sec["unscoped"] == pytest.approx(0.5)
    per = pt.scopes_per_call(made_up(), 2)
    assert per[tracing.LEGENDRE] == pytest.approx(1.0)


def test_a_cut_window_gives_no_scope_seconds():
    t = made_up()
    t["cut_at"]["/device:TPU:0"] = 9 * S
    assert pt.stage_seconds(t) is None
    t["cut_at"]["/device:TPU:0"] = 10.5 * S        # past the window
    assert pt.stage_seconds(t) is not None


def test_engine_batches_inside_the_window():
    t = made_up()
    b = pt.engine_batches(t)
    assert set(b) == {1}                           # batch 2 ends past it
    assert b[1]["execute"] == 1 and b[1]["formed"]
    assert b[1]["host_s"] == pytest.approx(0.4 + 0.3 + 0.5 + 0.5 + 0.2)
    assert pt.engine_host_s_per_batch(t) == pytest.approx(1.9)


def test_idle_caused_by_the_engine():
    # device idle in the window: [0,1], [5.5,7], [8,10]; engine work
    # (no idle, no handoff): [5.8,9], [9.05,9.25], [9.5,10]
    assert pt.engine_idle_s(made_up()) == pytest.approx(1.2 + 1.0 + 0.2
                                                       + 0.5)


def test_gaps_named_by_bench_and_program_span():
    gaps = pt.named_gaps(made_up())
    assert [g[0] for g in gaps] == [
        f"generate/{tracing.ENGINE_DOWNLOAD}",
        f"generate/{tracing.ENGINE_STACK}", "call"]
    assert [g[1] for g in gaps] == pytest.approx([2.0, 1.5, 1.0])


def _older_program(monkeypatch):
    """The program as it was before `repro.tracing` and
    `compile_cache.stats`."""
    import repro
    import repro.serve  # noqa: F401  (everything that imports the names)
    from repro import compile_cache
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.delattr(compile_cache, "stats")


def test_an_older_program_reads_nothing(monkeypatch):
    _older_program(monkeypatch)
    assert pt.names() is None
    t = made_up()
    assert pt.stage_seconds(t) is None
    assert pt.engine_host_s_per_batch(t) is None
    assert pt.engine_idle_s(t) is None


def test_a_trace_without_program_spans_reads_nothing():
    t = made_up()
    t["spans"] = [sp for sp in t["spans"] if sp[2] in tr.SPANS]
    t["ops"] = {p: [o[:3] + [""] for o in ops] for p, ops in t["ops"].items()}
    assert pt.stage_seconds(t) is None
    assert pt.engine_batches(t) is None
    assert pt.engine_idle_s(t) is None


def test_recorded_chip_trace_scopes():
    t = pt.load(FIXTURE)
    sec = pt.stage_seconds(t)
    for s in tracing.STAGES:
        assert sec[s] > 0, s
    leg = sec[tracing.LEGENDRE]
    for sub in tracing.SUB_STAGES:
        assert 0 < sec[f"{tracing.LEGENDRE}/{sub}"] < leg
    assert 1.0 - sec.get("unscoped", 0.0) / sec["ops"] >= 0.98
    assert leg > sec[tracing.PHASE] > sec[tracing.FOLD]


def test_recorded_chip_trace_engine():
    t = pt.load(FIXTURE)
    b = pt.engine_batches(t)
    assert len(b) == REQUESTS
    assert all(x["execute"] == 1 and x["formed"] and x["host_s"] > 0
               for x in b.values())
    # both engine threads' spans land on one host line named "python":
    # the spans are told apart by name and batch, never by line
    assert all(sp[4] for sp in t["spans"])
    idle = pt.engine_idle_s(t)
    window = tr.reduce(tr.load(FIXTURE))
    assert 0 < idle < window["window_s"] - window["busy_s"]
    names = [g[0] for g in pt.named_gaps(t)]
    assert f"generate/{tracing.ENGINE_IDLE}" in names


@pytest.mark.parametrize("path", [SMALL, FIXTURE])
def test_same_device_events_as_the_reduction(path):
    """The reader of event metadata sees the events `trace_reduce` sees,
    on the same clock, and leaves the reduction's numbers as they are."""
    before = tr.reduce(tr.load(path))
    mine = pt.load(path)
    theirs = tr.load(path)
    for plane, ops in theirs["devices"].items():
        assert len(mine["ops"][plane]) == len(ops)
        for (s, d, name, _), (s2, d2, name2, _) in zip(
                ops, mine["ops"][plane]):
            assert name2 == name
            assert abs(s2 - s) <= 1 and abs(d2 - d) <= 1
    for plane, mods in theirs["modules"].items():
        assert len(mine["modules"][plane]) == len(mods)
    assert tr.reduce(tr.load(path)) == before
    assert set(before) == {"window_s", "busy_s", "n_devices", "ops", "gaps"}


def test_readers_through_the_harness(tiny_root, capsys):
    rc, res = run_cell(tiny_root, "tiny.anal_open", trace=1, seconds=2.0,
                       capsys=capsys)
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["engine_host_s_per_batch"]["value"] > 0
    rc, res = run_cell(tiny_root, "tiny.anal_k4", trace=1, capsys=capsys)
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["setup_compile_s"]["value"] > 0


def test_readers_against_an_older_program(tiny_root, capsys, monkeypatch):
    _older_program(monkeypatch)
    rc, res = run_cell(tiny_root, "tiny.anal_k4", trace=1, capsys=capsys)
    assert rc == 0 and res["correct"] is True
    assert "setup_compile_s" not in res["metrics"]
