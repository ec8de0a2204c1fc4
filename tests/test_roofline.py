import numpy as np
import pytest

import repro  # noqa: F401
from repro.core import comm_model as CM
from repro.roofline import analysis as RA


HLO = """
ENTRY main {
  %p = f32[1024,256]{1,0} parameter(0)
  %ar = f32[1024,256]{1,0} all-reduce(%p), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = bf16[4096,128]{1,0} all-gather(%x), dimensions={0}, replica_groups=[2,256]<=[512]
  %a2a = f32[512,64]{1,0} all-to-all(%y), dimensions={0}
  %cp = f32[16,16]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
  %ars = (f32[8,8]{1,0}, f32[8,8]{1,0}) all-reduce-start(%w), replica_groups={{0,1}}
  %ard = f32[8,8]{1,0} all-reduce-done(%ars)
}
"""


def test_collective_parser():
    ops = RA.parse_hlo_collectives(HLO, world=512)
    kinds = [k for k, _, _ in ops]
    assert kinds.count("all-reduce") == 2      # sync + async start
    assert "all-gather" in kinds and "all-to-all" in kinds
    assert "collective-permute" in kinds
    by = {((k, g)): s for k, s, g in ops}
    assert by[("all-reduce", 4)] == 1024 * 256 * 4
    assert by[("all-gather", 256)] == 4096 * 128 * 2
    assert by[("all-reduce", 2)] == 8 * 8 * 4   # async tuple halved


def test_collective_wire_model():
    out = RA.collective_bytes(HLO, world=512)
    # ring all-reduce: 2 * S * (g-1)/g
    assert abs(out["all-reduce"] - (2 * 1024 * 256 * 4 * 3 / 4
                                    + 2 * 8 * 8 * 4 * 1 / 2)) < 1
    assert out["total"] > 0


def test_roofline_terms_and_bottleneck():
    r = RA.Roofline(flops_per_device=197e12, bytes_per_device=819e9,
                    wire_bytes_per_device=0.0, n_devices=4,
                    model_flops=4 * 197e12 / 2)
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 1.0) < 1e-9
    assert r.useful_flops_fraction == 0.5
    assert r.roofline_fraction == 0.5
    r2 = RA.Roofline(1e12, 1e9, 1e12, 4)
    assert r2.bottleneck == "collective"


def test_comm_model_matches_paper_structure():
    p = CM.MPICH_CLUSTER
    # Fig. 4 middle/left behaviours: compute ~ 1/nproc, comm ~ flat (large
    # msgs), so a crossover exists and grows with problem size.
    t64 = CM.sht_times(4096, 64, p)
    t512 = CM.sht_times(4096, 512, p)
    assert t512["compute"] < t64["compute"] / 4
    assert t512["comm"] >= 0.8 * t64["comm"]
    c1 = CM.crossover_nproc(1024, p)
    c2 = CM.crossover_nproc(8192, p)
    assert c2 >= c1
    # message-size switch: tiny problems land in the Bruck branch
    small = CM.message_size(63, 32, 64)
    assert small < p.bruck_cutoff


def test_comm_model_fold_reduces_compute():
    p = CM.TPU_V5E_ICI
    a = CM.sht_times(2048, 256, p, fold=False)
    b = CM.sht_times(2048, 256, p, fold=True)
    assert b["compute"] < a["compute"]
    assert b["comm"] == a["comm"]


def test_overlap_model_chunked_pipeline():
    for p in (CM.MPICH_CLUSTER, CM.TPU_V5E_ICI):
        serial = CM.sht_times(4096, 1024, p)
        # C=1 degenerates to the serial comp + comm sum
        t1 = CM.sht_times_overlap(4096, 1024, p, chunks=1)
        assert abs(t1["overlap"] - serial["total"]) < 1e-12
        assert t1["hidden_frac"] == 0.0
        # the auto pick never loses to serial, and hidden_frac is a fraction
        tb = CM.sht_times_overlap(4096, 1024, p)
        assert tb["chunks"] >= 1
        assert tb["overlap"] <= serial["total"] + 1e-15
        assert 0.0 <= tb["hidden_frac"] <= 1.0
        assert CM.best_chunks(4096, 1024, p) == tb["chunks"]
    # acceptance corner: comm-bound TPU mesh hides > half the hideable time
    corner = CM.sht_times_overlap(4096, 1024, CM.TPU_V5E_ICI)
    assert corner["chunks"] > 1
    assert corner["hidden_frac"] > 0.5, corner


def test_overlap_model_single_process_is_serial():
    t = CM.sht_times_overlap(1024, 1, CM.MPICH_CLUSTER, chunks=8)
    assert t["overlap"] == t["serial"]
    assert t["hidden_frac"] == 0.0


def test_predict_sht_time_overlap_and_chunk_pick():
    kw = dict(l_max=2048, m_max=2048, n_rings=4097, n_phi=8192, K=4,
              hw=RA.HW_V5E, n_devices=16)
    serial = RA.predict_sht_time("dist", **kw)
    over1 = RA.predict_sht_time("dist", overlap=True, comm_chunks=1, **kw)
    assert abs(over1 - serial) < 1e-15          # C=1 == blocking exchange
    c = RA.predict_comm_chunks(**kw)
    assert c >= 1
    over = RA.predict_sht_time("dist", overlap=True, comm_chunks=c, **kw)
    assert over <= serial + 1e-15
    # the pick must beat (or tie) a deliberately bad chunk count
    worse = RA.predict_sht_time("dist", overlap=True, comm_chunks=4096, **kw)
    assert over <= worse + 1e-15


def test_predict_comm_chunks_respects_axis_bounds():
    # K=1 on a single dealt m row leaves nothing to chunk -> C=1
    c = RA.predict_comm_chunks(l_max=8, m_max=8, n_rings=17, n_phi=34,
                               K=1, hw=RA.HW_V5E, n_devices=8, max_chunks=64)
    assert c >= 1
    assert c <= max(1, 64)


@pytest.mark.parametrize("direction", ["synth", "anal"])
def test_predict_comm_chunks_at_anal_4k_k4_on_four_chips(direction):
    # the chunk count measured fastest on a four-chip TPU v5e host at
    # l_max 4096, K=4 (PERF.md §5): each extra chunk repeats stage-1
    # work (the recurrence per K chunk, a loop over l per m chunk)
    c = RA.predict_comm_chunks(l_max=4096, m_max=4096, n_rings=4097,
                               n_phi=8194, K=4, direction=direction,
                               hw=RA.HW_V5E, n_devices=4)
    assert c == 1
