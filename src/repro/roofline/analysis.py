"""Three-term roofline analysis from compiled XLA artifacts.

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = wire_bytes_per_device / link_bw

HLO_FLOPs / bytes come from ``compiled.cost_analysis()`` (the compiled
module is the per-device SPMD program, so they are already per-device).
Collective bytes are NOT in cost_analysis: we parse the post-optimisation
HLO text and sum the wire traffic of every all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute, using ring-algorithm
per-device wire-byte formulas.

Hardware model (TPU v5e, per task spec): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

import numpy as np

__all__ = ["HW_V5E", "HW_HOST", "PEAKS", "hardware_for", "Roofline",
           "collective_bytes", "analyze_compiled", "parse_hlo_collectives",
           "sht_work", "predict_sht_time",
           "predict_comm_chunks", "BACKEND_MODELS", "BackendModel"]


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops: float        # bf16 FLOP/s per chip
    hbm_bw: float            # bytes/s per chip
    link_bw: float           # bytes/s per ICI link
    coll_latency: float = 1e-6   # launch latency per collective [s]
    #: fixed device time of one l step of the Legendre loop, whatever its
    #: rows [s]: ~28 us for the jnp loop on a TPU v5e (PERF.md §5)
    loop_step: float = 28e-6


HW_V5E = Hardware("tpu-v5e", 197e12, 819e9, 50e9)

#: Crude single-host CPU model (this container's baseline).  Used by the
#: ``mode="model"`` dispatch when no accelerator is attached; the absolute
#: numbers matter less than the *relative* per-backend ranking.  Simulated
#: host "collectives" are memcpys behind a dispatch, so the per-collective
#: launch latency is an order worse than real ICI.
HW_HOST = Hardware("host-cpu", 2e11, 5e10, 1e10, coll_latency=1e-5,
                   loop_step=2e-6)

#: Per-chip peaks keyed by JAX's ``device_kind``.  Source: Google Cloud
#: documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
#: 1,600 Gbit/s chip-to-chip interconnect = 4 links x 50 GB/s).
PEAKS = {"TPU v5 lite": HW_V5E}


def hardware_for(device=None) -> Hardware:
    """The cost-model peaks of ``device`` (default: the first JAX device).

    A CPU backend gets the crude host model; an accelerator must be listed
    in :data:`PEAKS` -- an unknown device kind raises instead of being
    priced as some other chip."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform == "cpu":
        return HW_HOST
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device kind {device.device_kind!r} "
            f"({device.platform}); known: {sorted(PEAKS)}") from None


# ---------------------------------------------------------------------------
# Analytic SHT cost model (drives repro.make_plan's ``mode="model"`` dispatch)
# ---------------------------------------------------------------------------


def sht_work(l_max: int, m_max: int, n_rings: int, n_phi: int,
             K: int, fft_lengths=None, spin: int = 0) -> dict:
    """Operation counts of one transform direction (paper §3 complexity).

    Returns a dict with:
      ``recurrence_flops`` -- P_lm generation, O(R * n_lm), K-independent
                              (the paper's on-the-fly beta recomputation:
                              ~10 flops per (l, m, ring) step);
      ``accum_flops``      -- the a_lm / Delta_m contraction, 4K flops per
                              (l, m, ring) (complex FMA) -- this is the part
                              an MXU can take as a matmul;
      ``fft_flops``        -- batched ring FFTs.  With ``fft_lengths``
                              (the per-ring bucket lengths of a ragged
                              grid's phase stage) the cost is summed per
                              bucketed ring instead of assuming one n_phi;
      ``bytes``            -- HBM traffic lower bound (alm + maps + Delta).

    ``spin=2`` doubles every term: the spin path runs TWO Wigner-d
    recurrences per m (the lambda^{+/-} panel pair), accumulates two alm
    components (E, B) and transforms two maps (Q, U).
    """
    ncomp = 1 if spin == 0 else 2
    n_lm = (m_max + 1) * (l_max + 1) - m_max * (m_max + 1) // 2
    rec = 10.0 * n_lm * n_rings * ncomp
    acc = 4.0 * n_lm * n_rings * K * ncomp
    if fft_lengths is not None:
        fl = np.asarray(fft_lengths, dtype=np.float64)
        fft = 5.0 * float(np.sum(fl * np.log2(np.maximum(fl, 2.0)))) * K
        maps_elems = float(np.sum(fl)) * K
    else:
        fft = 5.0 * n_rings * n_phi * float(np.log2(max(n_phi, 2))) * K
        maps_elems = float(n_rings * n_phi) * K
    fft *= ncomp
    maps_elems *= ncomp
    byts = (16.0 * (m_max + 1) * (l_max + 1) * K * ncomp   # alm (complex)
            + 8.0 * maps_elems                             # maps
            + 16.0 * (m_max + 1) * n_rings * K * ncomp)    # Delta (complex)
    return {"n_lm": n_lm, "recurrence_flops": rec, "accum_flops": acc,
            "fft_flops": fft, "bytes": byts,
            "total_flops": rec + acc + fft}


@dataclasses.dataclass(frozen=True)
class BackendModel:
    """Effective-throughput model of one execution backend.

    ``vector_eff``/``matrix_eff`` are fractions of ``Hardware.peak_flops``
    achieved on vector (VPU/scalar) and matrix (MXU) work; ``matrix_eff = 0``
    means the accumulation runs on the vector unit too.  ``anal_penalty``
    models the paper's direct/inverse dichotomy (§5): the analysis direction
    pays extra for its ring reduction (the paper's Algorithm 5 atomics), so
    the same backend may win synthesis and lose analysis.
    """

    name: str
    vector_eff: float
    matrix_eff: float = 0.0
    anal_penalty: float = 1.0


BACKEND_MODELS = {
    # the jnp loop: separate HLO ops, memory-bound.
    "jnp": BackendModel("jnp", vector_eff=0.01, anal_penalty=1.0),
    # dist = one device's share of the work + one all_to_all on the wire.
    # Its efficiencies are the old guesses for an MXU Legendre stage, not
    # the jnp loop that dist now runs (which "jnp" prices at 0.01), so
    # mode="model" ranks the two inconsistently.  They are kept because
    # predict_comm_chunks' C = 1 pick for the cells depends on them;
    # ROADMAP C5 replaces them with calibrated ones.
    "dist": BackendModel("dist", vector_eff=0.06, matrix_eff=0.4,
                         anal_penalty=1.2),
}


def predict_sht_time(backend: str, *, l_max: int, m_max: int, n_rings: int,
                     n_phi: int, K: int, direction: str = "synth",
                     hw: Hardware = HW_V5E, n_devices: int = 1,
                     fft_lengths=None, spin: int = 0,
                     overlap: bool = False, comm_chunks: int = 1) -> float:
    """Predicted seconds for one transform on ``backend`` (3-term model).

    compute = recurrence/vector + accumulation/(matrix or vector) + fft;
    memory = bytes / HBM bw;  collective (dist only) = all_to_all wire
    bytes / link bw.  The terms are summed (no overlap assumed -- the
    paper's kernels are serial stages), and ``anal_penalty`` is applied for
    ``direction="anal"``.  ``fft_lengths`` carries a ragged grid's
    per-ring bucket lengths into the FFT term; ``spin=2`` doubles every
    term including the exchanged Delta block (see `sht_work`).

    ``overlap=True`` with ``comm_chunks=C > 1`` (dist backend only) models
    the chunked software-pipelined exchange (`DistSHT(comm_chunks=C)`):
    instead of ``comp + comm``, the distributed time is the pipeline

        comp/C + comm_chunk + (C-1) * max(comp/C, comm_chunk)

    where ``comm_chunk = comm/C + hw.coll_latency`` -- each chunk's
    collective hides behind the adjacent chunk's compute, at the price of
    one extra collective-launch latency per chunk.  The chunks also repeat
    stage-1 work (`SHTPlan.chunk_schedule` splits K when K >= C, else the
    local m rows): each K chunk runs the K-independent recurrence again,
    and each m chunk runs its own loop over every l, ``hw.loop_step`` a
    step.  ``C=1`` reproduces the serial sum exactly.
    """
    if backend not in BACKEND_MODELS:
        raise ValueError(f"unknown backend {backend!r}")
    m = BACKEND_MODELS[backend]
    w = sht_work(l_max, m_max, n_rings, n_phi, K, fft_lengths=fft_lengths,
                 spin=spin)
    vec_rate = hw.peak_flops * m.vector_eff
    t = w["recurrence_flops"] / vec_rate + w["fft_flops"] / vec_rate
    if m.matrix_eff > 0:
        t += w["accum_flops"] / (hw.peak_flops * m.matrix_eff)
    else:
        t += w["accum_flops"] / vec_rate
    t += w["bytes"] / hw.hbm_bw
    if backend == "dist" and n_devices > 1:
        t /= n_devices
        # one tiled all_to_all of the (M, R, ncomp*2K) Delta block
        ncomp = 1 if spin == 0 else 2
        wire = 16.0 * (m_max + 1) * n_rings * K * ncomp / n_devices \
            * (n_devices - 1) / n_devices
        comm = wire / hw.link_bw
        C = max(1, int(comm_chunks))
        if overlap and C > 1 and comm > 0.0:
            m_local = -(-(m_max + 2) // (2 * n_devices)) * 2
            if K >= C:
                t += (C - 1) * w["recurrence_flops"] / vec_rate / n_devices
            else:
                t += (min(C, m_local) - 1) * (l_max + 1) * hw.loop_step
            comp_c = t / C
            comm_c = comm / C + hw.coll_latency
            t = comp_c + comm_c + (C - 1) * max(comp_c, comm_c)
        else:
            t += comm
    if direction == "anal":
        t *= m.anal_penalty
    return float(t)


def predict_comm_chunks(*, l_max: int, m_max: int, n_rings: int, n_phi: int,
                        K: int, direction: str = "synth",
                        hw: Hardware = HW_V5E, n_devices: int = 1,
                        fft_lengths=None, spin: int = 0,
                        max_chunks: int = 64) -> int:
    """Model-optimal ``comm_chunks`` for the dist backend's chunked
    exchange: argmin over powers of two of the overlapped
    `predict_sht_time`.  The cap is additionally clamped to what the plan
    can actually split -- the K channel axis, falling back to the local
    m rows (`SHTPlan.chunk_schedule` applies the same rule)."""
    if n_devices <= 1:
        return 1
    m_local = max(1, -(-(m_max + 2) // (2 * max(1, n_devices))) * 2)
    cap = min(max_chunks, max(int(K), m_local))
    cands = [1]
    while cands[-1] * 2 <= cap:
        cands.append(cands[-1] * 2)
    t_of = {c: predict_sht_time(
        "dist", l_max=l_max, m_max=m_max, n_rings=n_rings, n_phi=n_phi,
        K=K, direction=direction, hw=hw, n_devices=n_devices,
        fft_lengths=fft_lengths, spin=spin, overlap=True, comm_chunks=c)
        for c in cands}
    return int(min(t_of, key=t_of.get))

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# e.g.  %all-reduce.1 = f32[512,128]{1,0} all-reduce(...), replica_groups=...
_COLL_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_TUPLE_COLL_RE = re.compile(
    r"=\s*\(([^)]*)\)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUP_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_GROUP_RE2 = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str, world: int) -> int:
    m = _GROUP_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUP_RE2.search(line)
    if m:  # replica_groups=[G,S] -> S per group
        return int(m.group(2))
    return world


def parse_hlo_collectives(hlo_text: str, world: int):
    """Yield (op_kind, payload_bytes, group_size) per collective op."""
    out = []
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _COLL_RE.search(line)
        if m:
            dtype, dims, kind = m.group(1), m.group(2), m.group(3)
            out.append((kind, _shape_bytes(dtype, dims),
                        _group_size(line, world)))
            continue
        m = _TUPLE_COLL_RE.search(line)
        if m:
            kind = m.group(2)
            tot = sum(_shape_bytes(d, s)
                      for d, s in _SHAPE_RE.findall(m.group(1)))
            # async tuple shapes repeat (operand, result): halve
            out.append((kind, tot // 2 if "-start" in line else tot,
                        _group_size(line, world)))
    return out


def collective_bytes(hlo_text: str, world: int) -> dict:
    """Per-device wire bytes by collective kind (ring-algorithm model)."""
    per_kind: dict = {}
    total = 0.0
    for kind, size, g in parse_hlo_collectives(hlo_text, world):
        frac = (g - 1) / max(g, 1)
        if kind == "all-reduce":
            wire = 2.0 * size * frac          # reduce-scatter + all-gather
        elif kind in ("all-gather", "reduce-scatter", "all-to-all"):
            wire = size * frac
        else:  # collective-permute
            wire = float(size)
        per_kind[kind] = per_kind.get(kind, 0.0) + wire
        per_kind.setdefault(f"{kind}_count", 0)
        per_kind[f"{kind}_count"] += 1
        total += wire
    per_kind["total"] = total
    return per_kind


def _cost_get(cost, key):
    if cost is None:
        return 0.0
    if isinstance(cost, dict):
        return float(cost.get(key, 0.0))
    if isinstance(cost, (list, tuple)) and cost:
        return float(cost[0].get(key, 0.0))
    return 0.0


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    n_devices: int
    hw: Hardware = HW_V5E
    model_flops: float = 0.0           # 6*N*D (or 6*N_active*D) total
    collectives: Optional[dict] = None

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / self.hw.peak_flops

    @property
    def t_memory(self) -> float:
        return self.bytes_per_device / self.hw.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.wire_bytes_per_device / self.hw.link_bw

    @property
    def bottleneck(self) -> str:
        ts = {"compute": self.t_compute, "memory": self.t_memory,
              "collective": self.t_collective}
        return max(ts, key=ts.get)

    @property
    def useful_flops_fraction(self) -> float:
        tot = self.flops_per_device * self.n_devices
        return self.model_flops / tot if tot else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the dominant-resource bound achieved by useful work:
        t_useful_compute / max(t_compute, t_memory, t_collective)."""
        t_bound = max(self.t_compute, self.t_memory, self.t_collective)
        if t_bound <= 0:
            return 0.0
        t_useful = (self.model_flops / max(self.n_devices, 1)) \
            / self.hw.peak_flops
        return t_useful / t_bound

    def to_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "wire_bytes_per_device": self.wire_bytes_per_device,
            "n_devices": self.n_devices,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_flops_fraction": self.useful_flops_fraction,
            "roofline_fraction": self.roofline_fraction,
            "collectives": self.collectives,
        }


def analyze_compiled(compiled, *, n_devices: int, model_flops: float = 0.0,
                     hw: Hardware = HW_V5E) -> Roofline:
    cost = None
    try:
        cost = compiled.cost_analysis()
    except Exception:
        pass
    flops = _cost_get(cost, "flops")
    byts = _cost_get(cost, "bytes accessed")
    try:
        txt = compiled.as_text()
    except Exception:
        txt = ""
    colls = collective_bytes(txt, n_devices)
    return Roofline(
        flops_per_device=flops, bytes_per_device=byts,
        wire_bytes_per_device=colls["total"], n_devices=n_devices, hw=hw,
        model_flops=model_flops, collectives=colls)
