import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 host-platform devices stand in for 2 TPU v5e pods; every
cell's step function must partition, lower and compile, and the compiled
artifact yields the memory/cost analysis the roofline reads.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
      --shape train_4k --mesh single --out results/dryrun

The XLA_FLAGS assignment above MUST precede any jax import (device count
locks at first init); it is deliberately NOT set in conftest.py or
pyproject -- smoke tests and benches see 1 device.

Accounting correction
---------------------
XLA's HloCostAnalysis counts a while-loop body ONCE regardless of trip
count, so a scan-over-layers model under-reports flops/bytes by ~n_layers
and hides per-iteration collectives.  We therefore compile, per cell:
  * the FULL-depth step (the required mesh-validity + memory proof), and
  * 2-3 shallow "accounting" variants (scan unrolled, dense attention,
    single-chunk loss) whose per-group cost slopes extrapolate exactly to
    the full depth:  f_full = f_base + sum_g (reps_g - base_g) * slope_g.
Both raw and corrected numbers are recorded; the roofline (EXPERIMENTS.md)
uses the corrected ones.
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

import repro  # noqa: F401
from repro.configs import registry
from repro.configs.base import SHAPES
from repro.launch.mesh import make_production_mesh, sht_axis_names
from repro.models.model import make_bundle, input_specs
from repro.roofline import analysis as RA
from repro.train import optimizer as O
from repro.train import train_loop as TL


def _sds_with(tree_sds, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree_sds, shardings)


def _nrows(mesh):
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names
                        if a in ("pod", "data")]))


def _maybe_flat_batch_bundle(cfg, mesh, B):
    """Bundle with a replicated batch axis when B doesn't split the DP rows
    (the long_500k B=1 cell: data axis idle by design)."""
    bundle = make_bundle(cfg, mesh)
    if B % _nrows(mesh) != 0:
        rules = dataclasses.replace(bundle.rt.rules, batch=None)
        rt = dataclasses.replace(bundle.rt, rules=rules)
        bundle = dataclasses.replace(bundle, rt=rt)
    return bundle


def _lower_step(cfg, shape, mesh):
    """Lower one cell's step function (train/prefill/decode)."""
    B, S = shape.global_batch, shape.seq_len
    bundle = _maybe_flat_batch_bundle(cfg, mesh, B)
    if shape.kind == "train":
        tcfg = TL.TrainConfig()
        step = TL.make_train_step(bundle, tcfg)
        p_sh, o_sh = TL.train_state_shardings(bundle, tcfg)
        p_sds = _sds_with(jax.eval_shape(bundle.init, jax.random.PRNGKey(0)),
                          p_sh)
        o_sds = _sds_with(
            jax.eval_shape(lambda p: O.init_opt_state(p, tcfg.opt), p_sds),
            o_sh)
        batch = input_specs(cfg, shape, mesh)
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
        return jax.jit(step, donate_argnums=(0, 1)).lower(
            p_sds, o_sds, batch, rng)
    p_sh = bundle.param_shardings()
    p_sds = _sds_with(jax.eval_shape(bundle.init, jax.random.PRNGKey(0)),
                      p_sh)
    if shape.kind == "prefill":
        batch = input_specs(cfg, shape, mesh)
        caches = input_specs(cfg, dataclasses.replace(shape, kind="decode"),
                             mesh)["caches"]
        return jax.jit(bundle.prefill_fn, donate_argnums=(2,)).lower(
            p_sds, batch, caches)
    ins = input_specs(cfg, shape, mesh)
    return jax.jit(bundle.decode_fn, donate_argnums=(3,)).lower(
        p_sds, ins["token"], ins["pos"], ins["caches"])


# -- accounting variants --------------------------------------------------------


def _depth_overrides(cfg, reps):
    """Map per-group repeat counts -> ArchConfig depth overrides."""
    if cfg.is_encoder_decoder:
        return dict(n_encoder_layers=reps[0], n_layers=reps[1])
    if cfg.block_pattern:
        pat = tuple(cfg.block_pattern)
        n_full = cfg.n_layers // len(pat)
        rem = cfg.n_layers - n_full * len(pat)
        return dict(n_layers=reps[0] * len(pat) + rem)
    if cfg.n_experts and cfg.first_dense_layers:
        return dict(first_dense_layers=reps[0], n_layers=reps[0] + reps[1])
    return dict(n_layers=reps[0])


def _group_reps_full(cfg):
    if cfg.is_encoder_decoder:
        return [cfg.n_encoder_layers, cfg.n_layers]
    if cfg.block_pattern:
        return [cfg.n_layers // len(cfg.block_pattern)]
    if cfg.n_experts and cfg.first_dense_layers:
        return [cfg.first_dense_layers, cfg.n_layers - cfg.first_dense_layers]
    return [cfg.n_layers]


def _acct_cfg(cfg, reps, attn_impl="dense"):
    # inner_unroll explodes HLO for the mlstm chunk scan at 32k+ sequences;
    # the ssm family gets analytic flops instead (below), so never unroll it.
    return dataclasses.replace(
        cfg, scan_unroll=True, attn_impl=attn_impl, loss_chunks=1,
        inner_unroll=(cfg.family != "ssm"), **_depth_overrides(cfg, reps))


def _ssm_analytic_flops(cfg, shape, n_dev):
    """Closed-form per-device flops for the xLSTM family (the chunkwise
    mixing lives inside a scan whose trip count scales with S, which defeats
    the depth-slope trick; the architecture is exactly known, so count it).
    """
    d = cfg.d_model
    di = int(d * cfg.mlstm_pf)
    H = cfg.n_heads
    hd = di // H
    c = 64                                   # production chunk size
    per_tok_mlstm = (2 * d * 2 * di          # up
                     + 3 * 2 * di * di       # q, k, v
                     + 2 * 2 * di * H        # gates
                     + 2 * 2 * c * di        # intra-chunk qk + pv
                     + 2 * 2 * H * hd * hd   # inter read + state update
                     + 2 * di * d            # down
                     + 20 * di)              # norms/gating elementwise
    dff = int(d * 4.0 / 3.0)
    per_tok_slstm = (4 * 2 * d * d           # wz, wi, wf, wo
                     + 3 * 2 * d * dff       # ffn
                     + 30 * d)               # scan elementwise
    n_m = sum(1 for g_, n in
              [(p, 1) for p in (cfg.block_pattern or ())] if g_ == "mlstm")
    pat = cfg.block_pattern or ("mlstm",)
    L = cfg.n_layers
    n_mlstm = sum(1 for i in range(L) if pat[i % len(pat)] == "mlstm")
    n_slstm = L - n_mlstm
    per_tok = n_mlstm * per_tok_mlstm + n_slstm * per_tok_slstm
    loss = 2 * d * cfg.vocab
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = tokens * (per_tok * 4.0 + loss * 3.0)   # bwd + remat
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = tokens * per_tok + shape.global_batch * loss
    else:
        total = shape.global_batch * (per_tok + loss)
    return total / n_dev


def _measure(cfg, shape, mesh, n_dev):
    lowered = _lower_step(cfg, shape, mesh)
    compiled = lowered.compile()
    cost = {}
    try:
        cost = compiled.cost_analysis() or {}
    except Exception:
        pass
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    wire = RA.collective_bytes(compiled.as_text(), n_dev)["total"]
    return {"flops": flops, "bytes": byts, "wire": wire}


def _extrapolate(cfg, shape, mesh, n_dev, attn_impl):
    full = _group_reps_full(cfg)
    base_reps = [1] * len(full)
    base = _measure(_acct_cfg(cfg, base_reps, attn_impl), shape, mesh, n_dev)
    out = dict(base)
    details = {"base": base, "slopes": []}
    for g in range(len(full)):
        bump = list(base_reps)
        bump[g] += 1
        m = _measure(_acct_cfg(cfg, bump, attn_impl), shape, mesh, n_dev)
        slope = {k: m[k] - base[k] for k in base}
        details["slopes"].append(slope)
        for k in out:
            out[k] += (full[g] - base_reps[g]) * slope[k]
    return {k: max(v, 0.0) for k, v in out.items()}, details


def account_lm_cell(cfg, shape, mesh):
    """Extrapolated full-depth per-device (flops, bytes, wire bytes).

    Two passes: a dense-attention pass counts the true attention FLOPs in
    one un-looped HLO; an mea pass counts HBM-realistic BYTES (a fused TPU
    attention kernel keeps score tiles in VMEM -- the dense pass would
    charge the S^2 score materialisation to HBM).  Wire bytes: max of both.
    """
    n_dev = mesh.size
    if cfg.family == "ssm":
        by, d2 = _extrapolate(cfg, shape, mesh, n_dev, "mea")
        out = {"flops": _ssm_analytic_flops(cfg, shape, n_dev),
               "bytes": by["bytes"], "wire": by["wire"]}
        return out, {"mea_pass": d2, "flops": "analytic (ssm family)"}
    fl, d1 = _extrapolate(cfg, shape, mesh, n_dev, "dense")
    by, d2 = _extrapolate(cfg, shape, mesh, n_dev, "mea")
    out = {"flops": fl["flops"], "bytes": by["bytes"],
           "wire": max(fl["wire"], by["wire"])}
    return out, {"dense_pass": d1, "mea_pass": d2}


# -- cell drivers ----------------------------------------------------------------


def lower_lm_cell(arch: str, shape_name: str, multi_pod: bool,
                  profile: str | None = None, moe_impl: str | None = None):
    cfg = registry.get(arch)
    if profile:
        cfg = dataclasses.replace(cfg, tp_profile=profile)
    if moe_impl:
        cfg = dataclasses.replace(cfg, moe_impl=moe_impl)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    B, S = shape.global_batch, shape.seq_len

    if shape_name == "long_500k" and not cfg.long_context_ok:
        return {"status": "skip",
                "reason": "full-attention arch cannot serve a 524288-token "
                          "dense KV cache; sub-quadratic archs only "
                          "(DESIGN.md §6)"}

    lowered = _lower_step(cfg, shape, mesh)
    n_active = cfg.n_active_params()
    if shape.kind == "train":
        model_flops = 6.0 * n_active * B * S
    elif shape.kind == "prefill":
        model_flops = 2.0 * n_active * B * S
    else:
        model_flops = 2.0 * n_active * B
    return {"status": "ok", "lowered": lowered, "n_devices": mesh.size,
            "model_flops": model_flops, "cfg": cfg, "shape": shape,
            "mesh_obj": mesh, "n_params": cfg.n_params(),
            "n_active_params": n_active}


def lower_sht_cell(shape_name: str, multi_pod: bool, *, fold=False,
                   comm_dtype=None, stage1="jnp", variant=None):
    from repro.configs.sht_cmb import SHT_SHAPES
    from repro.core import grids, plan as planlib, dist_sht
    scfg = SHT_SHAPES[shape_name]
    if comm_dtype is not None or fold:
        scfg = dataclasses.replace(scfg, fold=fold, comm_dtype=comm_dtype)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    g = grids.make_grid("gl", l_max=scfg.l_max)
    p = planlib.SHTPlan(g, scfg.l_max, scfg.l_max, n_dev)
    if variant is not None:
        os.environ["REPRO_LEGENDRE_VARIANT"] = variant
    d = dist_sht.DistSHT(p, mesh, sht_axis_names(mesh), dtype=scfg.dtype,
                         fold=scfg.fold, comm_dtype=scfg.comm_dtype,
                         stage1=stage1)
    if scfg.direction == "synth":
        lowered, _ = d.lower_synth(scfg.K)
    else:
        lowered, _ = d.lower_anal(scfg.K)
    # Useful flops: recurrence (6) + complex accumulate (8K) per (l>=m, m,
    # ring) triple, + the batched FFT stage.  (No layer scans: the l loop is
    # a real sequential dependence counted per-iteration... NOT -- it is a
    # fori_loop, also undercounted; corrected analytically below since the
    # trip count (l_max+1) is exact and the body is homogeneous.)
    L1 = scfg.l_max + 1
    tri = g.n_rings * L1 * (L1 + 1) / 2.0
    n = g.max_n_phi
    fft = 5.0 * g.n_rings * n * np.log2(n) * scfg.K
    model_flops = tri * (6.0 + 8.0 * scfg.K) + fft
    return {"status": "ok", "lowered": lowered, "n_devices": n_dev,
            "model_flops": model_flops, "n_params": 0, "n_active_params": 0,
            "sht_cfg": scfg, "sht_grid": g}


def _sht_corrected(rec_roof, scfg, grid, n_dev, K):
    """Analytic while-loop correction for the SHT cell: the l fori_loop has
    l_max+1 iterations; stage-1 flops/bytes scale with it.  Collective
    bytes (one all_to_all outside the loop) are already correct.
    fold=True: the recurrence runs on northern rings only (20 -> 10 flops
    per triple); the parity accumulate cost is unchanged."""
    L1 = scfg.l_max + 1
    # per-device recurrence work (triangular, min-max balanced)
    tri_steps = grid.n_rings * L1 * (L1 + 1) / 2.0 / n_dev
    rec_per_step = (10.0 if scfg.fold else 20.0) + 8.0 * K
    rec_flops = tri_steps * rec_per_step
    n = grid.max_n_phi
    fft_flops = 5.0 * (grid.n_rings / n_dev) * n * np.log2(n) * K
    flops = rec_flops + fft_flops
    # bytes: a_lm read once, Delta written once, exchanged, maps written
    dt = 4 if scfg.dtype == "float32" else 8
    bytes_ = (L1 * L1 / 2 / n_dev * 2 * K          # alm
              + 2 * grid.n_rings * L1 / n_dev * 2 * K   # Delta in/out
              + grid.n_rings * n / n_dev * K) * dt
    return flops, bytes_


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             skip_existing: bool = True, account: bool = True, **sht_kw):
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape_name}__{mesh_kind}"
    if sht_kw:
        extras = "_".join(f"{k}-{v}" for k, v in sorted(sht_kw.items())
                          if v not in (None, False, "jnp"))
        if extras:
            tag += "__" + extras
    path = os.path.join(out_dir, tag + ".json")
    if skip_existing and os.path.exists(path):
        print(f"[dryrun] {tag}: cached")
        return json.load(open(path))
    multi = mesh_kind == "multi"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag}
    t0 = time.time()
    try:
        if arch == "sht_cmb":
            out = lower_sht_cell(shape_name, multi, **sht_kw)
        else:
            out = lower_lm_cell(arch, shape_name, multi,
                                profile=sht_kw.get("profile"),
                                moe_impl=sht_kw.get("moe_impl"))
        rec["status"] = out["status"]
        if out["status"] == "skip":
            rec["reason"] = out["reason"]
        else:
            lowered = out.pop("lowered")
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = time.time() - t1
            try:
                m = compiled.memory_analysis()
                rec["memory_analysis"] = {k: int(getattr(m, k)) for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "alias_size_in_bytes") if
                    hasattr(m, k)}
            except Exception as e:  # pragma: no cover
                rec["memory_analysis"] = {"error": str(e)}
            roof_raw = RA.analyze_compiled(
                compiled, n_devices=out["n_devices"],
                model_flops=out["model_flops"])
            rec["roofline_raw"] = roof_raw.to_dict()
            # corrected accounting
            if arch == "sht_cmb":
                fl, by = _sht_corrected(rec["roofline_raw"], out["sht_cfg"],
                                        out["sht_grid"], out["n_devices"],
                                        out["sht_cfg"].K)
                roof = dataclasses.replace(
                    roof_raw, flops_per_device=fl, bytes_per_device=by)
                rec["roofline"] = roof.to_dict()
            elif account:
                cfg = out["cfg"]
                acct, details = account_lm_cell(cfg, out["shape"],
                                                out["mesh_obj"])
                roof = dataclasses.replace(
                    roof_raw, flops_per_device=acct["flops"],
                    bytes_per_device=acct["bytes"],
                    wire_bytes_per_device=max(acct["wire"],
                                              roof_raw.wire_bytes_per_device))
                rec["roofline"] = roof.to_dict()
                rec["accounting"] = details
            else:
                rec["roofline"] = rec["roofline_raw"]
            rec["n_params"] = out["n_params"]
            rec["n_active_params"] = out["n_active_params"]
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["wall_s"] = time.time() - t0
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    bot = rec.get("roofline", {}).get("bottleneck", "-")
    print(f"[dryrun] {tag}: {rec['status']} ({rec['wall_s']:.1f}s, "
          f"bottleneck={bot})")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-account", action="store_true")
    ap.add_argument("--fold", action="store_true")
    ap.add_argument("--comm-dtype", default=None)
    ap.add_argument("--stage1", default="jnp")
    ap.add_argument("--variant", default=None)
    ap.add_argument("--profile", default=None,
                    help="override tp_profile (tp|small|dp) for perf iters")
    ap.add_argument("--moe-impl", default=None, choices=[None, "a2a",
                                                         "replicated"])
    a = ap.parse_args()
    kw = {}
    if a.arch == "sht_cmb":
        kw = dict(fold=a.fold, comm_dtype=a.comm_dtype, stage1=a.stage1,
                  variant=a.variant)
    else:
        if a.profile:
            kw["profile"] = a.profile
        if a.moe_impl:
            kw["moe_impl"] = a.moe_impl
    rec = run_cell(a.arch, a.shape, a.mesh, a.out,
                   skip_existing=not a.force, account=not a.no_account, **kw)
    raise SystemExit(0 if rec["status"] in ("ok", "skip") else 1)


if __name__ == "__main__":
    main()
