"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST pin the CPU platform and set XLA_FLAGS before any other import (jax
locks the platform and device count on first init) — hence the first
lines.  The 512 devices are host CPU devices; the dry-run never takes an
accelerator, even on a machine that has one.
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import json
import time
import traceback
from typing import Dict

import jax

from repro.analysis.roofline import build_report, model_flops_for
from repro.configs import (ASSIGNED_ARCHS, cell_applicable, get_config,
                           SHAPE_CELLS, smoke_config)
from repro.distributed.sharding import (batch_specs, opt_state_specs,
                                        param_specs, to_named)
from repro.launch import inputs as inp
from repro.launch.mesh import make_production_mesh
from repro.serve.serve_step import (cache_specs, make_decode_step,
                                    make_prefill_step)
from repro.train.train_step import make_train_step

# The chip whose peaks the roofline terms use: the production mesh
# models a v5e pod on host devices.
TARGET_DEVICE_KIND = "TPU v5 lite"

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")


def _accum_steps(cfg, cell) -> int:
    """Grad-accumulation factor sized so activations fit 16 GB HBM."""
    if cell.step != "train":
        return 1
    n = cfg.params_count()
    if n > 80e9:
        return 8
    if n > 20e9:
        return 4
    if n > 5e9:
        return 2
    return 1


def _attach(structs, specs, mesh):
    named = to_named(specs, mesh)
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        structs, named)


def _mem_analysis(compiled) -> Dict[str, float]:
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": float(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": float(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": float(getattr(ma, "temp_size_in_bytes", 0)),
            "generated_code_bytes": float(
                getattr(ma, "generated_code_size_in_bytes", 0)),
            "peak_bytes_estimate": float(
                getattr(ma, "argument_size_in_bytes", 0)
                + getattr(ma, "output_size_in_bytes", 0)
                + getattr(ma, "temp_size_in_bytes", 0)),
        }
    except Exception as e:                                   # CPU backend gaps
        return {"error": str(e)}


PROFILES = ("baseline", "optimized", "optimized_bf16grad")


def _apply_profile(profile: str):
    """Perf-profile knobs (EXPERIMENTS.md §Perf). baseline = paper-faithful
    naive paths; optimized = banded local attention + chunked prefill
    attention + sharded grad accumulators + int8 KV cache."""
    from repro.models.attention import set_attention_impl, set_kv_cache_quant
    from repro.models.moe import set_ep_impl
    from repro.models.transformer import set_loss_dtype
    from repro.kernels.ops import set_preserve_dims
    if profile == "baseline":
        set_attention_impl("naive", "naive")
        set_kv_cache_quant(False)
        set_ep_impl("psum")
        set_loss_dtype("f32")
        set_preserve_dims(False)   # the original flattening linear
        return {"shard_grads": False, "grad_compression": None}
    # chunked global prefill attention was refuted twice (§Perf,
    # cross-cutting): the pure-JAX q/kv-blocked scan trades the S^2
    # materialization for nc x per-block HBM round-trips; the win needs a
    # Pallas flash kernel (VMEM-resident carries) — future work.
    set_attention_impl("banded", "naive")
    set_kv_cache_quant(True)
    set_ep_impl("all_to_all")
    set_loss_dtype("bf16")
    return {"shard_grads": True,
            "grad_compression": ("bf16" if profile == "optimized_bf16grad"
                                 else None)}


def lower_cell(arch: str, cell_name: str, mesh_kind: str,
               smoke: bool = False, remat: str = "full",
               sharding_profile: str = "baseline"):
    """Lower + compile one cell; returns (artifact_dict, compiled)."""
    knobs = _apply_profile(sharding_profile)
    cfg = smoke_config(arch) if smoke else get_config(arch)
    if sharding_profile != "baseline":
        # Dim-preserving contraction is a measured, per-(family x mesh)
        # choice (§Perf #B iterations 1/4 + X4): always a win on the
        # 512-chip mesh (removes GSPMD's involuntary-remat replication
        # across the pod axis, 5x on command-r+); on single-pod the
        # flattened lowering partitions better for the head-sharded
        # dense/MoE models (-25% with preserve) while the
        # replicated-head small models (gemma3, recurrentgemma) win
        # with preserve (1.66x measured on gemma3 train).  A per-cell
        # best-of-two autotune is the production generalization.
        from repro.kernels.ops import set_preserve_dims
        set_preserve_dims(mesh_kind == "multi_pod"
                          or arch in ("gemma3-1b", "recurrentgemma-2b"))
    cell = SHAPE_CELLS[cell_name]
    ok, why = cell_applicable(cfg, cell)
    if not ok:
        return {"arch": arch, "cell": cell_name, "mesh": mesh_kind,
                "status": "skipped", "reason": why}, None

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi_pod"))
    chips = mesh.devices.size
    t0 = time.time()

    params_s = inp.params_structs(cfg)
    pspecs = param_specs(params_s, cfg, mesh)
    params_in = _attach(params_s, pspecs, mesh)

    with mesh:
        if cell.step == "train":
            accum = _accum_steps(cfg, cell)
            step_fn = make_train_step(cfg, mesh, accum_steps=accum,
                                      remat=remat, **knobs)
            opt_s = inp.opt_structs(params_s)
            ospecs = opt_state_specs(pspecs, opt_s)
            opt_in = _attach(opt_s, ospecs, mesh)
            batch_s = inp.batch_structs(cfg, cell)
            bspecs = {k: v for k, v in batch_specs(cell.step, mesh,
                                                   cfg).items()
                      if k in batch_s}
            batch_in = _attach(batch_s, bspecs, mesh)
            lowered = jax.jit(step_fn, donate_argnums=(0, 1)).lower(
                params_in, opt_in, batch_in)
            extra = {"accum_steps": accum}
        elif cell.step == "prefill":
            step_fn = make_prefill_step(cfg, mesh, cache_len=cell.seq_len)
            batch_s = inp.batch_structs(cfg, cell)
            bspecs = {k: v for k, v in batch_specs(cell.step, mesh,
                                                   cfg).items()
                      if k in batch_s}
            batch_in = _attach(batch_s, bspecs, mesh)
            lowered = jax.jit(step_fn).lower(params_in, batch_in)
            extra = {}
        else:                                               # decode
            step_fn = make_decode_step(cfg, mesh)
            tokens_s, pos_s, caches_s = inp.decode_structs(cfg, cell)
            cspecs = cache_specs(caches_s, cfg, mesh)
            caches_in = _attach(caches_s, cspecs, mesh)
            from jax.sharding import NamedSharding, PartitionSpec as P
            tok_in = jax.ShapeDtypeStruct(
                tokens_s.shape, tokens_s.dtype,
                sharding=NamedSharding(mesh, P(None, None)))
            lowered = jax.jit(step_fn, donate_argnums=(1,)).lower(
                params_in, caches_in, tok_in, pos_s)
            extra = {}

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    mem = _mem_analysis(compiled)
    hlo = compiled.as_text()
    report = build_report(
        arch=arch, cell=cell_name, mesh_name=mesh_kind, chips=chips,
        cost=cost, hlo_text=hlo,
        model_flops=model_flops_for(cfg, cell),
        tokens_per_step=cell.global_batch * cell.seq_len,
        device_kind=TARGET_DEVICE_KIND, axis_group_hint=16)

    artifact = {
        "arch": arch, "cell": cell_name, "mesh": mesh_kind,
        "status": "ok", "chips": chips,
        "sharding_profile": sharding_profile,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem,
        "cost_analysis": {k: v for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "roofline": report.to_json(),
        **extra,
    }
    return artifact, compiled


def run(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single_pod",
                    choices=["single_pod", "multi_pod", "both"])
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) cell")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs (plumbing test)")
    ap.add_argument("--remat", default="full",
                    choices=["full", "dots", "none"])
    ap.add_argument("--profile", default="baseline", choices=PROFILES)
    ap.add_argument("--out", default=ART_DIR)
    args = ap.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    archs = ASSIGNED_ARCHS if (args.all or not args.arch) else [args.arch]
    cells = list(SHAPE_CELLS) if (args.all or not args.shape) \
        else [args.shape]
    meshes = ["single_pod", "multi_pod"] if args.mesh == "both" \
        else [args.mesh]

    results = []
    for arch in archs:
        for cell in cells:
            for mesh_kind in meshes:
                tag = f"{arch}__{cell}__{mesh_kind}"
                if args.profile != "baseline":
                    tag += "__" + args.profile
                try:
                    art, compiled = lower_cell(arch, cell, mesh_kind,
                                               smoke=args.smoke,
                                               remat=args.remat,
                                               sharding_profile=args.profile)
                except Exception as e:
                    art = {"arch": arch, "cell": cell, "mesh": mesh_kind,
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()[-2000:]}
                    compiled = None
                path = os.path.join(args.out, tag + ".json")
                with open(path, "w") as f:
                    json.dump(art, f, indent=2)
                status = art["status"]
                msg = f"[{status:7s}] {tag}"
                if status == "ok":
                    r = art["roofline"]
                    msg += (f"  compile={art['compile_s']:.1f}s"
                            f"  bottleneck={r['bottleneck']}"
                            f"  step={r['step_s']*1e3:.2f}ms"
                            f"  peak_frac={r['hw_peak_frac']:.2f}")
                    if "peak_bytes_estimate" in art["memory_analysis"]:
                        gb = art["memory_analysis"]["peak_bytes_estimate"] / 2**30
                        msg += f"  mem~{gb:.1f}GB/dev"
                elif status == "error":
                    msg += "  " + art["error"][:120]
                print(msg, flush=True)
                results.append(art)
                del compiled
    n_ok = sum(1 for r in results if r["status"] == "ok")
    n_skip = sum(1 for r in results if r["status"] == "skipped")
    n_err = sum(1 for r in results if r["status"] == "error")
    print(f"\ndry-run summary: {n_ok} ok, {n_skip} skipped (documented), "
          f"{n_err} errors over {len(results)} cells")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(run())
