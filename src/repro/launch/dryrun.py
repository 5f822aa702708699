import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first: jax locks the device count at first
init, and the production meshes (16×16 single-pod, 2×16×16 multi-pod) need
512 placeholder host devices.

Per cell this driver records to ``results/dryrun/<arch>__<shape>__<mesh>.json``:

* ``memory_analysis()``       — per-device argument/output/temp bytes (the
  "fits on a v5e" proof),
* ``cost_analysis()``         — raw HLO flops/bytes (while-bodies counted
  once; see launch/costs.py),
* probe-extrapolated totals   — flops / bytes / collective bytes,
* the roofline terms and dominant bottleneck (TPU v5e constants),
* MODEL_FLOPS (6·N·D) and the useful-compute ratio.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all            # full sweep
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi
    PYTHONPATH=src python -m repro.launch.dryrun --graphgen       # paper cells
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCHS, LM_SHAPES, SHAPES_BY_NAME, get_config
from repro.launch import mesh as mesh_mod
from repro.launch import costs as costs_mod


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             overrides=None, tag: str = "", skip_probe: bool = False):
    cfg = get_config(arch)
    if overrides:
        ov = dict(overrides)
        pad = ov.pop("__pad_vocab__", None)
        if pad is not None and cfg.vocab % pad:
            ov["vocab"] = ((cfg.vocab + pad - 1) // pad) * pad
        cfg = cfg.replace(**ov)
    shape = SHAPES_BY_NAME[shape_name]
    ok, reason = cfg.supports_shape(shape)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag,
        "config": {"family": cfg.family, "n_layers": cfg.n_layers,
                   "d_model": cfg.d_model, "microbatches": cfg.microbatches,
                   "remat_policy": cfg.remat_policy,
                   "moe_path": cfg.moe_path},
    }
    name = f"{arch}__{shape_name}__{mesh_kind}{('__' + tag) if tag else ''}"
    path = os.path.join(out_dir, name + ".json")
    os.makedirs(out_dir, exist_ok=True)
    if os.environ.get("DRYRUN_SKIP_EXISTING") and os.path.exists(path):
        with open(path) as f:
            prev = json.load(f)
        if prev.get("status") in ("ok", "skipped"):
            print(f"[dryrun] {name}: cached ({prev['status']})")
            return prev
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = reason
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        print(f"[dryrun] {name}: SKIPPED ({reason[:60]}...)")
        return rec

    mesh = mesh_mod.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.size
    from repro.training.steps import build_cell

    from repro.distributed import sharding as shd
    try:
        t0 = time.time()
        cell = build_cell(cfg, shape, mesh)
        with shd.active_mesh(mesh), shd.activation_rules(
                shd.make_rules(cfg, mesh)):
            lowered = jax.jit(
                cell.fn, in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings,
                donate_argnums=cell.donate).lower(*cell.args)
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        print(compiled.memory_analysis())
        rec["status"] = "ok"
        rec["t_lower_s"] = round(t_lower, 2)
        rec["t_compile_s"] = round(t_compile, 2)
        rec["memory_analysis"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_per_device": (ma.argument_size_in_bytes
                                      + ma.temp_size_in_bytes),
        }
        rec["cost_analysis_raw"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        }
        # collective schedule of the real (scan) compile, body counted once
        rec["collectives_scan_hlo"] = costs_mod.parse_collectives(
            compiled.as_text(), mesh.shape.get("model", 2))

        if not skip_probe:
            t0 = time.time()
            probe = costs_mod.probe_costs(cfg, shape, mesh)
            rec["t_probe_s"] = round(time.time() - t0, 2)
            mf = costs_mod.model_flops(cfg, shape)
            # cost_analysis 'flops' is per-device for SPMD partitioned HLO
            total_flops = probe.flops * n_chips
            total_bytes = probe.bytes * n_chips
            comp = total_flops / (n_chips * mesh_mod.PEAK_FLOPS_BF16)
            mem = total_bytes / (n_chips * mesh_mod.HBM_BW)
            coll = probe.coll_link / mesh_mod.ICI_BW
            dom = max((comp, "compute"), (mem, "memory"), (coll, "collective"))
            rec["probe"] = {
                "flops_per_device": probe.flops,
                "bytes_per_device": probe.bytes,
                "coll_payload_bytes_per_device": probe.coll_payload,
                "coll_link_bytes_per_device": probe.coll_link,
                "coll_counts": probe.coll_counts,
            }
            rec["roofline"] = {
                "chips": n_chips,
                "compute_s": comp, "memory_s": mem, "collective_s": coll,
                "dominant": dom[1],
                "model_flops": mf,
                "hlo_flops_total": total_flops,
                "useful_ratio": mf / total_flops if total_flops else 0.0,
            }
            print(f"[dryrun] {name}: compute={comp*1e3:.2f}ms "
                  f"memory={mem*1e3:.2f}ms coll={coll*1e3:.2f}ms "
                  f"dom={dom[1]} useful={rec['roofline']['useful_ratio']:.2f}")
        print(f"[dryrun] {name}: OK lower={t_lower:.1f}s compile={t_compile:.1f}s "
              f"mem/dev={rec['memory_analysis']['peak_bytes_per_device']/2**30:.2f}GiB")
    except Exception as e:  # noqa: BLE001 — record failures, keep sweeping
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[dryrun] {name}: ERROR {type(e).__name__}: {str(e)[:200]}")

    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def run_graphgen_cell(mesh_kind: str, out_dir: str, scale: str = "1t",
                      mode: str = "threefry"):
    """Dry-run the paper's chunked RMAT generator on the production mesh."""
    from repro.core.distributed_gen import build_generation_cell
    mesh = mesh_mod.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    tag = "" if mode == "threefry" else "__uniforms_hbm"
    name = f"graphgen__{scale}__{mesh_kind}{tag}"
    path = os.path.join(out_dir, name + ".json")
    os.makedirs(out_dir, exist_ok=True)
    rec = {"arch": "graphgen-rmat", "shape": scale, "mesh": mesh_kind,
           "mode": mode}
    try:
        cell = build_generation_cell(mesh, scale, mode=mode)
        with mesh:
            lowered = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                              out_shardings=cell.out_shardings).lower(*cell.args)
            compiled = lowered.compile()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        print(compiled.memory_analysis())
        colls = costs_mod.parse_collectives(compiled.as_text(),
                                            mesh.shape.get("model", 2))
        flops = float(ca.get("flops", 0.0))
        bts = float(ca.get("bytes accessed", 0.0))
        comp = flops / mesh_mod.PEAK_FLOPS_BF16
        mem = bts / mesh_mod.HBM_BW
        coll = colls["link_bytes"] / mesh_mod.ICI_BW
        rec.update(status="ok",
                   memory_analysis={
                       "argument_bytes": ma.argument_size_in_bytes,
                       "temp_bytes": ma.temp_size_in_bytes,
                       "output_bytes": ma.output_size_in_bytes},
                   cost_analysis={"flops": flops, "bytes_accessed": bts},
                   collectives=colls,
                   roofline={"chips": mesh.size, "compute_s": comp,
                             "memory_s": mem, "collective_s": coll,
                             "dominant": max((comp, "compute"), (mem, "memory"),
                                             (coll, "collective"))[1],
                             "edges": cell.meta["edges"],
                             "edges_per_s_roofline": cell.meta["edges"]
                             / max(comp, mem, coll) if max(comp, mem, coll) else 0})
        print(f"[dryrun] {name}: OK edges={cell.meta['edges']:.2e} "
              f"compute={comp*1e3:.2f}ms mem={mem*1e3:.2f}ms coll={coll*1e3:.3f}ms")
    except Exception as e:  # noqa: BLE001
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[dryrun] {name}: ERROR {str(e)[:200]}")
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--graphgen", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--skip-probe", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--remat-policy", default=None)
    ap.add_argument("--moe-path", default=None)
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--pad-vocab", type=int, default=None,
                    help="pad vocab up to a multiple of N (sharding fix)")
    ap.add_argument("--dp2d", action="store_true",
                    help="FSDP-2D: batch over both axes, ZeRO-3 weights")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--attn-scores-dtype", default=None)
    ap.add_argument("--gen-mode", default="threefry",
                    choices=["threefry", "hbm_uniforms"])
    args = ap.parse_args()

    overrides = {}
    if args.microbatches is not None:
        overrides["microbatches"] = args.microbatches
    if args.remat_policy is not None:
        overrides["remat_policy"] = args.remat_policy
    if args.moe_path is not None:
        overrides["moe_path"] = args.moe_path
    if args.attn_scores_dtype is not None:
        overrides["attn_scores_dtype"] = args.attn_scores_dtype
    if args.seq_shard:
        overrides["seq_shard"] = True
    if args.dp2d:
        overrides["dp2d"] = True
    if args.fsdp:
        overrides["fsdp"] = True
    if args.pad_vocab is not None:
        overrides["__pad_vocab__"] = args.pad_vocab

    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])

    if args.graphgen:
        for mk in meshes:
            run_graphgen_cell(mk, args.out, mode=args.gen_mode)
        return

    if args.all:
        for mk in meshes:
            for arch in ARCHS:
                for sh in LM_SHAPES:
                    run_cell(arch, sh.name, mk, args.out, overrides, args.tag,
                             skip_probe=(args.skip_probe or mk == "multi"))
        return

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    for mk in meshes:
        run_cell(args.arch, args.shape, mk, args.out, overrides, args.tag,
                 skip_probe=args.skip_probe)


if __name__ == "__main__":
    main()
