import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first (before any jax import): jax locks the
device count on first init, and the production meshes need 512 host devices.

Usage:
    python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k --mesh single
    python -m repro.launch.dryrun --arch ... --shape ... --mesh multi --block

Writes one JSON per cell to experiments/dryrun/.  ``--block`` additionally
lowers the standalone layer-block for the roofline's scan-body scaling
(DESIGN.md §5).  Run cells in separate processes (see run_all_dryruns.py) to
bound compiler memory.
"""
import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs import SHAPE_BY_NAME, cell_is_runnable, get_config
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def run_cell(arch: str, shape_name: str, mesh_kind: str, block: bool = False,
             attn_impl: str = "xla", overrides: dict = None) -> dict:
    import dataclasses

    from repro.launch import specs as specs_lib

    cfg = get_config(arch)
    if overrides:
        typed = {}
        for k, v in overrides.items():
            cur = getattr(cfg, k)
            typed[k] = type(cur)(v) if cur is not None else v
        cfg = dataclasses.replace(cfg, **typed)
    shape = SHAPE_BY_NAME[shape_name]
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
              "block": block, "status": "skipped"}
    if not cell_is_runnable(arch, shape):
        result["reason"] = ("long_500k requires sub-quadratic attention; "
                            f"{arch} is pure full-attention (DESIGN.md §6)")
        return result

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    jax.set_mesh(mesh)
    if block:
        cell = specs_lib.build_block_cell(cfg, shape, mesh, attn_impl=attn_impl)
    else:
        cell = specs_lib.build_cell(cfg, shape, mesh, attn_impl=attn_impl)

    t0 = time.time()
    jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                     out_shardings=cell.out_shardings)
    lowered = jitted.lower(*cell.args)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    n_devices = len(mesh.devices.flatten())
    coll = hlo_analysis.analyze_collectives(hlo, default_group=n_devices)

    result.update({
        "status": "ok",
        "overrides": overrides or {},
        "kind": cell.static["kind"],
        "n_devices": n_devices,
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "flops_per_device": float(ca.get("flops", -1.0)),
        "bytes_accessed_per_device": float(ca.get("bytes accessed", -1.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
        "peak_memory_bytes": getattr(ma, "peak_memory_in_bytes", None),
        "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
        "output_bytes": getattr(ma, "output_size_in_bytes", None),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
        "wire_bytes_per_device": coll["wire_bytes_per_device"],
        "collective_op_counts": coll["op_counts"],
        "loop_trip_counts": coll["loops"],
        "hlo_size": len(hlo),
        "n_repeats": cfg.n_repeats,
    })
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--block", action="store_true",
                    help="lower one layer-block (roofline scan-body scaling)")
    ap.add_argument("--attn-impl", default="xla")
    ap.add_argument("--out-dir", default=str(OUT_DIR))
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (e.g. shard_strategy=pure_dp)")
    ap.add_argument("--tag", default="", help="variant suffix for the output file")
    args = ap.parse_args()

    out_dir = pathlib.Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = f"{args.arch}__{args.shape}__{args.mesh}" + ("__block" if args.block else "")
    if args.tag:
        tag += f"__{args.tag}"
    out_path = out_dir / f"{tag}.json"
    overrides = dict(kv.split("=", 1) for kv in args.set)

    try:
        result = run_cell(args.arch, args.shape, args.mesh, block=args.block,
                          attn_impl=args.attn_impl, overrides=overrides)
    except Exception as e:  # record failures as data, not crashes
        result = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
                  "block": args.block, "status": "error",
                  "overrides": overrides,
                  "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-4000:]}
    out_path.write_text(json.dumps(result, indent=2))
    status = result["status"]
    extra = ""
    if status == "ok":
        extra = (f" compile={result['compile_s']}s"
                 f" flops/dev={result['flops_per_device']:.3e}"
                 f" peak={result['peak_memory_bytes']}")
    elif status == "error":
        extra = " " + result["error"][:200]
    print(f"[dryrun] {tag}: {status}{extra}")


if __name__ == "__main__":
    main()
