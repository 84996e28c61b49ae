"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes, print memory/cost analysis, extract roofline terms.

The XLA_FLAGS assignment below MUST precede every other import (jax
locks the device count at first init) — but a docstring is not an
import, so it stays first. Do not import this module from tests — they
should see 1 device.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi
    PYTHONPATH=src python -m repro.launch.dryrun --skip-existing

Per-cell JSON artifacts land in results/dryrun/.
"""
import os

# ra: allow[RA103] the 512-device override is this module's whole point
# and precedes the jax import below; only __main__ execution reaches it
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, cells, get_arch
from repro.launch import hlo as hlo_lib
from repro.launch import jaxpr_cost as jc_lib
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_production_mesh
from repro.sharding import act, specs

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "results", "dryrun")


def cell_shardings(cell, mesh, params_sds, opt_sds):
    """(in_shardings, out_shardings) trees matching the cell fn."""
    p_sh = specs.param_shardings(params_sds, mesh)
    rep = NamedSharding(mesh, P())
    if cell.kind == "train":
        o_sh = {"m": p_sh, "v": p_sh, "step": rep}
        if "ef_residual" in (opt_sds or {}):
            o_sh["ef_residual"] = p_sh
        b_sh = specs.data_shardings(cell.inputs, mesh)
        return (p_sh, o_sh, b_sh), (p_sh, o_sh, None)
    if cell.kind == "prefill":
        b_sh = specs.data_shardings(cell.inputs, mesh)
        c_sds = jax.eval_shape(cell.fn, params_sds, cell.inputs)[1]
        c_sh = specs.cache_shardings(
            c_sds, mesh, cell.shp.global_batch)
        return (p_sh, b_sh), (None, c_sh)
    # decode
    B = cell.shp.global_batch
    c_sh = specs.cache_shardings(cell.inputs["cache"], mesh, B)
    t_sh = specs.data_shardings(
        {"token": cell.inputs["token"], "pos": cell.inputs["pos"]}, mesh)
    out_logits = None
    return ((p_sh, c_sh, t_sh["token"], t_sh["pos"]),
            (out_logits, c_sh))


def run_cell(arch: str, shape: str, multi_pod: bool,
             out_dir: str = RESULTS, verbose: bool = True,
             save: bool = True, cfg_override=None):
    cfg = cfg_override or get_arch(arch)
    shp = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    mesh_name = "multi" if multi_pod else "single"
    tag = f"{arch}_{shape}_{mesh_name}"
    t0 = time.time()

    cell = steps_lib.build_cell(cfg, shp)
    params_sds, opt_sds = steps_lib.abstract_state(cfg, cell.kind, cell.tc)
    in_sh, out_sh = cell_shardings(cell, mesh, params_sds, opt_sds)

    with mesh, act.use_mesh(mesh):
        if cell.kind == "train":
            fn = jax.jit(cell.fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=cell.donate)
            lowered = fn.lower(params_sds, opt_sds, cell.inputs)
        elif cell.kind == "prefill":
            fn = jax.jit(cell.fn, in_shardings=in_sh, out_shardings=out_sh)
            lowered = fn.lower(params_sds, cell.inputs)
        else:
            fn = jax.jit(cell.fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=cell.donate)
            lowered = fn.lower(params_sds, cell.inputs["cache"],
                               cell.inputs["token"], cell.inputs["pos"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    text = compiled.as_text()
    coll = hlo_lib.collective_bytes(text)
    # exact FLOP/byte totals from the traced jaxpr (XLA's cost_analysis
    # counts scan bodies once — see launch/jaxpr_cost.py); global / chips
    with mesh, act.use_mesh(mesh):
        if cell.kind == "train":
            jc = jc_lib.jaxpr_cost(cell.fn, params_sds, opt_sds, cell.inputs)
        elif cell.kind == "prefill":
            jc = jc_lib.jaxpr_cost(cell.fn, params_sds, cell.inputs)
        else:
            jc = jc_lib.jaxpr_cost(cell.fn, params_sds,
                                   cell.inputs["cache"],
                                   cell.inputs["token"], cell.inputs["pos"])
    cost_corrected = {"flops": jc["flops"] / n_dev,
                      "bytes accessed": jc["bytes"] / n_dev}
    mf_total = hlo_lib.model_flops(cfg, shp)
    roof = hlo_lib.roofline_terms(cost_corrected, coll, mf_total / n_dev)

    mem_d = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            mem_d[k] = int(v)
    # live bytes per device: args + temps (aliased args don't double count)
    live = (mem_d.get("argument_size_in_bytes", 0)
            + mem_d.get("temp_size_in_bytes", 0)
            - mem_d.get("alias_size_in_bytes", 0)
            + mem_d.get("output_size_in_bytes", 0))
    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "devices": int(n_dev), "kind": cell.kind,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem_d, "live_bytes_per_device": int(live),
        "cost": cost_corrected,
        "xla_cost_raw": {k: float(v) for k, v in cost.items()
                         if isinstance(v, (int, float))},
        "collectives": {k: int(v) for k, v in coll.items()},
        "roofline": roof.to_dict(),
        "model_flops_total": mf_total,
    }
    if verbose:
        print(f"[dryrun] {tag}: lower {t_lower:.1f}s compile "
              f"{t_compile:.1f}s  live/dev {live/2**30:.2f} GiB  "
              f"flops/dev {roof.flops:.3e}  dominant {roof.dominant} "
              f"({roof.bound_s*1e3:.2f} ms)")
        print(f"  memory_analysis: {mem_d}")
        print(f"  cost_analysis: flops={roof.flops:.4g} "
              f"bytes={roof.hbm_bytes:.4g} coll={roof.coll_bytes:.4g}")
    if save:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default=RESULTS)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    todo = [(a, s) for (a, s) in cells(args.arch)
            if args.shape is None or s == args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = []
    for arch, shape in todo:
        for mp in meshes:
            tag = f"{arch}_{shape}_{'multi' if mp else 'single'}"
            path = os.path.join(args.out, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[dryrun] {tag}: cached, skipping")
                continue
            try:
                run_cell(arch, shape, mp, out_dir=args.out)
            except Exception as e:                       # noqa: BLE001
                failures.append((tag, repr(e)))
                print(f"[dryrun] {tag}: FAILED {e!r}")
                traceback.print_exc()
    print(f"\n[dryrun] done; {len(failures)} failures")
    for tag, err in failures:
        print(f"  FAIL {tag}: {err[:200]}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
