import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes; record memory/cost/roofline artifacts.

  PYTHONPATH=src python -m repro.launch.dryrun                 # all 40 x 2
  PYTHONPATH=src python -m repro.launch.dryrun --arch dit-l2 --shape gen_1024
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi

The XLA_FLAGS line above MUST stay the first statement: jax locks the device
count at first init, and only the dry-run wants 512 placeholder devices.

Cost extraction: XLA's HloCostAnalysis counts while-loop bodies ONCE, so a
scanned model under-reports per-step FLOPs/collectives by ~n_layers x. Each
cell is compiled 1 + n_loop_tags times with one tagged loop's unroll bumped
per compile; the deltas solve exactly for each loop body's cost (see
repro.common.flags). The memory roofline term is analytic
(repro.roofline.memtraffic) because CPU-backend 'bytes accessed' reflects
unfused execution — both the XLA and analytic numbers are recorded.
"""

import argparse
import json
import time
import traceback

import jax

from repro import configs as C
from repro.common import flags
from repro.launch import steps as S
from repro.launch.mesh import make_production_mesh
from repro.roofline.analysis import Roofline, collective_bytes
from repro.roofline.hw import V5E
from repro.roofline.memtraffic import cell_memory
from repro.roofline.model_flops import cell_model_flops


def _compile_once(cell_builder, mesh, unroll_map):
    # Rebuild the cell each time: jax caches traces on function identity, so
    # reusing one step_fn closure would ignore the unroll-flag change.
    flags.LAYER_UNROLL = dict(unroll_map)
    flags.UNROLL_SMALL = True
    try:
        cell = cell_builder()
        in_sh = cell.in_shardings(mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(cell.step_fn, in_shardings=in_sh,
                              donate_argnums=cell.donate
                              ).lower(*cell.abstract_args)
            compiled = lowered.compile()
        ca = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text())
        return {
            "flops": float(ca.get("flops", 0.0)),
            "xla_bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll,
            "compiled": compiled,
        }
    finally:
        flags.LAYER_UNROLL = {}
        flags.UNROLL_SMALL = False


def _solve_totals(base, tag_runs, chains):
    """Linear trip-count solve; returns corrected totals for every metric."""
    metrics = ["flops", "xla_bytes"]
    coll_keys = set(base["coll"]) | {k for r in tag_runs.values()
                                     for k in r["run"]["coll"]}

    def get(run, m):
        if m in metrics:
            return run[m]
        return run["coll"].get(m, 0.0)

    out = {}
    for m in metrics + sorted(coll_keys):
        total = get(base, m)
        for chain in chains:
            # deltas outer->inner
            Ds = []
            for tag, trip in chain:
                u2 = flags.smallest_unroll(trip)
                d = (get(tag_runs[tag]["run"], m) - get(base, m)) / (u2 - 1)
                Ds.append(max(d, 0.0))
            Ds.append(0.0)
            mult = 1.0
            for i, (tag, trip) in enumerate(chain):
                body = max(Ds[i] - Ds[i + 1], 0.0)
                mult *= trip
                total += (mult - 1.0) * body
        out[m] = total
    return out


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             verbose: bool = True, kv_dtype: str | None = None) -> dict:
    arch = C.get(arch_id)
    if kv_dtype:
        import dataclasses
        arch = dataclasses.replace(
            arch, config=dataclasses.replace(arch.config,
                                             kv_cache_dtype=kv_dtype))
    shape = arch.shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rec = {"arch": arch_id, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips}
    t0 = time.time()
    try:
        builder = lambda: S.build_cell(arch, shape, mesh)  # noqa: E731
        cell = builder()
        base = _compile_once(builder, mesh, {})
        tag_runs = {}
        for chain in cell.loops:
            for tag, trip in chain:
                if tag in tag_runs:
                    continue
                u2 = flags.smallest_unroll(trip)
                tag_runs[tag] = {"u2": u2,
                                 "run": _compile_once(builder, mesh,
                                                      {tag: u2})}
        solved = _solve_totals(base, tag_runs,
                               cell.loops) if cell.loops else {
            "flops": base["flops"], "xla_bytes": base["xla_bytes"],
            **base["coll"]}

        # --- roofline terms -------------------------------------------
        coll_total = solved.get("total", 0.0)
        mem = cell_memory(cell.config, shape, arch.train, chips,
                          param_shards=_param_shards(cell, mesh))
        rl = Roofline(solved["flops"], mem["traffic"]["total"] / chips,
                      coll_total, chips)
        rec.update(rl.as_dict())
        rec["collectives"] = {k: v for k, v in solved.items()
                              if k not in ("flops", "xla_bytes")}
        rec["xla_bytes_per_device_unfused"] = solved["xla_bytes"]
        rec["mem_traffic"] = mem["traffic"]
        rec["mem_capacity"] = mem["capacity"]
        rec["fits_hbm_analytic"] = bool(
            mem["capacity"]["total"] <= V5E.hbm_bytes)
        rec["hbm_frac_analytic"] = mem["capacity"]["total"] / V5E.hbm_bytes

        ma = base["compiled"].memory_analysis()
        rec["xla_memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        }

        mf = cell_model_flops(cell.config, shape)
        rec["model_flops"] = mf["model_flops"]
        hlo_total = solved["flops"] * chips
        rec["useful_flops_frac"] = mf["model_flops"] / hlo_total \
            if hlo_total else 0.0
        if shape.steps:
            rec["sampler_steps"] = shape.steps
        rec["n_compiles"] = 1 + len(tag_runs)
        rec["t_total_s"] = round(time.time() - t0, 1)
        rec["ok"] = True
        if verbose:
            print(f"[ok] {arch_id:17s} {shape_name:11s} {rec['mesh']:7s} "
                  f"comp={rec['t_compute_s']:.2e} mem={rec['t_memory_s']:.2e} "
                  f"coll={rec['t_collective_s']:.2e} dom={rec['dominant']:10s} "
                  f"hbm={rec['hbm_frac_analytic']*100:5.1f}% "
                  f"useful={rec['useful_flops_frac']*100:5.1f}% "
                  f"({rec['n_compiles']} compiles, {rec['t_total_s']}s)",
                  flush=True)
    except Exception as e:  # noqa: BLE001 — record and continue
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        rec["t_total_s"] = round(time.time() - t0, 1)
        if verbose:
            print(f"[FAIL] {arch_id} {shape_name} {rec['mesh']}: "
                  f"{rec['error']}", flush=True)
    return rec


def _param_shards(cell, mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model = sizes.get("model", 1)
    emb = cell.rules.as_dict().get("embed")
    if emb:  # FSDP over data(+pod) in addition to model TP
        n = model
        for ax in emb:
            n *= sizes.get(ax, 1)
        return n
    return model


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--kv-dtype", default=None, choices=[None, "int8",
                                                         "bfloat16"])
    args = ap.parse_args()

    archs = C.ARCH_IDS if args.arch == "all" else [args.arch]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    n_fail = 0
    for aid in archs:
        arch = C.get(aid)
        shapes = [s.name for s in arch.shapes] if args.shape == "all" \
            else [args.shape]
        for sname in shapes:
            for mp in meshes:
                rec = run_cell(aid, sname, mp, kv_dtype=args.kv_dtype)
                tag = f"{aid}__{sname}__{'multi' if mp else 'single'}"
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1, default=float)
                n_fail += 0 if rec["ok"] else 1
    print(f"dry-run complete; failures: {n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
