"""Readings that set a cell's correctness limits: the program's numbers
and the control's, on many seeds, in one process.

    python chipbench/control.py --workload <name> --seeds 1,2,3 \\
        --seconds <s> [--out <file.jsonl>]

For each seed the cell is built and run for a short window exactly as
``run.py`` does; then the numbers compared are read as the benchmark
reads them, and again with each control the driver names
(``controls()``) in the timed path's place: the reference in the
nearest lower precision and any other the cell offers. One JSON line
per seed. The benchmark's own runs never run a control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    from chipbench import bench
    from chipbench.run import check_device
    from chipbench.spans import Recorder

    cell = bench.load_cell(ROOT, args.workload)
    check_device(cell.chips)
    drv_mod = bench.driver(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        drv = drv_mod.setup(cell.config, cell.traffic, seed, Recorder())
        drv.window(args.seconds)
        t1 = time.perf_counter()
        program = drv.check()
        t2 = time.perf_counter()
        controls = {f"control_{c}": drv.check(control=c)
                    for c in drv.controls()}
        attempted, failed = drv.attempted()
        line = json.dumps({"workload": cell.name, "seed": seed,
                           "program": program, **controls,
                           "attempted": attempted, "failed": failed,
                           "run_s": t1 - t0, "check_s": t2 - t1,
                           "control_s": time.perf_counter() - t2})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as out:
                out.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
