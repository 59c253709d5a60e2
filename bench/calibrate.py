"""Readings the correctness limits are set from, for one cell, in one
process (set-up is paid once per seed, compiles once).

    python3 bench/calibrate.py --workload mnist_d5c4.fl --seeds 1,2,3 \
        [--controls 1,2,3] [--seconds 2] [--out chiprun_out/cal.jsonl]

For every seed in ``--seeds``: the cell's set-up and a short window at the
cell's own load, then each number the cell compares, program against the
reference (the sound readings; their maximum is a limit's lower reading).
For every seed in ``--controls`` also the control (the reference computed
at the next precision down, in the program's place) and each fault the
driver can plant in the reference put in the program's place, against the
reference (a limit's upper reading is the least of these that qualifies).
One JSON line per seed and kind.
"""
from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--controls", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import os
    (ROOT / ".jax_cache").mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from bench import common
    from bench.run import check_device
    from repro.api import enable_persistent_compilation_cache

    spec, cell, cfg, traffic, limits = common.load_cell(args.workload, ROOT)
    check_device(cell["chips"])
    enable_persistent_compilation_cache()
    mod = importlib.import_module(f"bench.drivers.{traffic['kind']}")
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.controls.split(",") if s]
    out = open(args.out, "a") if args.out else None
    for seed in dict.fromkeys(seeds + controls):
        t0 = time.perf_counter()
        drv = mod.Driver(cfg, traffic, seed)
        drv.setup()
        drv.window(args.seconds, None)
        drv.release()
        rows = []
        if seed in seeds:
            nums = {k: v["value"] for k, v in drv.check(limits).items()}
            rows.append({"kind": "program", **nums,
                         **getattr(drv, "each", {})})
        if seed in controls:
            for kind, nums in drv.controls().items():
                rows.append({"kind": kind, **nums})
        for r in rows:
            line = json.dumps({"workload": args.workload, "seed": seed,
                               "seconds": time.perf_counter() - t0, **r})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
