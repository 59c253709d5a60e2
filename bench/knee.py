"""Find the serving knee once: the highest offered rate at which the
backlog does not grow over a window.

    python3 bench/knee.py --workload mnist_d5c4.serve --seed 5 \
        --rates 500,1000,2000 --seconds 10 [--out chiprun_out/knee.jsonl]

One process sets the cell up once, then runs one window per rate (the
cell's traffic with ``rate_per_s`` replaced) and prints, per rate: the
95th and 50th percentile latency, queued rows at half the window and at
its close, how late the generator ran, and failed requests. The chosen
rate (about 0.8 of the knee) is written into the cell's traffic file by
hand.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    (ROOT / ".jax_cache").mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from bench import common
    from bench.run import check_device
    from repro.api import enable_persistent_compilation_cache

    spec, cell, cfg, traffic, limits = common.load_cell(args.workload, ROOT)
    check_device(cell["chips"])
    enable_persistent_compilation_cache()
    mod = importlib.import_module(f"bench.drivers.{traffic['kind']}")
    drv = mod.Driver(cfg, traffic, args.seed)
    drv.setup()
    out = open(args.out, "a") if args.out else None
    for rate in [float(r) for r in args.rates.split(",")]:
        drv.traffic = dict(traffic, rate_per_s=rate)
        w = drv.window(args.seconds, None)
        row = {"rate_per_s": rate, "p95_ms": w["end_to_end"]["serve_p95_ms"],
               **{k: w[k] for k in ("p50_ms", "backlog_mid_rows",
                                    "backlog_close_rows", "late_p50_ms",
                                    "late_max_ms", "requests", "steps",
                                    "rows_served", "step_s", "drain_s",
                                    "loop_stall_max_ms", "gc_max_ms",
                                    "gc_total_ms")},
               "failed": drv.failed}
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
