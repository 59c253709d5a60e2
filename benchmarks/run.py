"""Benchmark entry point — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (fast variants by default; pass
--full for the paper-scale runs recorded in EXPERIMENTS.md)."""
from __future__ import annotations

import argparse
import sys
import time


def _timed(name, fn, *a, **kw):
    t0 = time.perf_counter()
    out = fn(*a, **kw)
    us = (time.perf_counter() - t0) * 1e6
    return name, us, out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale runs (minutes on CPU)")
    ap.add_argument("--only", default=None,
                    choices=["exp1", "exp2", "exp3", "comm", "kernels", "noniid"])
    args = ap.parse_args()
    fast = not args.full
    rows = []

    if args.only in (None, "kernels"):
        from benchmarks import kernels_bench
        for name, us, derived in kernels_bench.run(fast=fast):
            rows.append((name, us, derived))

    if args.only in (None, "exp1"):
        from benchmarks import exp1_convergence
        name, us, (res, claims) = _timed("exp1_convergence(fig4)",
                                         exp1_convergence.run, fast=fast)
        rows.append((name, us, f"claims_pass={all(claims.values())}"))

    if args.only in (None, "exp2"):
        from benchmarks import exp2_datasets
        name, us, res = _timed("exp2_datasets(fig5)", exp2_datasets.run,
                               fast=fast)
        ok = all(r["metrics"]["FedDCL"] < r["metrics"]["Local"]
                 if r["task"] == "regression"
                 else r["metrics"]["FedDCL"] > r["metrics"]["Local"]
                 for r in res.values())
        rows.append((name, us, f"feddcl_beats_local_all={ok}"))

    if args.only in (None, "exp3"):
        from benchmarks import exp3_groups
        name, us, out = _timed("exp3_groups(fig6)", exp3_groups.run, fast=fast)
        ds = sorted(out)
        rows.append((name, us,
                     f"feddcl_d{ds[0]}={out[ds[0]]['FedDCL']:.3f};"
                     f"d{ds[-1]}={out[ds[-1]]['FedDCL']:.3f}"))

    if args.only == "noniid":
        from benchmarks import ablation_noniid
        name, us, out = _timed("ablation_noniid(beyond-paper)",
                               ablation_noniid.run, fast=fast)
        rows.append((name, us,
                     f"feddcl_iid={out['iid']['FedDCL']:.3f};"
                     f"dir0.1={out['dir0.1']['FedDCL']:.3f}"))

    if args.only in (None, "comm"):
        from benchmarks import comm_cost
        name, us, (rows_c, table) = _timed("comm_cost(sec3.2)", comm_cost.run,
                                           fast=fast)
        red = rows_c["fedavg_user_bytes_total"] / max(
            rows_c["feddcl_user_bytes_total"], 1)
        rows.append((name, us, f"user_traffic_reduction={red:.1f}x"))

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    main()
