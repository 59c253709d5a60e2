"""Experiment I (paper Fig. 4, Tables 1–2): proof-of-concept on the
BatterySmall stand-in — 4 users in 2 groups, convergence per round of all
five methods. Claim under test: FedDCL converges at least as fast per round
as FedAvg and reaches comparable final RMSE.

`--engine` selects the federated trainer (core/federated.py): "host" is the
per-batch-dispatch reference loop, "scan" compiles the whole FL phase into
one program — same schedule, same results, far fewer dispatches
(benchmarks/fed_bench.py measures the gap).
"""
from __future__ import annotations

import argparse
import json
import os

from benchmarks.common import run_all_methods


def run(fast: bool = False, engine: str = "host", svd_backend: str = "host"):
    res = run_all_methods(
        "battery_small", d=2, c=2, n_ij=100,
        rounds=6 if fast else 20, local_epochs=4,
        epochs=12 if fast else 40, n_test=1000, track_rounds=True,
        engine=engine, svd_backend=svd_backend)
    os.makedirs("results", exist_ok=True)
    with open("results/exp1_convergence.json", "w") as f:
        json.dump(res, f, indent=1)
    m = res["metrics"]
    print(f"Exp I — BatterySmall RMSE (lower better), engine={engine}:")
    for k, v in m.items():
        print(f"  {k:12s} {v:.4f}")
    claims = {
        "feddcl_beats_local": m["FedDCL"] < m["Local"],
        "feddcl_comparable_fedavg": m["FedDCL"] < 1.5 * m["FedAvg"],
        "feddcl_comparable_dc": m["FedDCL"] < 1.5 * m["DC"],
    }
    print("claims:", claims)
    return res, claims


if __name__ == "__main__":
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--engine", default="host", choices=["host", "scan"])
    ap.add_argument("--svd-backend", default="host",
                    choices=["host", "device"])
    args = ap.parse_args()
    run(fast=args.fast, engine=args.engine, svd_backend=args.svd_backend)
