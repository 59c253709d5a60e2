"""Experiment II (paper Fig. 5, Table 3): all six datasets × five methods,
d=5 groups × c=4 users (paper layout). Claim under test: FedDCL ≫ Local and
comparable to FedAvg / DC on every dataset."""
from __future__ import annotations

import json
import os

from benchmarks.common import run_all_methods

DATASETS = ["battery_small", "credit_rating", "eicu", "human_activity",
            "mnist", "fashion_mnist"]


def run(fast: bool = False, datasets=None):
    datasets = datasets or (DATASETS[:3] if fast else DATASETS)
    all_res = {}
    for name in datasets:
        n_ij = 1000 if name == "fashion_mnist" and not fast else 100
        res = run_all_methods(
            name, d=5, c=4, n_ij=n_ij,
            rounds=5 if fast else 20, local_epochs=2 if fast else 4,
            epochs=10 if fast else 40,
            n_test=500 if fast else 1000)
        all_res[name] = res
        m = res["metrics"]
        unit = "RMSE" if res["task"] == "regression" else "acc"
        print(f"{name:16s} ({unit}): " + "  ".join(
            f"{k}={v:.4f}" for k, v in m.items()))
    os.makedirs("results", exist_ok=True)
    with open("results/exp2_datasets.json", "w") as f:
        json.dump({k: {"metrics": v["metrics"], "task": v["task"]}
                   for k, v in all_res.items()}, f, indent=1)
    return all_res


if __name__ == "__main__":
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    run()
