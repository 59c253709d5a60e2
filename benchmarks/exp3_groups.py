"""Experiment III (paper Fig. 6): accuracy vs number of groups d for the
MNIST stand-in, c_i=4 users per group. Claim under test: FedDCL accuracy
increases with d (more total data), tracking Centralized/DC.

`scenarios()` additionally sweeps the batched collaboration engine over a
scenario matrix — d ∈ {2..32} groups × c ∈ {1..8} users/group × IID vs
Dirichlet non-IID — timing protocol step 3 on the "host" (serial NumPy)
and "device" (batched jitted) backends and recording their agreement, so
the batched-engine speedup is measured, not asserted.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import run_all_methods


def run(fast: bool = False, engine: str = "host", cache: bool = False):
    """The d-grid rides the generic sweep loop (experiments/sweep.run_sweep)
    instead of an ad-hoc for-loop; engine="scan", cache=True additionally
    share compiled FL executables across the grid via the plan cache."""
    from experiments.sweep import run_sweep

    ds_grid = [1, 2, 4] if fast else [1, 2, 4, 6, 8, 10]

    def one_d(case):
        d = case["d"]
        methods = ["Centralized", "DC", "FedDCL"] if d == 1 else \
            ["Centralized", "FedAvg", "DC", "FedDCL"]
        res = run_all_methods(
            "mnist", d=max(d, 1), c=4, n_ij=100,
            rounds=4 if fast else 15, local_epochs=2 if fast else 4,
            epochs=8 if fast else 30, n_test=500 if fast else 1000,
            methods=methods, engine=engine, cache=cache)
        print(f"d={d}: " + "  ".join(f"{k}={v:.4f}"
                                     for k, v in res["metrics"].items()))
        return res["metrics"]

    rows = run_sweep([{"d": d} for d in ds_grid], one_d, label="exp3",
                     verbose=False)
    out = {r["d"]: {k: v for k, v in r.items() if k not in ("d", "time_s")}
           for r in rows}
    os.makedirs("results", exist_ok=True)
    with open("results/exp3_groups.json", "w") as f:
        json.dump(out, f, indent=1)
    feddcl = [out[d]["FedDCL"] for d in ds_grid]
    increasing = feddcl[-1] > feddcl[0]
    print(f"FedDCL acc d={ds_grid[0]} -> d={ds_grid[-1]}: "
          f"{feddcl[0]:.4f} -> {feddcl[-1]:.4f} (increasing={increasing})")
    return out


def scenarios(fast: bool = False, seed: int = 0):
    """Backend scenario matrix: setup (steps 1–3) wall time, host vs device,
    and the relative Frobenius disagreement of the collab representations."""
    from repro.core.protocol import run_protocol
    from repro.data.partition import split_dirichlet, split_iid

    d_grid = [2, 4, 8] if fast else [2, 4, 8, 16, 32]
    c_grid = [1, 4] if fast else [1, 2, 4, 8]
    parts = ["iid", "dirichlet"]
    m, m_tilde, n_ij, anchor_r = 32, 8, 50, 1000
    rng = np.random.default_rng(seed)
    rows = []
    for d in d_grid:
        for c in c_grid:
            n = d * c * n_ij
            X = rng.standard_normal((n + 64, m))
            Y = rng.integers(0, 5, size=n + 64).astype(np.float64)
            for part in parts:
                split = split_iid if part == "iid" else split_dirichlet
                Xs, Ys = split(X, Y, d, [c] * d, n_ij, seed=seed)
                res = {"d": d, "c": c, "partition": part}
                setups = {}
                for backend in ("host", "device"):
                    if backend == "device":   # absorb one-time jit compile
                        run_protocol(Xs, Ys, m_tilde=m_tilde,
                                     anchor_r=anchor_r, seed=seed,
                                     svd_backend=backend)
                    t0 = time.perf_counter()
                    setups[backend] = run_protocol(
                        Xs, Ys, m_tilde=m_tilde, anchor_r=anchor_r,
                        seed=seed, svd_backend=backend)
                    res[f"{backend}_s"] = time.perf_counter() - t0
                rel = max(
                    float(np.linalg.norm(a - b) / max(np.linalg.norm(a), 1e-12))
                    for a, b in zip(setups["host"].collab_X,
                                    setups["device"].collab_X))
                res["rel_frobenius"] = rel
                res["speedup"] = res["host_s"] / max(res["device_s"], 1e-12)
                rows.append(res)
                print(f"d={d:<3} c={c} {part:<9} host={res['host_s']:.3f}s "
                      f"device={res['device_s']:.3f}s "
                      f"speedup={res['speedup']:.2f}x rel={rel:.2e}")
    os.makedirs("results", exist_ok=True)
    with open("results/exp3_scenarios.json", "w") as f:
        json.dump(rows, f, indent=1)
    return rows


if __name__ == "__main__":
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    import sys
    if "--scenarios" in sys.argv:
        scenarios(fast="--fast" in sys.argv)
    else:
        run(fast="--fast" in sys.argv,
            engine="scan" if "--engine=scan" in sys.argv else "host",
            cache="--cache" in sys.argv)
