"""Sweep driver: many FedDCL configs through ONE plan cache.

This is the canonical loop for sweep / many-tenant traffic (it replaces the
ad-hoc per-benchmark loops that previously lived only as untracked
prototypes — see ROADMAP "compiled-plan cache" item): every config runs the
full pipeline via the public ``FedDCL.fit()`` API with the shared plan
cache, so configs whose padded shapes land in the same bucket reuse one
compiled executable and the 2nd–Nth calls cost milliseconds.

Two committed artifacts (regenerate with this script):

  results/BENCH_sweep.json      cold pass vs warm pass over the 6-config
                                sweep; executables (= cache misses) strictly
                                fewer than configs
  results/BENCH_api_cache.json  one config's fit() called N times: first
                                call pays trace+compile, the rest hit

The script ASSERTS the cache invariants (fewer executables than configs,
warm speedup floor), so CI running ``--fast`` fails on a cache regression
instead of waiting for someone to re-run a benchmark by hand.

  PYTHONPATH=src:. python experiments/sweep.py [--fast] [--out-dir results]

XLA executables persist across processes in the compilation cache
(`JAX_COMPILATION_CACHE_DIR`, else `.jax_cache/` in the checkout; see
repro.api.enable_persistent_compilation_cache).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, "src"))

import numpy as np


def run_sweep(cases: List[Dict], run_case: Callable[[Dict], Dict], *,
              label: str = "sweep", out_path: Optional[str] = None,
              verbose: bool = True) -> List[Dict]:
    """Generic timed config-grid loop: run `run_case` on each case dict,
    recording wall time per case. Returns rows = case ∪ result ∪ {time_s};
    writes them as JSON when out_path is given. Benchmarks (exp3_groups)
    and the FedDCL sweep below share this loop instead of each rolling
    their own."""
    rows = []
    for case in cases:
        t0 = time.perf_counter()
        res = run_case(case)
        dt = time.perf_counter() - t0
        row = {**case, **(res or {}), "time_s": round(dt, 4)}
        rows.append(row)
        if verbose:
            desc = " ".join(f"{k}={v}" for k, v in case.items())
            print(f"[{label}] {desc}  ({dt:.3f}s)")
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(rows, f, indent=1)
        if verbose:
            print(f"[{label}] -> {out_path}")
    return rows


# --------------------------------------------------------------------------
# The FedDCL 6-config sweep (BENCH_sweep) + api-cache bench (BENCH_api_cache)
# --------------------------------------------------------------------------

M_FEAT = 16          # raw feature dim m
M_TILDE = 8          # intermediate dim m̃ = m̂
ANCHOR_R = 512


def _make_groups(d: int, c: int, n_ij: int, seed: int = 0):
    """Synthetic (Xs, Ys) in the protocol layout: group i, user j."""
    r = np.random.default_rng(seed)
    w = r.standard_normal((M_FEAT, 1))
    Xs, Ys = [], []
    for i in range(d):
        gx, gy = [], []
        for j in range(c):
            X = r.standard_normal((n_ij, M_FEAT))
            gx.append(X)
            gy.append(X @ w + 0.05 * r.standard_normal((n_ij, 1)))
        Xs.append(gx)
        Ys.append(gy)
    return Xs, Ys


def sweep_configs(fast: bool = False) -> List[Dict]:
    """Six tenant configs spanning three shape buckets — two configs per
    (silo-bucket, batch-bucket) pair, so the cache must land 3 executables
    and 3 hits on the cold pass (and 6 hits warm)."""
    if fast:
        return [dict(d=2, c=2, n_ij=40, seed=0), dict(d=2, c=2, n_ij=34, seed=1),
                dict(d=3, c=2, n_ij=40, seed=2), dict(d=4, c=2, n_ij=34, seed=3)]
    return [dict(d=2, c=2, n_ij=60, seed=0), dict(d=2, c=2, n_ij=50, seed=1),
            dict(d=3, c=2, n_ij=60, seed=2), dict(d=4, c=2, n_ij=50, seed=3),
            dict(d=6, c=2, n_ij=50, seed=4), dict(d=8, c=2, n_ij=40, seed=5)]


def _fit_case(case: Dict, rounds: int, local_epochs: int) -> Dict:
    from repro.api import FedDCL

    Xs, Ys = _make_groups(case["d"], case["c"], case["n_ij"], case["seed"])
    model = FedDCL(m_tilde=M_TILDE, anchor_r=ANCHOR_R, rounds=rounds,
                   local_epochs=local_epochs, seed=case["seed"])
    t0 = time.perf_counter()
    _, res = model.fit(Xs, Ys)
    fit_s = time.perf_counter() - t0
    return {"fit_s": round(fit_s, 4), "hit": res.cache_stats["hit"],
            "final_loss": res.history[-1]["loss"],
            "score": model.score(Xs[0][0], Ys[0][0])}


def bench_sweep(fast: bool = False) -> Dict:
    from repro.core.federated import default_plan_cache

    rounds, epochs = (4, 2) if fast else (15, 4)
    cases = sweep_configs(fast)
    cache = default_plan_cache()
    cache.clear()

    cold = run_sweep(cases, lambda c: _fit_case(c, rounds, epochs),
                     label="sweep:cold")
    cold_stats = cache.stats()
    warm = run_sweep(cases, lambda c: _fit_case(c, rounds, epochs),
                     label="sweep:warm")
    warm_stats = cache.stats()

    t_cold = sum(r["fit_s"] for r in cold)
    t_warm = sum(r["fit_s"] for r in warm)
    out = {
        "bench": "feddcl_api_sweep",
        "configs": len(cases),
        "rounds": rounds, "local_epochs": epochs,
        "executables": cold_stats["misses"],
        "cold_pass": cold, "warm_pass": warm,
        "t_cold_total_s": round(t_cold, 4),
        "t_warm_total_s": round(t_warm, 4),
        "speedup": round(t_cold / max(t_warm, 1e-9), 1),
        "cache_cold": cold_stats, "cache_warm": warm_stats,
    }
    # cache invariants — a regression here should fail CI, not linger in an
    # unregenerated benchmark artifact
    assert cold_stats["misses"] < len(cases), \
        f"bucketing broken: {cold_stats['misses']} executables for {len(cases)} configs"
    assert all(r["hit"] for r in warm), "warm pass missed the plan cache"
    floor = 3.0 if fast else 20.0
    assert out["speedup"] >= floor, \
        f"warm sweep only {out['speedup']}x over cold (floor {floor}x)"
    print(f"[sweep] {len(cases)} configs -> {out['executables']} executables; "
          f"cold {t_cold:.2f}s warm {t_warm:.3f}s ({out['speedup']}x)")
    return out


def bench_api_cache(fast: bool = False) -> Dict:
    """One shape bucket, N fresh fit() calls: call 1 pays trace+compile,
    calls 2..N cost milliseconds — the sklearn-API amortization claim."""
    from repro.core.federated import default_plan_cache

    rounds, epochs = (4, 2) if fast else (15, 4)
    n_calls = 4 if fast else 6
    default_plan_cache().clear()
    calls = []
    for k in range(n_calls):
        case = dict(d=3, c=2, n_ij=50 + 2 * k, seed=k)   # same bucket, new tenant
        calls.append({**case, **_fit_case(case, rounds, epochs)})
        print(f"[api-cache] call {k}: {calls[-1]['fit_s']:.4f}s "
              f"hit={calls[-1]['hit']}")
    t_first = calls[0]["fit_s"]
    t_rest = [c["fit_s"] for c in calls[1:]]
    out = {
        "bench": "feddcl_api_cache",
        "calls": calls,
        "t_first_s": round(t_first, 4),
        "t_warm_mean_s": round(float(np.mean(t_rest)), 4),
        "speedup": round(t_first / max(float(np.mean(t_rest)), 1e-9), 1),
        "cache": default_plan_cache().stats(),
    }
    assert not calls[0]["hit"] and all(c["hit"] for c in calls[1:]), \
        "api-cache: expected exactly one miss then all hits"
    floor = 3.0 if fast else 20.0
    assert out["speedup"] >= floor, \
        f"warm fit() only {out['speedup']}x over cold (floor {floor}x)"
    print(f"[api-cache] first {t_first:.3f}s, warm mean "
          f"{out['t_warm_mean_s']*1000:.1f}ms ({out['speedup']}x)")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="CI smoke grid")
    ap.add_argument("--out-dir", default="results")
    args = ap.parse_args()

    from repro.api import enable_persistent_compilation_cache
    print("[sweep] persistent XLA compilation cache: "
          f"{enable_persistent_compilation_cache()}")

    import jax
    meta = {"platform": jax.default_backend(), "jax": jax.__version__,
            "fast": args.fast}
    os.makedirs(args.out_dir, exist_ok=True)
    for name, bench in (("BENCH_sweep", bench_sweep),
                        ("BENCH_api_cache", bench_api_cache)):
        out = {**meta, **bench(fast=args.fast)}
        path = os.path.join(args.out_dir, f"{name}.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
        print(f"-> {path}")


if __name__ == "__main__":
    main()
