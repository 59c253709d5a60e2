"""Collaboration-serving benchmark: warm vs cold bucketed dispatch, and
incremental onboarding vs a from-scratch protocol recompute (DESIGN.md §10).

Measures, for a mixed multi-tenant request stream on a `ServeCollab`
server:

  * cold sweep — first traffic of each shape bucket (pays trace+compile),
  * warm sweep — the same traffic pattern re-submitted: the acceptance bar
    is EXACTLY 0 executable builds (CompileCounter across the sweep) and
    p50/p99 request latency + rows/s at steady state,
  * artifact hygiene — assert_no_baked_data on every group's lowered
    resident step (tenant tables are runtime arguments, never constants),
  * onboarding — admitting new users onto the LIVE server (blocked-Gram +
    cached-factor update, tables refreshed) timed against the full
    `run_protocol` recompute of the grown deployment on the same anchor;
    asserts agreement <= 1e-5 and an incremental speedup >= 5x.

  PYTHONPATH=src python benchmarks/serve_bench.py [--fast] [--out PATH]

Writes results/BENCH_serve.json (cited in DESIGN.md / ROADMAP.md).
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List

import jax
import numpy as np

from repro.analysis.hlo_audit import CompileCounter, assert_no_baked_data
from repro.core import protocol
from repro.models import mlp
from repro.serve_collab import ServeCollab

M_RAW = 20
M_TILDE = 16
ONBOARD_SPEEDUP_BAR = 5.0
ONBOARD_AGREE_BAR = 1e-5


def _make_data(rng, d: int, c: int, n_ij: int):
    Xs = [[rng.standard_normal((n_ij, M_RAW)) for _ in range(c)]
          for _ in range(d)]
    Ys = [[rng.standard_normal((n_ij, 1)) for _ in range(c)] for _ in range(d)]
    return Xs, Ys


def _sweep(srv, rng, d: int, c: int, n_req: int, max_rows: int):
    """Submit a mixed-tenant stream and drain it; returns (dt, stats)."""
    for _ in range(n_req):
        g = int(rng.integers(0, d))
        u = int(rng.integers(0, c))
        srv.submit(rng.standard_normal(
            (int(rng.integers(1, max_rows + 1)), M_RAW)), g, u)
    t0 = time.perf_counter()
    out = srv.serve()
    dt = time.perf_counter() - t0
    assert all(s == "done" for s in out.status.values())
    return dt, srv.stats()


def _setup_agreement(inc, ref) -> float:
    """Max relative difference between an incrementally-grown setup and a
    from-scratch reference over Z, every G, every X̂."""
    worst = 0.0

    def rel(a, b):
        a, b = np.asarray(a), np.asarray(b)
        return float(np.abs(a - b).max() / max(1.0, np.abs(b).max()))

    worst = max(worst, rel(inc.Z, ref.Z))
    for i in range(ref.num_groups):
        for j in range(ref.num_users(i)):
            worst = max(worst, rel(inc.Gs[i][j], ref.Gs[i][j]))
        worst = max(worst, rel(inc.collab_X[i], ref.collab_X[i]))
    return worst


def run(fast: bool = False) -> Dict:
    # layout sized so the speedup claim is honest: the incremental path's
    # floor is the shared central refresh (Z + all-group G re-solve), so
    # tiny layouts where THAT dominates both sides can't separate them —
    # at these sizes the from-scratch per-user step-2/3 work (mapping SVDs,
    # full Grams, full QRs) dominates the recompute and the gap is real
    d, c = (4, 10) if fast else (5, 10)
    n_ij = 120 if fast else 200
    n_req = 24 if fast else 96
    max_rows = 24 if fast else 48
    anchor_r = 1024 if fast else 2048
    n_onboard = 2 if fast else 3
    rng = np.random.default_rng(0)

    Xs, Ys = _make_data(rng, d, c, n_ij)
    t0 = time.perf_counter()
    setup = protocol.run_protocol(Xs, Ys, m_tilde=M_TILDE, anchor_r=anchor_r,
                                  seed=0, onboard=True)
    t_setup = time.perf_counter() - t0
    params = mlp.init_mlp_params(jax.random.PRNGKey(0), setup.m_hat,
                                 (32,), 1)
    srv = ServeCollab.from_setup(setup, params, max_batch=64)

    # -- cold then warm sweep (identical traffic distribution) ------------
    # identical traffic both times (same stream seed): the cold pass pays
    # every bucket's trace+compile, the warm replay is pure steady state —
    # tail-batch pow2 buckets are traffic-dependent, so a different stream
    # could legitimately compile a fresh (unseen) tail width
    with CompileCounter() as cc_cold:
        t_cold, st_cold = _sweep(srv, np.random.default_rng(1), d, c, n_req,
                                 max_rows)
    srv.latencies.clear()
    with CompileCounter() as cc_warm:
        t_warm, st = _sweep(srv, np.random.default_rng(1), d, c, n_req,
                            max_rows)
    warm_rows = st["rows_served"] - st_cold["rows_served"]
    assert cc_warm.count == 0, \
        f"warm mixed-tenant sweep built {cc_warm.count} executables"

    # -- artifact hygiene: no tenant data baked into any group's step -----
    for g in range(setup.num_groups):
        assert_no_baked_data(srv.lower_step(g, 64))

    # -- onboarding: live incremental admit vs full protocol recompute ----
    grown_X = [list(row) for row in Xs]
    grown_Y = [list(row) for row in Ys]
    t_onboards: List[float] = []
    for k in range(n_onboard):
        Xn = rng.standard_normal((n_ij, M_RAW))
        Yn = rng.standard_normal((n_ij, 1))
        tgt = k % d
        t0 = time.perf_counter()
        srv.onboard_user(tgt, Xn, Yn)           # incl. table refresh
        t_onboards.append(time.perf_counter() - t0)
        grown_X[tgt].append(Xn)
        grown_Y[tgt].append(Yn)
    t_onboard = min(t_onboards)

    t_recompute = float("inf")
    ref = None
    for _ in range(3):
        t0 = time.perf_counter()
        ref = protocol.run_protocol(grown_X, grown_Y, m_tilde=M_TILDE,
                                    anchor_r=anchor_r, seed=0,
                                    anchor=setup.anchor)
        t_recompute = min(t_recompute, time.perf_counter() - t0)

    agree = _setup_agreement(setup, ref)
    speedup = t_recompute / t_onboard
    assert agree <= ONBOARD_AGREE_BAR, \
        f"onboarded setup drifted {agree:.2e} from full recompute"
    assert speedup >= ONBOARD_SPEEDUP_BAR, \
        f"incremental onboarding only {speedup:.1f}x cheaper than recompute"

    return {
        "layout": {"groups": d, "users_per_group": c, "n_ij": n_ij,
                   "m_raw": M_RAW, "m_tilde": M_TILDE, "anchor_r": anchor_r},
        "traffic": {"requests_per_sweep": n_req, "max_rows": max_rows,
                    "max_batch": 64},
        "t_setup_s": round(t_setup, 4),
        "serve": {
            "t_cold_s": round(t_cold, 4),
            "t_warm_s": round(t_warm, 4),
            "compiles_cold": cc_cold.count,
            "compiles_warm": cc_warm.count,
            "rows_per_s_warm": round(warm_rows / t_warm, 1),
            "p50_latency_ms": round(st["p50_latency_s"] * 1e3, 3),
            "p99_latency_ms": round(st["p99_latency_s"] * 1e3, 3),
            "buckets": st["buckets"],
            "cache": st["cache"],
        },
        "onboard": {
            "n_onboarded": n_onboard,
            "t_incremental_s": round(t_onboard, 5),
            "t_full_recompute_s": round(t_recompute, 4),
            "speedup": round(speedup, 1),
            "agreement_max_rel": agree,
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="small layout + fewer requests (CI)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    result = run(fast=args.fast)
    result["fast"] = args.fast
    out_path = args.out or os.path.join(
        os.path.dirname(__file__), "..", "results", "BENCH_serve.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    s, o = result["serve"], result["onboard"]
    print(f"warm sweep: {s['t_warm_s']}s ({s['rows_per_s_warm']} rows/s), "
          f"compiles cold->warm {s['compiles_cold']}->{s['compiles_warm']}")
    print(f"latency p50 {s['p50_latency_ms']}ms / p99 {s['p99_latency_ms']}ms")
    print(f"onboard: {o['t_incremental_s']}s incremental vs "
          f"{o['t_full_recompute_s']}s recompute = {o['speedup']}x, "
          f"agreement {o['agreement_max_rel']:.2e}")
    print(f"wrote {os.path.abspath(out_path)}")


if __name__ == "__main__":
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    main()
