"""Federated-engine benchmark: the compiled scan engine vs the per-batch
dispatch host loop (core/federated.py, DESIGN.md §5) across silo counts and
round budgets — the FL-phase analogue of kernels_bench's batched-Gram row.

For each (d, rounds) case both engines train the same MLP on the same
ragged silo stack with the same seed/schedule; we record host dispatch time
(marginal cost of the FL rounds with the per-call step jit cancelled out),
host total time (one call incl. its unavoidable re-jit), scan cold time
(trace + compile + run: what a one-shot caller pays), scan warm time (the
compiled FL phase re-invoked), and the host/scan parameter agreement.
Speedup_warm = host dispatch / scan warm (steady state); speedup_cold =
host total / scan cold (one-shot).

  PYTHONPATH=src python benchmarks/fed_bench.py [--fast] [--out PATH]
  PYTHONPATH=src python benchmarks/fed_bench.py --sharded [--fast]

Writes results/BENCH_fed.json (cited in DESIGN.md / ROADMAP.md).

--sharded runs on VIRTUAL CPU devices only: its parent never initializes a
jax backend and starts one worker per device count with JAX_PLATFORMS=cpu
and --xla_force_host_platform_device_count, so on a machine with a chip no
worker contends for it. The on-chip sharded-vs-unsharded comparison is
`python chip_smoke.py --four-chips`.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import federated
from repro.core.federated import (make_scan_runner, pad_silo_data,
                                  run_federated)
from repro.models import mlp
from repro.optim import adamw

M_FEAT = 16
LOCAL_EPOCHS = 4
BATCH = 32


def _make_silos(d: int, seed: int = 0):
    """d ragged silos (84..116 samples) of a linear-regression task."""
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((M_FEAT, 1))
    silos = []
    for i in range(d):
        n = 84 + 8 * (i % 5)
        r = np.random.default_rng(seed * 1009 + i)
        X = r.standard_normal((n, M_FEAT))
        silos.append((X, X @ w + 0.01 * r.standard_normal((n, 1))))
    return silos


def _rel_diff(a, b) -> float:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return max(
        float(np.max(np.abs(np.asarray(x) - np.asarray(y))) /
              (np.max(np.abs(np.asarray(x))) + 1e-12))
        for x, y in zip(la, lb))


def bench_case(d: int, rounds: int, *, warm_iters: int = 3) -> Dict:
    silos = _make_silos(d)
    params = mlp.init_mlp_params(jax.random.PRNGKey(0), M_FEAT, (32,), 1)
    loss = lambda p, x, y: mlp.mlp_per_example_loss(p, x, y, "regression")
    kw = dict(opt=adamw(1e-3), rounds=rounds, local_epochs=LOCAL_EPOCHS,
              batch_size=BATCH, seed=0)

    # The host engine re-jits its step closure on every run_federated call
    # (jit caches key on function identity), so a single wall-clock includes
    # one unavoidable trace+compile. Report both: t_host_total (what one
    # call costs) and t_host_dispatch = t(3R) − t(R) over 2R rounds, where
    # the compile cancels and only marginal per-batch dispatch remains —
    # the steady-state number speedup_warm is computed from. Each leg is
    # best-of-3 because compile-time jitter (~±0.3 s) would otherwise swamp
    # the small-R dispatch signal.
    def _host_time(r):
        best, res = float("inf"), None
        for _ in range(3):
            t0 = time.perf_counter()
            out = run_federated(loss, params, silos, engine="host",
                                **{**kw, "rounds": r})
            dt = time.perf_counter() - t0
            if dt < best:
                best, res = dt, out
        return best, res

    t_host_total, host = _host_time(rounds)
    t_3r, _ = _host_time(3 * rounds)
    t_host = max((t_3r - t_host_total) / 2.0, 1e-4)

    t0 = time.perf_counter()
    scan = run_federated(loss, params, silos, engine="scan", **kw)
    t_cold = time.perf_counter() - t0

    # warm: the SAME compiled runner re-invoked (executable cache hit)
    padded = pad_silo_data(silos, BATCH)
    batch_loss = federated._make_batch_loss(loss, True, 0.0)
    runner = make_scan_runner(batch_loss, padded, opt=adamw(1e-3),
                              rounds=rounds, local_epochs=LOCAL_EPOCHS, seed=0)
    jax.block_until_ready(runner(params))                 # compile
    t_warm = float("inf")
    for _ in range(warm_iters):
        t0 = time.perf_counter()
        jax.block_until_ready(runner(params))
        t_warm = min(t_warm, time.perf_counter() - t0)

    dispatches = d * rounds * LOCAL_EPOCHS * padded.num_batches
    return {
        "d": d, "rounds": rounds, "local_epochs": LOCAL_EPOCHS,
        "batch_size": BATCH, "host_step_dispatches": dispatches,
        "t_host_dispatch_s": round(t_host, 4),
        "t_host_total_s": round(t_host_total, 4),
        "t_scan_cold_s": round(t_cold, 4),
        "t_scan_warm_s": round(t_warm, 4),
        "speedup_warm": round(t_host / t_warm, 1),
        "speedup_cold": round(t_host_total / t_cold, 1),
        "rel_param_diff": _rel_diff(host.params, scan.params),
        "final_loss_host": host.history[-1]["loss"],
        "final_loss_scan": scan.history[-1]["loss"],
    }


# collective counting lives in repro.analysis.hlo_audit (DESIGN.md §9) —
# the one census implementation shared with the tests and feddcl_audit
from repro.analysis import collective_census as _collective_histogram  # noqa: E402,E501


def bench_sharded_case(d: int, rounds: int, *, warm_iters: int = 3,
                       aggregator: str = "fedavg") -> Dict:
    """One worker-process case: vmap (unsharded) plan vs the same plan
    sharded over a mesh spanning every available device, plus the
    round-boundary collective-structure check on the sharded HLO. The
    expected structure is aggregator-aware (DESIGN.md §8): weighted
    aggregators psum partial weighted sums; robust aggregators all_gather
    the silo submissions and reduce only the loss scalar."""
    from repro.launch.mesh import make_host_mesh

    silos = _make_silos(d)
    params = mlp.init_mlp_params(jax.random.PRNGKey(0), M_FEAT, (32,), 1)
    loss = lambda p, x, y: mlp.mlp_per_example_loss(p, x, y, "regression")
    batch_loss = federated._make_batch_loss(loss, True, 0.0)
    padded = pad_silo_data(silos, BATCH)
    args = federated._plan_args(padded, 0, rounds)
    devices = jax.device_count()

    def plan_for(mesh):
        return federated.make_fl_plan(
            num_silos=padded.num_silos, num_batches=padded.num_batches,
            batch_size=padded.batch_size, opt=adamw(1e-3),
            batch_loss=batch_loss, rounds=rounds, local_epochs=LOCAL_EPOCHS,
            aggregator=aggregator, masked=padded.has_padding, mesh=mesh)

    def warm_time(plan):
        out = jax.block_until_ready(plan(params, *args))     # compile
        t = float("inf")
        for _ in range(warm_iters):
            t0 = time.perf_counter()
            jax.block_until_ready(plan(params, *args))
            t = min(t, time.perf_counter() - t0)
        return t, out

    base = plan_for(None)
    t_vmap, (p_vmap, _) = warm_time(base)

    mesh = make_host_mesh(model=1)                  # ("data", "model")=(n, 1)
    sharded = plan_for(mesh)
    t_sharded, (p_sharded, _) = warm_time(sharded)
    hlo = sharded.lower(params, *args).compile().as_text()
    hist = _collective_histogram(hlo)
    n_leaves = len(jax.tree_util.tree_leaves(params))

    return {
        "devices": devices, "d": d, "rounds": rounds,
        "aggregator": aggregator,
        "local_epochs": LOCAL_EPOCHS, "batch_size": BATCH,
        "t_vmap_warm_s": round(t_vmap, 4),
        "t_sharded_warm_s": round(t_sharded, 4),
        "speedup_sharded": round(t_vmap / t_sharded, 2),
        "rel_param_diff": _rel_diff(p_vmap, p_sharded),
        "collectives": hist,
        "param_leaves": n_leaves,
    }


def run_sharded_parent(fast: bool, out_path: str) -> None:
    """Spawn one CPU subprocess per virtual-device count (XLA_FLAGS must be
    set before jax initializes, hence processes, not threads), collect rows,
    assert the sharded-engine invariants, write BENCH_fed_sharded.json.
    This process stays off every jax backend."""
    import subprocess
    import sys
    import tempfile

    base_cases = [(8, 5)] if fast else [(8, 5), (32, 5), (8, 20), (32, 20)]
    cases = [(d, r, "fedavg") for d, r in base_cases]
    # robust-boundary rows: the collective structure changes (all_gather
    # instead of psum), so each robust aggregator gets its own asserted row
    robust = ("median",) if fast else ("median", "trimmed_mean", "krum")
    cases += [(8, 5, agg) for agg in robust]
    rows: List[Dict] = []
    for devices in (1, 8):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}")
        for d, rounds, agg in cases:
            with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
                tmp = f.name
            subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--sharded-worker", "--d", str(d), "--rounds", str(rounds),
                 "--aggregator", agg, "--out", tmp],
                env=env, check=True)
            with open(tmp) as f:
                row = json.load(f)
            os.unlink(tmp)
            rows.append(row)
            print(f"devices={devices} d={d:3d} rounds={rounds:3d} "
                  f"{agg:12s}  "
                  f"vmap {row['t_vmap_warm_s']:7.4f}s  "
                  f"sharded {row['t_sharded_warm_s']:7.4f}s  "
                  f"({row['speedup_sharded']:.2f}x)  "
                  f"agree {row['rel_param_diff']:.2e}  "
                  f"collectives {row['collectives']}")

    for row in rows:
        # Short-horizon rows get the acceptance tolerance. Long-horizon
        # (rounds=20) timing rows only a sanity bound: the sharded psum of
        # per-shard partial sums and the unsharded single tensordot sum in
        # different f32 orders, and adam amplifies that ~1e-7/round seed
        # chaotically over many rounds (observed non-monotonic ~1e-3 at 10
        # rounds, ~6e-4 at 20 — both trajectories converge to the same
        # optimum).
        tol = 1e-5 if row["rounds"] <= 5 else 1e-2
        assert row["rel_param_diff"] <= tol, row
        if row["devices"] > 1:
            if row["aggregator"] in federated.ROBUST_AGGREGATORS:
                # robust boundary: one all-gather per param leaf plus one
                # for the availability mask; the only all-reduce is the
                # per-round loss scalar (the robust statistic itself is
                # computed redundantly per shard on the gathered stack)
                assert row["collectives"] == {
                    "all-reduce": 1,
                    "all-gather": row["param_leaves"] + 1}, row
            else:
                # weighted boundary: round-boundary-only traffic — exactly
                # one all-reduce per param leaf plus one for the loss, per
                # hierarchy level (single-level host mesh here), and no
                # other collective kind anywhere in the module
                assert set(row["collectives"]) == {"all-reduce"}, row
                assert row["collectives"]["all-reduce"] == \
                    row["param_leaves"] + 1, row

    out = {
        "bench": "fed_engine_sharded_vs_vmap",
        "platform": "cpu",
        "jax": jax.__version__,
        "invariants": {
            "agreement_tol": "1e-5 at rounds<=5; 1e-2 sanity bound on the "
                             "rounds=20 timing rows (f32 reduction-order "
                             "seed amplified chaotically by adam)",
            "collectives": "weighted aggregators: all-reduce only, "
                           "(param_leaves + 1) per hierarchy level in the "
                           "round-scan body — round boundaries only, local "
                           "phase clean; robust aggregators: "
                           "(param_leaves + 1) all-gathers (params + "
                           "availability mask) + 1 loss all-reduce",
        },
        "cases": rows,
    }
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"-> {out_path}")


def run(fast: bool = False) -> List[Dict]:
    cases = ([(2, 5), (8, 5)] if fast
             else [(d, r) for d in (2, 8, 32) for r in (5, 20)])
    rows = []
    for d, rounds in cases:
        row = bench_case(d, rounds)
        rows.append(row)
        print(f"d={d:3d} rounds={rounds:3d}  host {row['t_host_dispatch_s']:8.3f}s "
              f"dispatch ({row['host_step_dispatches']} steps, "
              f"{row['t_host_total_s']:.3f}s incl. jit)  "
              f"scan cold {row['t_scan_cold_s']:7.3f}s  "
              f"warm {row['t_scan_warm_s']:7.4f}s  "
              f"speedup {row['speedup_warm']:6.1f}x (cold "
              f"{row['speedup_cold']:.1f}x)  "
              f"agree {row['rel_param_diff']:.2e}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: d<=8, rounds=5 only")
    ap.add_argument("--out", default=None)
    ap.add_argument("--sharded", action="store_true",
                    help="sharded-vs-vmap rows at 1 and 8 virtual devices "
                         "(spawns worker subprocesses; writes "
                         "results/BENCH_fed_sharded.json)")
    ap.add_argument("--sharded-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--d", type=int, default=8, help=argparse.SUPPRESS)
    ap.add_argument("--rounds", type=int, default=5, help=argparse.SUPPRESS)
    ap.add_argument("--aggregator", default="fedavg", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.sharded_worker:
        row = bench_sharded_case(args.d, args.rounds,
                                 aggregator=args.aggregator)
        with open(args.out, "w") as f:
            json.dump(row, f)
        return
    if args.sharded:
        run_sharded_parent(args.fast,
                           args.out or "results/BENCH_fed_sharded.json")
        return

    args.out = args.out or "results/BENCH_fed.json"
    rows = run(fast=args.fast)
    out = {
        "bench": "fed_engine_scan_vs_host",
        "platform": jax.default_backend(),
        "jax": jax.__version__,
        "cases": rows,
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"-> {args.out}")


if __name__ == "__main__":
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    main()
