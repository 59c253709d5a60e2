#!/usr/bin/env python
"""feddcl_audit — compiled-artifact smoke audit (repro.analysis.hlo_audit;
DESIGN.md §9): lower a tiny FL plan in EVERY flavor and assert

  1. no baked tenant data: the StableHLO holds no large non-splat
     constant (the PR 3 artifact-level privacy leak), for
     {vmap, sharded} × {weighted, robust} × {whole-phase, chunked};
  2. collective census: unsharded plans contain ZERO collectives; sharded
     weighted plans exactly {all-reduce: leaves+1} per hierarchy level;
     sharded robust plans {all-reduce: 1, all-gather: leaves+1};
  3. the positive control: a deliberately closure-baked plan (data
     captured instead of passed) FAILS the audit — the check can actually
     see the leak it guards against;
  4. CompileCounter: a second identical plan invocation performs zero
     backend compilations.

  PYTHONPATH=src python scripts/feddcl_audit.py [--devices N] [--json]

Exit status: 0 all invariants hold, 1 otherwise. Run by the CI `lint`
job next to scripts/feddcl_lint.py.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual CPU devices to force (default 8, so the "
                         "sharded flavors really shard; must be set before "
                         "jax initializes)")
    ap.add_argument("--min-elems", type=int, default=512,
                    help="baked-constant threshold in elements")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if "jax" not in sys.modules and args.devices > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.devices}").strip()
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir, "src"))

    import jax
    import numpy as np

    from repro.analysis.hlo_audit import (BakedDataError, CompileCounter,
                                          assert_no_baked_data,
                                          collective_census)
    from repro.api import enable_persistent_compilation_cache
    from repro.core import federated
    from repro.core.federated import lower_fl_plan, make_fl_plan, pad_silo_data
    from repro.launch.mesh import make_host_mesh
    from repro.models import mlp
    from repro.optim import adamw

    enable_persistent_compilation_cache()
    # sized so every padded tensor (and the closure-captured control slice)
    # clears --min-elems: 3 silos (bucketed to 4) x 7 batches (to 8) x 8 x 16
    # features
    rng = np.random.default_rng(0)
    feat = 16
    w_true = rng.standard_normal((feat, 1))
    silos = []
    for n in (56, 49, 52):
        X = rng.standard_normal((n, feat))
        silos.append((X, X @ w_true + 0.01 * rng.standard_normal((n, 1))))
    params = mlp.init_mlp_params(jax.random.PRNGKey(0), feat, (8,), 1)
    loss = lambda p, x, y: mlp.mlp_per_example_loss(p, x, y, "regression")
    batch_loss = federated._make_batch_loss(loss, True, 0.0)
    leaves = len(jax.tree_util.tree_leaves(params))
    mesh = make_host_mesh(model=1) if jax.device_count() > 1 else None
    shards = federated.num_silo_shards(mesh) if mesh is not None else 1

    report = {"devices": jax.device_count(), "flavors": [], "ok": True}

    def check(name, *, mesh, aggregator, collect):
        padded = pad_silo_data(silos, 8,
                               shards=shards if mesh is not None else 1)
        plan = make_fl_plan(
            num_silos=padded.num_silos, num_batches=padded.num_batches,
            batch_size=padded.batch_size, opt=adamw(1e-2),
            batch_loss=batch_loss, rounds=2, local_epochs=2,
            aggregator=aggregator, collect=collect, mesh=mesh)
        lowered = lower_fl_plan(plan, params, padded, rounds=2)
        assert_no_baked_data(lowered, min_elems=args.min_elems)
        census = collective_census(lowered)
        row = {"flavor": name, "baked": 0, "collectives": census}
        if mesh is None:
            assert census == {}, (
                f"{name}: unsharded plan must hold no collective, "
                f"got {census}")
        elif aggregator in federated.ROBUST_AGGREGATORS:
            assert census == {"all-reduce": 1, "all-gather": leaves + 1}, (
                name, census)
        else:
            assert census == {"all-reduce": leaves + 1}, (name, census)
        report["flavors"].append(row)
        return plan, padded

    # flavor matrix: {vmap, sharded} × {weighted, robust} × {phase, chunk}
    plan, padded = check("vmap/fedavg/whole", mesh=None,
                         aggregator="fedavg", collect="none")
    check("vmap/median/whole", mesh=None, aggregator="median",
          collect="none")
    check("vmap/fedavg/chunk", mesh=None, aggregator="fedavg",
          collect="chunk")
    if mesh is not None:
        check("sharded/fedavg/whole", mesh=mesh, aggregator="fedavg",
              collect="none")
        check("sharded/trimmed_mean/whole", mesh=mesh,
              aggregator="trimmed_mean", collect="none")
        check("sharded/fedavg/chunk", mesh=mesh, aggregator="fedavg",
              collect="chunk")

    # positive control: a closure-baked "plan" must FAIL the audit
    import jax.numpy as jnp
    baked_X = jnp.asarray(padded.X)                     # captured, not passed
    # feddcl-lint: disable=R004  deliberate: this IS the leak the control verifies the audit can see
    leaky = jax.jit(lambda p: batch_loss(
        p, baked_X[0], jnp.asarray(padded.Y)[0],
        jnp.asarray(padded.w)[0], p))
    try:
        assert_no_baked_data(leaky.lower(params),
                             min_elems=args.min_elems)
    except BakedDataError:
        report["positive_control"] = "caught"
    else:
        report["ok"] = False
        report["positive_control"] = "MISSED"
        raise SystemExit(
            "closure-baked control passed the audit — assert_no_baked_data "
            "cannot see the leak it guards against")

    # recompile sentinel: an identical second invocation compiles nothing
    fl_args = federated._plan_args(padded, 0, 2)
    jax.block_until_ready(plan(params, *fl_args))        # compile once
    with CompileCounter() as cc:
        jax.block_until_ready(plan(params, *fl_args))
    report["warm_recompiles"] = cc.count
    assert cc.count == 0, f"warm plan invocation compiled {cc.count} modules"

    if args.json:
        print(json.dumps(report, indent=1))
    else:
        for row in report["flavors"]:
            print(f"AUDIT_OK {row['flavor']:28s} baked=0 "
                  f"collectives={row['collectives']}")
        print(f"POSITIVE_CONTROL {report['positive_control']}")
        print(f"WARM_RECOMPILES {report['warm_recompiles']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
