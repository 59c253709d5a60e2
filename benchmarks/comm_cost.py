"""Communication-cost accounting (the paper's §3.2 claim and the systems
point of the whole method): per-user cross-institution round trips and bytes,
FedDCL vs FedAvg, plus the mesh-level per-step collective amortization
(cross-silo bytes / H) read from the dry-run JSONs when present."""
from __future__ import annotations

import glob
import json
import os

import numpy as np

from repro.configs.feddcl_mlp import PAPER_MLPS
from repro.core import protocol
from repro.data.partition import split_iid
from repro.data.tabular import make_dataset, train_test_split
from repro.models import mlp

import jax


def protocol_comm(dataset: str = "mnist", d: int = 5, c: int = 4,
                  n_ij: int = 100, rounds: int = 20):
    cfg = PAPER_MLPS[dataset]
    ds = make_dataset(dataset, n=d * c * n_ij + 100, seed=0)
    (Xtr, Ytr), _ = train_test_split(ds, d * c * n_ij, 64, seed=0)
    Xs, Ys = split_iid(Xtr, Ytr, d, [c] * d, n_ij, seed=0)
    setup = protocol.run_protocol(Xs, Ys, m_tilde=cfg.reduced_dim, seed=0)
    params = mlp.for_config(jax.random.PRNGKey(0), cfg, reduced=True)
    pbytes = sum(np.prod(l.shape) * 4 for l in jax.tree_util.tree_leaves(params))
    protocol.finalize_user_models(setup, h=lambda z: z,
                                  h_params_bytes=int(pbytes))

    trips = setup.comm.user_round_trips()
    user_bytes = setup.comm.total_bytes(
        lambda e: e.src.startswith("user") or e.dst.startswith("user"))
    # FedAvg: every user exchanges model params twice per round
    fedavg_user_msgs = 2 * rounds
    fedavg_user_bytes = int(2 * rounds * pbytes * d * c)
    feddcl_server_bytes = setup.comm.total_bytes(
        lambda e: not (e.src.startswith("user") or e.dst.startswith("user")))
    # DC-server <-> FL-server federated phase (rounds × params × d × 2)
    feddcl_server_bytes += int(2 * rounds * pbytes * d)

    rows = {
        "users": d * c,
        "feddcl_msgs_per_user": max(trips.values()),
        "fedavg_msgs_per_user": fedavg_user_msgs,
        "feddcl_user_bytes_total": user_bytes,
        "fedavg_user_bytes_total": fedavg_user_bytes,
        "feddcl_server_bytes_total": int(feddcl_server_bytes),
        "model_bytes": int(pbytes),
    }
    return rows


def mesh_amortization(result_dir: str = "results/dryrun", H: int = 4):
    """Per-step cross-silo collective bytes: baseline vs feddcl local+sync/H."""
    out = {}
    for f in glob.glob(os.path.join(result_dir, "*__train_4k__16x16__*.json")):
        rec = json.load(open(f))
        key = (rec["arch"], rec["mode"])
        out[key] = rec["collective_bytes_per_device"]
    rows = {}
    for (arch, mode), v in sorted(out.items()):
        rows.setdefault(arch, {})[mode] = v
    table = []
    for arch, modes in rows.items():
        if "feddcl" in modes and "feddcl_sync" in modes and "baseline" in modes:
            amort = modes["feddcl"] + modes["feddcl_sync"] / H
            table.append({
                "arch": arch,
                "baseline_coll_bytes_per_step": modes["baseline"],
                "feddcl_amortized_coll_bytes_per_step": amort,
                "reduction_x": modes["baseline"] / max(amort, 1.0),
            })
    return table


def run(fast: bool = False):
    rows = protocol_comm()
    print("Protocol communication (mnist stand-in, d=5, c=4, 20 FL rounds):")
    for k, v in rows.items():
        print(f"  {k:32s} {v:,}")
    ratio = rows["fedavg_user_bytes_total"] / max(rows["feddcl_user_bytes_total"], 1)
    print(f"  user-traffic reduction vs FedAvg: {ratio:.1f}x, "
          f"msgs {rows['fedavg_msgs_per_user']} -> {rows['feddcl_msgs_per_user']}")
    table = mesh_amortization()
    if table:
        print("\nMesh-level per-step cross-silo bytes (dry-run):")
        for r in table:
            print(f"  {r['arch']:24s} baseline={r['baseline_coll_bytes_per_step']:.3e} "
                  f"feddcl(H=4)={r['feddcl_amortized_coll_bytes_per_step']:.3e} "
                  f"({r['reduction_x']:.2f}x)")
    os.makedirs("results", exist_ok=True)
    with open("results/comm_cost.json", "w") as f:
        json.dump({"protocol": rows, "mesh": table}, f, indent=1)
    return rows, table


if __name__ == "__main__":
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    run()
