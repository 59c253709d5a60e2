"""Quickstart: the full FedDCL protocol (Algorithm 1) on a BatterySmall-like
synthetic regression task — 4 user institutions in 2 groups, exactly the
paper's Experiment I layout. Runs in ~10 s on CPU.

  PYTHONPATH=src python examples/quickstart.py
  FEDDCL_BACKEND=device PYTHONPATH=src python examples/quickstart.py

FEDDCL_BACKEND selects the step-3 collaboration backend: "host" (serial
NumPy float64, default) or "device" (batched jitted Gram+eigh and QR —
DESIGN.md §3). FEDDCL_ENGINE selects the step-4 federated engine: "host"
(per-batch dispatch reference) or "scan" (the whole FL phase as one
compiled lax.scan program — DESIGN.md §4).
"""
import os

import numpy as np

from repro.configs.feddcl_mlp import PAPER_MLPS
from repro.core import protocol
from repro.core.federated import run_federated
from repro.data.partition import split_iid
from repro.data.tabular import make_dataset, train_test_split
from repro.models import mlp
from repro.optim import adamw

import jax
import jax.numpy as jnp


def main():
    # ---- data: paper Exp I — d=2 groups, c_i=2 users, n_ij=100 ----------
    cfg = PAPER_MLPS["battery_small"]
    ds = make_dataset("battery_small", n=1500, seed=0)
    (Xtr, Ytr), (Xte, Yte) = train_test_split(ds, 400, 1000, seed=0)
    Xs, Ys = split_iid(Xtr, Ytr, d=2, c=[2, 2], n_ij=100, seed=0)

    # ---- FedDCL steps 1-3: anchor, private maps, SVD alignment ----------
    backend = os.environ.get("FEDDCL_BACKEND", "host")
    setup = protocol.run_protocol(Xs, Ys, m_tilde=cfg.reduced_dim,
                                  anchor_r=2000, seed=0,
                                  svd_backend=backend)
    print(f"collab backend: {backend} | anchor:", setup.anchor.shape,
          "| collab reps per group:", [x.shape for x in setup.collab_X])

    # ---- FedDCL step 4: FedAvg between the intra-group DC servers -------
    # per-example loss lets the engine zero-pad + mask ragged silos;
    # FEDDCL_ENGINE=scan compiles all 20 rounds into ONE device dispatch
    params = mlp.for_config(jax.random.PRNGKey(0), cfg, reduced=True)
    loss = lambda p, x, y: mlp.mlp_per_example_loss(p, x, y, cfg.task)
    engine = os.environ.get("FEDDCL_ENGINE", "host")
    res = run_federated(
        loss, params, setup.fed_silos(),
        opt=adamw(1e-3), rounds=20, local_epochs=4, batch_size=32,
        engine=engine)

    # ---- step 5: per-user integrated model t(X) = h(f(X) G) -------------
    h = lambda Z: mlp.mlp_forward(res.params, jnp.asarray(Z))
    models = protocol.finalize_user_models(setup, h)
    t00 = models[0][0]
    pred = np.asarray(t00(Xte))
    rmse = float(np.sqrt(np.mean((pred - Yte) ** 2)))
    print(f"FedDCL test RMSE: {rmse:.4f}")

    # ---- the paper's headline communication property --------------------
    trips = setup.comm.user_round_trips()
    print("cross-institution communications per user:", trips)
    assert all(v == 2 for v in trips.values()), \
        "exactly 2 per user: one upload (step 4) + one download (step 15)"
    print("== exactly 2 per user, as the paper claims (Algorithm 1)")


if __name__ == "__main__":
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    main()
