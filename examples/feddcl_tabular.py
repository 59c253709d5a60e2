"""Five-method comparison on one tabular dataset (paper Experiment II, one
column of Fig. 5): Centralized / Local / FedAvg / DC / FedDCL.

  PYTHONPATH=src python examples/feddcl_tabular.py --dataset human_activity
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.feddcl_mlp import PAPER_MLPS
from repro.core import baselines, protocol
from repro.core.federated import run_federated
from repro.data.partition import split_iid
from repro.data.tabular import make_dataset, train_test_split
from repro.models import mlp
from repro.optim import adamw


def evaluate(params, X, Y, task):
    return mlp.mlp_metric(params, jnp.asarray(X), jnp.asarray(Y), task)


def run(dataset: str, d: int = 5, c: int = 4, n_ij: int = 100, seed: int = 0,
        engine: str = "host"):
    cfg = PAPER_MLPS[dataset]
    n_train = d * c * n_ij
    ds = make_dataset(dataset, n=n_train + 1200, seed=seed)
    (Xtr, Ytr), (Xte, Yte) = train_test_split(ds, n_train, 1000, seed=seed)
    Xs, Ys = split_iid(Xtr, Ytr, d=d, c=[c] * d, n_ij=n_ij, seed=seed)
    task = cfg.task
    key = jax.random.PRNGKey(seed)
    # per-example losses let the ONE federated engine mask ragged/padded
    # silos (core/federated.py); engine='scan' compiles each trainer run
    # into a single dispatch
    loss = lambda p, x, y: mlp.mlp_per_example_loss(p, x, y, task)
    results = {}

    # Centralized (shares raw data; upper baseline)
    p = mlp.for_config(key, cfg, reduced=False)
    p, _ = baselines.sgd_train(loss, p, Xtr, Ytr, opt=adamw(1e-3), epochs=40,
                               engine=engine)
    results["Centralized"] = evaluate(p, Xte, Yte, task)

    # Local (single institution)
    p = mlp.for_config(key, cfg, reduced=False)
    p, _ = baselines.sgd_train(loss, p, Xs[0][0], Ys[0][0], opt=adamw(1e-3),
                               epochs=40, engine=engine)
    results["Local"] = evaluate(p, Xte, Yte, task)

    # FedAvg over all c·d institutions on raw features
    p = mlp.for_config(key, cfg, reduced=False)
    flat = [(Xs[i][j], Ys[i][j]) for i in range(d) for j in range(len(Xs[i]))]
    res = run_federated(loss, p, flat, opt=adamw(1e-3), rounds=20,
                        local_epochs=4, engine=engine)
    results["FedAvg"] = evaluate(res.params, Xte, Yte, task)

    # DC (conventional single-server data collaboration)
    flatX = [Xs[i][j] for i in range(d) for j in range(len(Xs[i]))]
    flatY = [Ys[i][j] for i in range(d) for j in range(len(Xs[i]))]
    maps, Gs, collabX = baselines.dc_setup(flatX, m_tilde=cfg.reduced_dim,
                                           seed=seed)
    p = mlp.for_config(key, cfg, reduced=True)
    p, _ = baselines.sgd_train(loss, p, np.concatenate(collabX),
                               np.concatenate(flatY), opt=adamw(1e-3), epochs=40,
                               engine=engine)
    results["DC"] = evaluate(p, np.asarray(maps[0](Xte) @ Gs[0]), Yte, task)

    # FedDCL (this paper)
    setup = protocol.run_protocol(Xs, Ys, m_tilde=cfg.reduced_dim, seed=seed)
    p = mlp.for_config(key, cfg, reduced=True)
    res = run_federated(loss, p, setup.fed_silos(),
                        opt=adamw(1e-3), rounds=20, local_epochs=4,
                        engine=engine)
    tr = setup.user_transform(0, 0)
    results["FedDCL"] = evaluate(res.params, np.asarray(tr(Xte)), Yte, task)

    metric = "RMSE" if task == "regression" else "Accuracy"
    print(f"\n{dataset} ({metric}):")
    for k, v in results.items():
        print(f"  {k:12s} {v:.4f}")
    return results


if __name__ == "__main__":
    from repro.api import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="battery_small",
                    choices=sorted(PAPER_MLPS))
    ap.add_argument("--engine", default="host", choices=["host", "scan"])
    args = ap.parse_args()
    run(args.dataset, engine=args.engine)
