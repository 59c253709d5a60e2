"""Shared harness for the paper-experiment benchmarks: run all five methods
(Centralized / Local / FedAvg / DC / FedDCL) on one dataset layout."""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.feddcl_mlp import PAPER_MLPS
from repro.core import baselines, protocol
from repro.core.federated import run_federated
from repro.data.partition import split_dirichlet, split_iid
from repro.data.tabular import make_dataset, train_test_split
from repro.models import mlp
from repro.optim import adamw


def run_all_methods(dataset: str, *, d: int = 5, c: int = 4, n_ij: int = 100,
                    rounds: int = 20, local_epochs: int = 4, epochs: int = 40,
                    n_test: int = 1000, seed: int = 0, lr: float = 1e-3,
                    non_iid: bool = False, dirichlet_alpha: float = 0.5,
                    methods=None, track_rounds: bool = False,
                    engine: str = "host", svd_backend: str = "host") -> Dict:
    """Returns {"metrics": {method: test metric}, "curves": {...}, "task": str}.
    Paper setup: batch 32; Centralized/Local/DC train `epochs`; FedAvg/FedDCL
    run `rounds` rounds × `local_epochs` epochs (§4.1).

    All five methods train through the ONE federated engine
    (core/federated.py): `engine` selects the per-batch-dispatch host loop
    or the fully compiled lax.scan program; `svd_backend` selects the step-3
    collaboration backend for FedDCL (DESIGN.md §3). The scan engine's plans
    carry stable loss/optimizer identities, so grid scripts (exp3_groups)
    reuse executables across configs from the shared plan cache instead of
    recompiling."""
    cfg = PAPER_MLPS[dataset]
    methods = methods or ["Centralized", "Local", "FedAvg", "DC", "FedDCL"]
    n_train = d * c * n_ij
    ds = make_dataset(dataset, n=n_train + n_test + 200, seed=seed)
    (Xtr, Ytr), (Xte, Yte) = train_test_split(ds, n_train, n_test, seed=seed)
    if non_iid:
        Xs, Ys = split_dirichlet(Xtr, Ytr, d, [c] * d, n_ij,
                                 alpha=dirichlet_alpha, seed=seed)
    else:
        Xs, Ys = split_iid(Xtr, Ytr, d, [c] * d, n_ij, seed=seed)
    task = cfg.task
    key = jax.random.PRNGKey(seed)
    loss = lambda p, x, y: mlp.mlp_per_example_loss(p, x, y, task)
    opt = adamw(lr)
    cache_kw = (dict(loss_id=("mlp_per_example_loss", task),
                     opt_id=("adamw", lr))
                if engine == "scan" else {})
    Xte_j, Yte_j = jnp.asarray(Xte), jnp.asarray(Yte)

    def metric(p, X=Xte_j):
        return mlp.mlp_metric(p, X, Yte_j, task)

    out: Dict[str, float] = {}
    curves: Dict[str, List[float]] = {}
    times: Dict[str, float] = {}

    for method in methods:
        t0 = time.perf_counter()
        if method == "Centralized":
            p = mlp.for_config(key, cfg, reduced=False)
            ev = (lambda pp: {"metric": metric(pp)}) if track_rounds else None
            p, hist = baselines.sgd_train(loss, p, Xtr, Ytr, opt=opt,
                                          epochs=epochs, eval_fn=ev,
                                          engine=engine, **cache_kw)
            out[method] = metric(p)
            if track_rounds:
                curves[method] = [h["metric"] for h in hist]
        elif method == "Local":
            p = mlp.for_config(key, cfg, reduced=False)
            ev = (lambda pp: {"metric": metric(pp)}) if track_rounds else None
            p, hist = baselines.sgd_train(loss, p, Xs[0][0], Ys[0][0],
                                          opt=opt, epochs=epochs,
                                          eval_fn=ev, engine=engine,
                                          **cache_kw)
            out[method] = metric(p)
            if track_rounds:
                curves[method] = [h["metric"] for h in hist]
        elif method == "FedAvg":
            p = mlp.for_config(key, cfg, reduced=False)
            flat = [(Xs[i][j], Ys[i][j]) for i in range(d) for j in range(c)]
            ev = (lambda pp: {"metric": metric(pp)}) if track_rounds else None
            res = run_federated(loss, p, flat, opt=opt, rounds=rounds,
                                local_epochs=local_epochs, eval_fn=ev,
                                engine=engine, **cache_kw)
            out[method] = metric(res.params)
            if track_rounds:
                curves[method] = [h["metric"] for h in res.history]
        elif method == "DC":
            flatX = [Xs[i][j] for i in range(d) for j in range(c)]
            flatY = [Ys[i][j] for i in range(d) for j in range(c)]
            maps, Gs, collabX = baselines.dc_setup(
                flatX, m_tilde=cfg.reduced_dim, seed=seed)
            p = mlp.for_config(key, cfg, reduced=True)
            Xte_dc = jnp.asarray(np.asarray(maps[0](Xte) @ Gs[0]))
            ev = (lambda pp: {"metric": metric(pp, Xte_dc)}) if track_rounds else None
            p, hist = baselines.sgd_train(loss, p, np.concatenate(collabX),
                                          np.concatenate(flatY), opt=opt,
                                          epochs=epochs, eval_fn=ev,
                                          engine=engine, **cache_kw)
            out[method] = metric(p, Xte_dc)
            if track_rounds:
                curves[method] = [h["metric"] for h in hist]
        elif method == "FedDCL":
            setup = protocol.run_protocol(Xs, Ys, m_tilde=cfg.reduced_dim,
                                          anchor_r=2000, seed=seed,
                                          svd_backend=svd_backend)
            p = mlp.for_config(key, cfg, reduced=True)
            tr = setup.user_transform(0, 0)
            Xte_f = jnp.asarray(np.asarray(tr(Xte)))
            ev = (lambda pp: {"metric": metric(pp, Xte_f)}) if track_rounds else None
            res = run_federated(loss, p, setup.fed_silos(),
                                opt=opt, rounds=rounds,
                                local_epochs=local_epochs, eval_fn=ev,
                                engine=engine, **cache_kw)
            out[method] = metric(res.params, Xte_f)
            if track_rounds:
                curves[method] = [h["metric"] for h in res.history]
        times[method] = time.perf_counter() - t0

    return {"metrics": out, "curves": curves, "task": task, "times": times}
