"""Fully-connected nets for the paper's tabular experiments (§4).

Matches the paper's setup: layers [{m, m̂} - hidden… - out], sigmoid-free
ReLU hidden activations, linear output for regression / logits for
classification. Trained with the substrate optimizer (optim/) under
Centralized / Local / FedAvg / DC / FedDCL drivers (core/).
"""
from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.feddcl_mlp import MLPConfig

Params = Dict[str, Any]


def init_mlp_params(key, in_dim: int, hidden: Sequence[int], out_dim: int,
                    dtype=jnp.float32) -> Params:
    dims = [in_dim, *hidden, out_dim]
    keys = jax.random.split(key, len(dims) - 1)
    layers = []
    for i, k in enumerate(keys):
        w = jax.random.normal(k, (dims[i], dims[i + 1]), jnp.float32)
        w = w * jnp.sqrt(2.0 / dims[i])
        layers.append({"w": w.astype(dtype), "b": jnp.zeros((dims[i + 1],), dtype)})
    return {"layers": layers}


def mlp_forward(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    # full f32 passes: at TPU default precision an f32 matmul is one bf16
    # pass, and the paper's f32 networks would train and serve ~3 digits off
    # the f32 reference (serving is held to 2e-5 of the direct path)
    h = x
    n = len(params["layers"])
    for i, lp in enumerate(params["layers"]):
        h = jnp.matmul(h, lp["w"], precision=jax.lax.Precision.HIGHEST) \
            + lp["b"]
        if i < n - 1:
            h = jax.nn.relu(h)
    return h


def mlp_per_example_loss(params: Params, x: jnp.ndarray, y: jnp.ndarray,
                         task: str) -> jnp.ndarray:
    """(n,) per-example losses — what the federated engine masks/weights for
    zero-padded ragged silos (core/federated.py). mlp_loss is its mean."""
    pred = mlp_forward(params, x)
    if task == "regression":
        return jnp.mean(jnp.square(pred - y), axis=-1)
    logz = jax.nn.logsumexp(pred, axis=-1)
    gold = jnp.take_along_axis(pred, y.astype(jnp.int32)[:, None], axis=-1)[:, 0]
    return logz - gold


def mlp_loss(params: Params, x: jnp.ndarray, y: jnp.ndarray, task: str,
             l2: float = 0.0) -> jnp.ndarray:
    loss = jnp.mean(mlp_per_example_loss(params, x, y, task))
    if l2:
        sq = sum(jnp.sum(jnp.square(lp["w"])) for lp in params["layers"])
        loss = loss + l2 * sq
    return loss


def mlp_metric(params: Params, x: jnp.ndarray, y: jnp.ndarray, task: str) -> float:
    """RMSE for regression (paper Fig. 4/5), accuracy for classification."""
    pred = mlp_forward(params, x)
    if task == "regression":
        return float(jnp.sqrt(jnp.mean(jnp.square(pred - y))))
    return float(jnp.mean(jnp.argmax(pred, -1) == y.astype(jnp.int32)))


def for_config(key, cfg: MLPConfig, *, reduced: bool, dtype=jnp.float32) -> Params:
    in_dim = cfg.reduced_dim if reduced else cfg.in_dim
    return init_mlp_params(key, in_dim, cfg.hidden, cfg.out_dim, dtype)
