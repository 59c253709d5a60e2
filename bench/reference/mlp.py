"""Plain reference of the paper's networks (arXiv:2409.18356, Table 3): a
fully connected ReLU net [m̂ → hidden… → classes] with logits out and the
per-example cross-entropy, and FedAvg of Adam-trained silos (§4.1) over it.

Weights are the benchmark's own, drawn from a seed on the device in one
jitted call (He-normal weights, zero biases); the program and the reference
both start from them. Every matrix product runs in float32 at the highest
precision, as the configuration states; ``lowp=True`` rounds the products'
operands to bfloat16 (accumulating in float32): the control.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def dims(cfg: dict) -> List[int]:
    net = cfg["network"]
    return [net["m_hat"], *net["hidden"], net["classes"]]


@partial(jax.jit, static_argnames=("widths",))
def init_params(key, widths: Sequence[int]):
    """{"layers": [{"w", "b"}, ...]}: w ~ N(0, 2/fan_in), b = 0, f32."""
    keys = jax.random.split(key, len(widths) - 1)
    return {"layers": [
        {"w": jax.random.normal(k, (a, b), jnp.float32) * jnp.sqrt(2.0 / a),
         "b": jnp.zeros((b,), jnp.float32)}
        for k, a, b in zip(keys, widths[:-1], widths[1:])]}


def param_count(widths: Sequence[int]) -> int:
    return sum(a * b + b for a, b in zip(widths[:-1], widths[1:]))


def forward(params, x, lowp: bool = False):
    h = x.astype(jnp.float32)
    layers = params["layers"]
    for i, lp in enumerate(layers):
        if lowp:
            h = jnp.matmul(h.astype(jnp.bfloat16), lp["w"].astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32) + lp["b"]
        else:
            h = jnp.matmul(h, lp["w"], precision=HIGHEST) + lp["b"]
        if i < len(layers) - 1:
            h = jax.nn.relu(h)
    return h


def xent(params, x, y, lowp: bool = False):
    """Per-example cross-entropy of integer labels."""
    z = forward(params, x, lowp)
    gold = jnp.take_along_axis(z, y.astype(jnp.int32)[:, None], axis=-1)[:, 0]
    return jax.nn.logsumexp(z, axis=-1) - gold


def forward_np(params, x: np.ndarray, lowp: bool = False) -> np.ndarray:
    """The same network in NumPy float64 (serving's reference), or on
    bfloat16-rounded operands for the control."""
    from bench.reference.protocol import mm
    h = np.asarray(x, np.float64)
    layers = params["layers"]
    for i, lp in enumerate(layers):
        h = mm(h, np.asarray(lp["w"], np.float64), lowp) + np.asarray(
            lp["b"], np.float64)
        if i < len(layers) - 1:
            h = np.maximum(h, 0.0)
    return h


def schedule(key, rnd: int, silo: int, epoch: int, n_slots: int):
    """One epoch's visiting order of a silo's slots: a permutation drawn
    from fold_in(fold_in(fold_in(key, round), silo), epoch)."""
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        key, rnd), silo), epoch)
    return jax.random.permutation(k, n_slots)


@partial(jax.jit, static_argnames=("batch", "lr", "b1", "b2", "eps", "lowp",
                                   "half"))
def local_epoch(params, m, v, t, X, Y, n, order, *, batch, lr, b1, b2, eps,
                lowp, half=False):
    """One epoch of Adam over one silo. X, Y hold the silo's n rows and
    zeros after them up to len(order) slots; each minibatch is the next
    `batch` slots of `order`, its loss the mean over its real rows, and a
    minibatch with no real row changes nothing. Returns the new state and
    the epoch's loss (mean over its real rows). `half` plants a fault:
    the second half of every minibatch is left out."""
    def step(carry, idx):
        p, m, v, t = carry
        w = (idx < n).astype(jnp.float32)
        if half:
            w = w * (jnp.arange(batch) < batch // 2)
        xb, yb = X[idx], Y[idx]

        def loss(p):
            return jnp.sum(w * xent(p, xb, yb, lowp)) / jnp.maximum(
                jnp.sum(w), 1e-12)

        val, g = jax.value_and_grad(loss)(p)
        t1 = t + 1
        tf = t1.astype(jnp.float32)
        m1 = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v1 = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        p1 = jax.tree.map(
            lambda p_, a, b: p_ - lr * (a / (1 - b1 ** tf)) / (
                jnp.sqrt(b / (1 - b2 ** tf)) + eps), p, m1, v1)
        real = jnp.sum(w) > 0
        keep = lambda new, old: jax.tree.map(
            lambda a, b: jnp.where(real, a, b), new, old)
        return ((keep(p1, p), keep(m1, m), keep(v1, v),
                 jnp.where(real, t1, t)), (val * jnp.sum(w), jnp.sum(w)))

    (params, m, v, t), (ls, ws) = jax.lax.scan(
        step, (params, m, v, t), order.reshape(-1, batch))
    return params, m, v, t, jnp.sum(ls) / jnp.maximum(jnp.sum(ws), 1e-12)


def fedavg(params, silos, *, rounds: int, local_epochs: int, batch: int,
           lr: float, b1: float, b2: float, eps: float, key, n_slots: int,
           lowp: bool = False, fault: Optional[str] = None):
    """FedAvg over silos [(X_i, Y_i)]: each round every silo starts from the
    global params with fresh Adam state, trains `local_epochs` epochs, and
    the global params become the sample-weighted mean. Returns the final
    params and each round's loss (the weighted mean of each silo's last
    epoch loss).

    `fault` plants one of the faults the comparison must catch, in the
    reference put in the program's place: "half_batch" leaves out half of
    every minibatch; "no_boundary" leaves out the exchange at the round
    boundary (every round restarts from the round's starting params)."""
    sizes = np.array([len(x) for x, _ in silos], np.float64)
    wts = (sizes / sizes.sum()).astype(np.float32)
    data = []
    for x, y in silos:
        Xp = np.zeros((n_slots, x.shape[1]), np.float32)
        Yp = np.zeros((n_slots,), np.int32)
        Xp[:len(x)], Yp[:len(y)] = x, y
        data.append((jnp.asarray(Xp), jnp.asarray(Yp), len(x)))
    hyper = dict(batch=batch, lr=lr, b1=b1, b2=b2, eps=eps, lowp=lowp,
                 half=fault == "half_batch")
    gp, losses = params, []
    for rnd in range(rounds):
        new, round_loss = [], 0.0
        for i, (X, Y, n) in enumerate(data):
            p = gp
            m = jax.tree.map(jnp.zeros_like, p)
            v = jax.tree.map(jnp.zeros_like, p)
            t = jnp.zeros((), jnp.int32)
            for e in range(local_epochs):
                p, m, v, t, ep = local_epoch(
                    p, m, v, t, X, Y, n, schedule(key, rnd, i, e, n_slots),
                    **hyper)
            new.append(p)
            round_loss += float(wts[i]) * float(ep)
        if fault != "no_boundary":
            gp = jax.tree.map(lambda *ps: sum(float(w) * q for w, q in
                                              zip(wts, ps)), *new)
        losses.append(round_loss)
    return gp, np.asarray(losses)


def first_grad_norms(params, X, Y, batch: int):
    """Per-leaf norms of the loss gradient on one minibatch: which leaves
    the comparison may hold (a leaf whose gradient is nought to rounding
    moves by round-off alone)."""
    g = jax.grad(lambda p: jnp.mean(xent(p, X[:batch], Y[:batch])))(params)
    return [float(jnp.linalg.norm(x)) for x in jax.tree.leaves(g)]
