"""Shared pieces of the benchmark: paths, seeds, cell lookup and the
deployment's data, made from the seed alone.

The synthetic rows follow the latent-class stand-in of the paper's tabular
datasets (class-conditional latent Gaussians mapped to the raw width), with
the parameters each configuration file states under ``data``. The generator
is the benchmark's own copy, so a change to the program's data module
cannot move the yardstick.
"""
from __future__ import annotations

import json
import pathlib
import zlib
from dataclasses import dataclass
from typing import List

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, root: pathlib.Path = ROOT):
    """(cell entry, configuration, traffic mix, limits) for a workload name,
    each found by name: bench/configs/<config>.json,
    bench/traffic/<traffic>.json and bench/limits/<workload>.json."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; "
                       f"choose from {sorted(cells)}")
    cell = cells[workload]
    bench = root / "bench"
    cfg = load_json(bench / "configs" / f"{cell['config']}.json")
    traffic = load_json(bench / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(bench / "limits" / f"{workload}.json")
    return spec, cell, cfg, traffic, limits


def subseed(seed: int, *tags) -> int:
    """A 31-bit seed for one use, derived from the run's seed and tags, so
    that any whole number the driver passes (beyond 32 bits too) works and
    no two uses share a stream."""
    words = [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF]
    words += [zlib.crc32(str(t).encode()) for t in tags]
    return int(np.random.SeedSequence(words).generate_state(1)[0] & 0x7FFFFFFF)


@dataclass
class Deployment:
    """A FedDCL deployment's raw data: Xs[i][j] / Ys[i][j] for user j of
    group i, a held-out pool of rows for serving, and newcomers for
    onboarding (each one user's rows)."""
    Xs: List[List[np.ndarray]]
    Ys: List[List[np.ndarray]]
    pool_X: np.ndarray
    pool_Y: np.ndarray
    new_X: List[np.ndarray]
    new_Y: List[np.ndarray]


def latent_rows(rng, n: int, m: int, latent: int, classes: int, noise: float,
                sep: float):
    """Class-conditional latent Gaussians mapped to m raw features."""
    y = rng.integers(0, classes, size=n)
    centers = rng.standard_normal((classes, latent))
    centers = centers / np.linalg.norm(centers, axis=1, keepdims=True) * sep
    Z = centers[y] + rng.standard_normal((n, latent))
    W = rng.standard_normal((latent, m)) / np.sqrt(latent)
    X = Z @ W + noise * rng.standard_normal((n, m))
    return X, y.astype(np.int64)


def make_deployment(cfg: dict, seed: int, *, pool: int = 0,
                    newcomers: int = 0) -> Deployment:
    """Every user's rows, a serving pool and newcomers, all drawn from one
    distribution (one draw of centres and loadings) from the seed."""
    lay, net, data = cfg["layout"], cfg["network"], cfg["data"]
    d, c, n = lay["groups"], lay["users_per_group"], lay["rows_per_user"]
    total = d * c * n + pool + newcomers * n
    rng = np.random.default_rng(subseed(seed, "rows"))
    X, Y = latent_rows(rng, total, net["in_dim"], data["latent"],
                       net["classes"], data["noise"], data["sep"])
    Xs = [[X[(i * c + j) * n:(i * c + j + 1) * n] for j in range(c)]
          for i in range(d)]
    Ys = [[Y[(i * c + j) * n:(i * c + j + 1) * n] for j in range(c)]
          for i in range(d)]
    k = d * c * n
    pool_X, pool_Y = X[k:k + pool], Y[k:k + pool]
    k += pool
    new_X = [X[k + t * n:k + (t + 1) * n] for t in range(newcomers)]
    new_Y = [Y[k + t * n:k + (t + 1) * n] for t in range(newcomers)]
    return Deployment(Xs, Ys, pool_X, pool_Y, new_X, new_Y)


def protocol_kwargs(cfg: dict) -> dict:
    """The protocol's settings as the configuration states them."""
    net, lay = cfg["network"], cfg["layout"]
    return dict(m_tilde=net["m_tilde"], m_hat=net["m_hat"],
                anchor_r=lay["anchor_r"])
