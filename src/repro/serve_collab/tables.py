"""Device-resident tenant tables for collaboration serving (DESIGN.md §10).

After FedDCL setup, user (i, j)'s whole input pipeline collapses to ONE
affine map: f_j(x) G_j = (x − mu_j) (W_j G_j). A group's tenants therefore
serve from two stacked arrays

    M  (T_pad, m, m̂)   combined per-tenant maps  W_j @ G_j
    mu (T_pad, m)       per-tenant centering offsets

zero-padded on the tenant axis to the next power of two, so onboarding a
tenant usually lands in the existing padded shape (the resident batch step
never recompiles) and at worst doubles it (one fresh bucket). The tables
are ARGUMENTS of the jitted serve step, never closure captures — tenant
data stays out of the executable (analysis.hlo_audit.assert_no_baked_data
enforces this on the artifact).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import jax.numpy as jnp
import numpy as np

from repro.core.plans import bucket_pow2
from repro.core.protocol import FedDCLSetup


@dataclass
class TenantTable:
    """One group's resident serving state."""
    M: jnp.ndarray                    # (T_pad, m, m_hat) float32
    mu: jnp.ndarray                   # (T_pad, m) float32
    count: int                        # real tenants; rows past it are zeros

    @property
    def t_pad(self) -> int:
        return int(self.M.shape[0])

    @property
    def in_dim(self) -> int:
        return int(self.M.shape[1])

    @property
    def out_dim(self) -> int:
        return int(self.M.shape[2])


def combined_user_map(setup: FedDCLSetup, i: int, j: int) -> np.ndarray:
    """W_j^(i) @ G_j^(i) — the (m, m̂) matrix user (i,j) serves through."""
    return np.asarray(setup.mappings[i][j].W, np.float64) @ np.asarray(
        setup.Gs[i][j], np.float64)


def build_table(setup: FedDCLSetup, i: int) -> TenantTable:
    """Stack group i's tenants into one padded device-resident table."""
    count = len(setup.mappings[i])
    m = setup.mappings[i][0].W.shape[0]
    m_hat = np.asarray(setup.Gs[i][0]).shape[1]
    t_pad = bucket_pow2(count)
    M = np.zeros((t_pad, m, m_hat), np.float32)
    mu = np.zeros((t_pad, m), np.float32)
    for j in range(count):
        M[j] = combined_user_map(setup, i, j).astype(np.float32)
        mu[j] = np.asarray(setup.mappings[i][j].mu, np.float32)
    return TenantTable(M=jnp.asarray(M), mu=jnp.asarray(mu), count=count)


def build_tables(setup: FedDCLSetup) -> List[TenantTable]:
    """One table per DC group."""
    return [build_table(setup, i) for i in range(setup.num_groups)]
