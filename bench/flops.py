"""Operations and bytes the benchmark's work needs, from shapes alone.

These do not depend on what implements the work: a later change to the
program can make a kernel faster, never change what it is credited with.
Counts are of multiply-adds as two operations; bytes are float32 operands
read once and results written once.
"""
from __future__ import annotations

import json
import pathlib
from typing import List, Sequence, Tuple

F32 = 4


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind (bench/peaks.json); an
    unknown kind is an error, not a default."""
    table = json.loads((pathlib.Path(__file__).parent / "peaks.json")
                       .read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f"; add them to bench/peaks.json with their source")
    return table[device_kind]


def gram(batch: int, rows: int, cols: int) -> Tuple[float, float]:
    """AᵀA for `batch` matrices of rows × cols: (flops, bytes)."""
    flops = 2.0 * batch * rows * cols * cols
    nbytes = F32 * batch * (rows * cols + cols * cols)
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, pk: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / pk["bf16_flops_per_s"]
    t_m = nbytes / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def protocol_grams(cfg: dict) -> List[Tuple[int, int, int]]:
    """The Gram reductions one from-scratch build needs: every group's
    stacked anchors (step 3a) and the stacked bases (step 3b)."""
    net, lay = cfg["network"], cfg["layout"]
    d, c, r = lay["groups"], lay["users_per_group"], lay["anchor_r"]
    return [(d, r, c * net["m_tilde"]), (1, r, d * net["m_hat"])]


def protocol_build(cfg: dict) -> float:
    """Dense linear algebra one from-scratch build of steps 2-3 needs
    (recomputations not counted): each user's local PCA through its Gram
    (2nm² + 9m³ for the symmetric eigensolver) and projections; every
    group's Gram, eigensolve and basis; the central one; each user's QR
    least squares against Z; and X̂ = X̃ G."""
    net, lay = cfg["network"], cfg["layout"]
    d, c, n, r = (lay["groups"], lay["users_per_group"],
                  lay["rows_per_user"], lay["anchor_r"])
    m, mt, mh = net["in_dim"], net["m_tilde"], net["m_hat"]
    users = d * c
    step2 = users * (2 * n * m * m + 9 * m ** 3 + 2 * m * mt * mt
                     + 2 * n * m * mt + 2 * r * m * mt)
    W, D = c * mt, d * mh
    step3a = d * (2 * r * W * W + 9 * W ** 3 + 2 * r * W * mh
                  + 2 * r * mh * mh)
    step3b = 2 * r * D * D + 9 * D ** 3 + 2 * r * D * mh + 2 * r * mh * mh
    step3c = users * (2 * r * mt * mt - (2 / 3) * mt ** 3 + 2 * r * mt * mh
                      + mt * mt * mh + 2 * n * mt * mh)
    return float(step2 + step3a + step3b + step3c)


def mlp_train_per_sample(widths: Sequence[int]) -> float:
    """Forward and backward operations of one training sample through a
    fully connected net: 6 per parameter (2 forward, 4 backward)."""
    return 6.0 * sum(a * b + b for a, b in zip(widths[:-1], widths[1:]))
