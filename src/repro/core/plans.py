"""Compiled-executable reuse across tenants: the pow2 shape-bucket rule and
the LRU cache that stores one jitted program per bucketed signature
(DESIGN.md §6).

Two consumers share it: step 4's FL plans (`core/federated.py`, whose
padded layout rounds silos and batches with `bucket_pow2`) and the
collaboration server's resident batch steps (`serve_collab/`, whose tenant
tables and request batches round with the same rule).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def bucket_pow2(n: int) -> int:
    """Round n up to the next power of two — the one bucket rule: ≤ 2×
    padding waste, log-many buckets over any tenant population."""
    return 1 << (max(int(n), 1) - 1).bit_length()


def tree_signature(tree: Any) -> Tuple:
    """Hashable (structure, leaf shapes/dtypes) fingerprint of a pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (str(treedef),
            tuple((tuple(np.shape(l)), str(jnp.asarray(l).dtype))
                  for l in leaves))


class PlanCache:
    """LRU cache of compiled programs keyed on their full compile signature.

    A cached program takes all tenant data as arguments, so two calls whose
    padded shapes land in the same `bucket_pow2` bucket and share the same
    static config reuse ONE jitted callable and therefore ONE XLA
    executable.

    Counters: hits / misses / evictions; a miss builds (and on first call
    compiles) a new program, so `misses` == number of executables built
    through this cache.
    """

    def __init__(self, max_plans: int = 64):
        self._plans: "OrderedDict[Tuple, Tuple]" = OrderedDict()
        self.max_plans = max_plans
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._plans)

    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "plans": len(self._plans)}

    def clear(self) -> None:
        self._plans.clear()
        self.hits = self.misses = self.evictions = 0

    def lookup(self, key: Tuple, build: Callable[[], Callable],
               pins: Tuple = ()) -> Tuple[Callable, bool]:
        """Return (plan, was_hit). `pins` holds strong references (loss_fn,
        opt) for entries keyed on object identity, so a cached id() can
        never be recycled by the allocator while the entry lives."""
        if key in self._plans:
            self._plans.move_to_end(key)
            self.hits += 1
            return self._plans[key][0], True
        plan = build()
        self._plans[key] = (plan, pins)
        self.misses += 1
        while len(self._plans) > self.max_plans:
            self._plans.popitem(last=False)
            self.evictions += 1
        return plan, False


_DEFAULT_PLAN_CACHE: Optional[PlanCache] = None


def default_plan_cache() -> PlanCache:
    """The process-wide plan cache: every scan-engine `run_federated` call
    that names no cache of its own, `FedDCL.fit()` and `ServeCollab`."""
    global _DEFAULT_PLAN_CACHE
    if _DEFAULT_PLAN_CACHE is None:
        _DEFAULT_PLAN_CACHE = PlanCache()
    return _DEFAULT_PLAN_CACHE


def clear_plan_cache() -> None:
    if _DEFAULT_PLAN_CACHE is not None:
        _DEFAULT_PLAN_CACHE.clear()
