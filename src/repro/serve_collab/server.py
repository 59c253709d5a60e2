"""Multi-tenant online inference over the collaboration pipeline
(DESIGN.md §10) — the plan cache's first live consumer.

Heterogeneous prediction requests (any tenant, any row count) share ONE
resident jitted batch step per (tenant-table pad, pow2 batch pad) shape
bucket:

    step(params, M, mu, x, tix) = h((x − mu[tix]) · M[tix])

Tenant dispatch is a take-along-tenant-index gather, so a mixed batch of
users — even from different onboarding generations — is a single fused
einsum + model forward. Every array (model params, tenant tables, request
rows, tenant indices) is a runtime ARGUMENT: executables are shared across
groups with equal padded shapes, tenant data is never baked into the
artifact (hlo_audit-enforced), and warm mixed-tenant traffic compiles
nothing (CompileCounter == 0, tested).

Admission reuses the slot-table/continuous-batching idiom of
launch/serve.py, adapted to one-shot requests: a FIFO deque is scanned for
rows of the head request's group, packed up to `max_batch`, padded to the
pow2 bucket, and served in one dispatch; oversize requests are chunked
across steps and requeue implicitly (their `served` cursor advances in
place). Statuses mirror launch/serve.py: "done" / "truncated" (partially
served when `max_steps` ran out) / "pending" (never reached a batch).
"""
from __future__ import annotations

import time
from collections import Counter, deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.plans import (PlanCache, bucket_pow2, default_plan_cache,
                              tree_signature)
from repro.core.protocol import FedDCLSetup
from repro.models import mlp
from repro.serve_collab.tables import TenantTable, build_tables


def _fetch(x) -> np.ndarray:
    """A device array read to the host (counted as
    ``feddcl.device.fetches``)."""
    obs.count("feddcl.device.fetches")
    return np.asarray(x)


@dataclass
class CollabRequest:
    """One prediction request: `x` rows through tenant (group, user)."""
    rid: int
    group: int
    user: int
    x: np.ndarray                      # (n, m) float; (m,) is auto-promoted
    out: Optional[np.ndarray] = None   # (n, out_dim), filled as rows serve
    served: int = 0
    status: str = "pending"            # pending | truncated | done
    t_submit: float = field(default=0.0, repr=False)
    t_done: float = field(default=0.0, repr=False)

    def __post_init__(self):
        self.x = np.asarray(self.x, np.float32)
        if self.x.ndim == 1:
            self.x = self.x[None, :]

    @property
    def rows(self) -> int:
        return int(self.x.shape[0])

    @property
    def latency(self) -> float:
        return self.t_done - self.t_submit


class ServeOutput(Dict[int, np.ndarray]):
    """{rid: served output rows} plus `.status`: {rid: done|truncated|pending}."""

    def __init__(self, outputs: Dict[int, np.ndarray],
                 status: Dict[int, str]):
        super().__init__(outputs)
        self.status = status


def serve_step(params, M, mu, x, tix):
    """The resident batch step — a PURE function of its arguments.

    params: model pytree;  M: (T_pad, m, m̂) tenant maps;  mu: (T_pad, m)
    offsets;  x: (B_pad, m) request rows;  tix: (B_pad,) tenant indices.
    Padded rows carry tix 0 and produce garbage the server slices away.
    """
    z = x - mu[tix]                                   # (B, m)
    h = jnp.einsum("bm,bmh->bh", z, M[tix],           # (B, m̂)
                   precision=jax.lax.Precision.HIGHEST)
    return mlp.mlp_forward(params, h)


class ServeCollab:
    """Queued, bucketed, continuously-admitted collaboration serving."""

    def __init__(self, tables: Sequence[TenantTable], params: Any, *,
                 setup: Optional[FedDCLSetup] = None,
                 max_batch: int = 256, cache: Optional[PlanCache] = None):
        self.tables: List[TenantTable] = list(tables)
        self.params = params
        self.setup = setup
        self.max_batch = int(max_batch)
        self.cache = cache if isinstance(cache, PlanCache) \
            else default_plan_cache()
        self.queue: deque = deque()
        self._psig = tree_signature(params)
        self._next_rid = 0
        self.steps = 0
        self.rows_served = 0
        self.requests_done = 0
        self.latencies: List[float] = []
        self.bucket_hist: Counter = Counter()   # (group, T_pad, B_pad) -> steps

    # -- construction ------------------------------------------------------

    @classmethod
    def from_setup(cls, setup: FedDCLSetup, params: Any,
                   **kw) -> "ServeCollab":
        return cls(build_tables(setup), params, setup=setup, **kw)

    @classmethod
    def from_model(cls, model, **kw) -> "ServeCollab":
        """Bind to a fitted repro.api.FedDCL estimator."""
        if model.setup_ is None:
            raise RuntimeError("call fit() before serve()")
        return cls.from_setup(model.setup_, model.params_, **kw)

    # -- admission ---------------------------------------------------------

    def submit(self, x: np.ndarray, group: int, user: int,
               rid: Optional[int] = None) -> CollabRequest:
        """Enqueue rows for tenant (group, user); returns the request."""
        if not 0 <= group < len(self.tables):
            raise ValueError(f"unknown group {group}")
        if not 0 <= user < self.tables[group].count:
            raise ValueError(f"unknown user {user} in group {group} "
                             f"(count={self.tables[group].count})")
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid) + 1
        req = CollabRequest(rid=rid, group=group, user=user, x=x)
        req.t_submit = time.perf_counter()
        self.queue.append(req)
        return req

    # -- the resident step -------------------------------------------------

    def _step_fn(self, t_pad: int, b_pad: int, m: int, m_hat: int):
        """The compiled step for one shape bucket, through the plan cache.
        The key is ALL-shape (no group id, no tenant identity): groups with
        equal padded shapes share one executable, and warm lookups build
        nothing."""
        key = ("serve_collab", int(m), int(m_hat), int(t_pad), int(b_pad),
               self._psig)
        fn, _ = self.cache.lookup(key, lambda: jax.jit(serve_step))
        return fn

    def lower_step(self, group: int, b_pad: int):
        """Lower (don't run) the serve step for a bucket — feed for
        analysis.hlo_audit (assert_no_baked_data / collective_census)."""
        tbl = self.tables[group]
        x = jnp.zeros((b_pad, tbl.in_dim), jnp.float32)
        tix = jnp.zeros((b_pad,), jnp.int32)
        return jax.jit(serve_step).lower(self.params, tbl.M, tbl.mu, x, tix)

    # -- serving loop ------------------------------------------------------

    def step(self) -> int:
        """Serve ONE bucket: pack rows of the head request's group from the
        queue (FIFO within the group, other groups undisturbed), pad to the
        pow2 width, dispatch the resident step, scatter outputs back.
        Returns rows served (0 when idle)."""
        if not self.queue:
            return 0
        with obs.span("feddcl.serve.step"):
            with obs.span("feddcl.serve.pack"):
                g = self.queue[0].group
                tbl = self.tables[g]
                batch: List[tuple] = []                # (req, lo, take)
                rows = 0
                for req in self.queue:
                    if req.group != g:
                        continue
                    take = min(req.rows - req.served, self.max_batch - rows)
                    if take <= 0:
                        continue
                    batch.append((req, req.served, take))
                    rows += take
                    if rows >= self.max_batch:
                        break
                b_pad = bucket_pow2(rows)
                x = np.zeros((b_pad, tbl.in_dim), np.float32)
                tix = np.zeros((b_pad,), np.int32)
                at = 0
                for req, lo, take in batch:
                    x[at:at + take] = req.x[lo:lo + take]
                    tix[at:at + take] = req.user
                    at += take
            with obs.span("feddcl.serve.dispatch"):
                fn = self._step_fn(tbl.t_pad, b_pad, tbl.in_dim, tbl.out_dim)
                y = _fetch(fn(self.params, tbl.M, tbl.mu, x, tix))
            with obs.span("feddcl.serve.scatter"):
                at = 0
                now = time.perf_counter()
                for req, lo, take in batch:
                    if req.out is None:
                        req.out = np.zeros((req.rows, y.shape[1]), np.float32)
                    req.out[lo:lo + take] = y[at:at + take]
                    at += take
                    req.served += take
                    req.status = "truncated"       # partially served so far
                    if req.served == req.rows:
                        req.status = "done"
                        req.t_done = now
                        self.latencies.append(req.latency)
                        self.requests_done += 1
                self.queue = deque(r for r in self.queue
                                   if r.served < r.rows)
                self.steps += 1
                self.rows_served += rows
                self.bucket_hist[(g, tbl.t_pad, b_pad)] += 1
        return rows

    def serve(self, requests: Optional[Sequence[CollabRequest]] = None, *,
              max_steps: int = 10_000) -> ServeOutput:
        """Drain the queue (plus `requests`, submitted first) through at
        most `max_steps` dispatches. The returned mapping holds each
        request's SERVED rows; `.status` distinguishes finished requests
        from ones truncated mid-serve or never admitted."""
        tracked: List[CollabRequest] = list(self.queue)
        for req in requests or ():
            req.t_submit = time.perf_counter()
            self.queue.append(req)
            tracked.append(req)
        steps = 0
        while self.queue and steps < max_steps:
            self.step()
            steps += 1
        outputs = {r.rid: (r.out[: r.served] if r.out is not None
                           else np.zeros((0, 0), np.float32))
                   for r in tracked}
        return ServeOutput(outputs, {r.rid: r.status for r in tracked})

    # -- live onboarding ---------------------------------------------------

    def _refresh_tables(self) -> None:
        """Rebuild every group's table from the (refreshed) setup: Z moved,
        so every tenant's combined map changed — table CONTENT is runtime
        data, only a grown pow2 tenant pad can introduce a new bucket."""
        with obs.span("feddcl.serve.refresh_tables"):
            self.tables = build_tables(self.setup)

    def onboard_user(self, i: int, X_new: np.ndarray,
                     Y_new: np.ndarray) -> int:
        """Onboard a new user into group i of the LIVE server (incremental
        protocol update, DESIGN.md §10) and refresh the tenant tables; the
        queue and compiled buckets stay warm. Returns the new user index."""
        if self.setup is None:
            raise RuntimeError(
                "this server was built from raw tables; onboarding needs "
                "ServeCollab.from_setup/from_model (a FedDCLSetup with "
                "onboarding state)")
        j = self.setup.onboard_user(i, X_new, Y_new)
        self._refresh_tables()
        return j

    def onboard_silo(self, Xs_new: Sequence[np.ndarray],
                     Ys_new: Sequence[np.ndarray]) -> int:
        """Onboard a whole new group onto the live server; returns its
        index (immediately servable)."""
        if self.setup is None:
            raise RuntimeError(
                "this server was built from raw tables; onboarding needs "
                "ServeCollab.from_setup/from_model")
        i = self.setup.onboard_silo(Xs_new, Ys_new)
        self._refresh_tables()
        return i

    # -- introspection -----------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        lat = sorted(self.latencies)

        def pct(p: float) -> float:
            if not lat:
                return 0.0
            return lat[min(len(lat) - 1, int(p * len(lat)))]

        return {
            "steps": self.steps,
            "rows_served": self.rows_served,
            "requests_done": self.requests_done,
            "queued": len(self.queue),
            "p50_latency_s": pct(0.50),
            "p99_latency_s": pct(0.99),
            "buckets": {f"g{g}/T{t}/B{b}": n
                        for (g, t, b), n in sorted(self.bucket_hist.items())},
            "cache": self.cache.stats(),
        }
