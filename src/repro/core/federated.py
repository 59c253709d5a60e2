"""Step 4 of FedDCL: federated learning between intra-group DC servers.

ONE trainer serves every method (`run_federated`; Centralized / Local / DC
reach it through `baselines.sgd_train`, the d=1 degenerate case), with two
interchangeable engines mirroring the step-3 `CollabBackend` split
(DESIGN.md §3, §4):

  engine="host" — the paper-faithful reference: a NumPy-orchestrated Python
      loop that dispatches one tiny jitted SGD step per minibatch per epoch
      per silo per round (thousands of device launches for a 20-round run).
  engine="scan" — the compiled form: the WHOLE FL phase is one jitted
      program. Silo datasets are zero-padded to a (d, n_slots, m) stack with
      per-sample masks, minibatch order comes from `jax.random.permutation`
      folded from the seed, local epochs and minibatches are inner
      `lax.scan`s with the per-silo step vmapped over the leading silo dim,
      and rounds are an outer `lax.scan` whose boundary is the weighted
      `fedavg_sync`. A 20-round × 4-epoch run is ONE dispatch.

Both engines consume the same padded layout (`pad_silo_data`) and the same
batch schedule (`round_perms`), so with the same seed they agree to float
tolerance on parameters and loss trajectories (tests/test_fed_engine.py).
FedAvg / FedProx / FedSGD all route through the same code path.

The scan engine's compiled unit is a PLAN (`make_fl_plan`): a jitted
program taking ALL tenant data (padded stacks, weights, PRNG key) as
arguments, so executables are reusable across tenants. `PlanCache` stores
plans keyed on the full compile signature with silo/batch axes rounded up
to shape buckets (`run_federated(cache=True)`, DESIGN.md §6) — the
amortization layer that makes sweeps and many-tenant traffic pay the
~1 s trace+compile once instead of per call.

Plans also run MULTI-DEVICE (`make_fl_plan(mesh=...)`, DESIGN.md §7): the
rounds-scan is wrapped in one `shard_map` with the padded silo stack split
over the mesh's silo axes (("pod", "data") jointly on multi-pod meshes)
and params replicated; the local phase is collective-free per shard and
the round boundary lowers to one weighted all-reduce per leaf per
hierarchy level. With `eval_fn`, plans are `StreamedPlan` chunk steps
that bound eval memory to eval_chunk × |params| regardless of rounds
(no more (rounds, |params|) stack inside the scan).

Loss reporting: `history[rnd]["loss"]` is the sample-weighted mean over
silos of each silo's final-local-epoch masked mean loss (the scan engine
carries it through the scan; the host engine accumulates the same sums).

HOSTILE-WORLD federation (DESIGN.md §8): the aggregation boundary can be
made adversarial-robust (`aggregator="median" | "trimmed_mean" | "krum"` —
masked coordinate statistics over the per-silo deltas, computed from a
cross-silo all_gather instead of the weighted psum when sharded), silos can
drop out mid-training (`dropout_rate` / an explicit `availability` matrix —
the schedule is drawn on HOST, outside any shard_map manual region, and
folded into per-round normalized weights so unavailable silos are exact
no-ops under the §4 mask rules), and per-silo deltas can be scaled
(`silo_scale` — the gradient-scaling attacker injection point,
core/privacy.py).

The mesh-collective primitives (`silo_vmap_step`, `fedavg_sync`,
`scan_local_steps`) are the production form on the TPU mesh: parameters
carry a leading silo dim sharded over the silo mesh axis, local steps are
vmapped over that dim (provably zero cross-silo collectives) and the round
boundary is one mean-reduce. launch/steps.py builds its federated round on
top of them.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.optim import Optimizer, apply_updates
from repro.shardingx.policy import batch_spec

# Aggregation sums over the silo axis are matmuls; at TPU default precision
# an f32 matmul runs one bf16 pass, which would round every averaged
# parameter to ~3 significant digits each round.
HIGHEST = jax.lax.Precision.HIGHEST


# ==========================================================================
# 1. Shared engine substrate: padded silo layout + batch schedule + step
# ==========================================================================

@dataclass(frozen=True)
class PaddedSilos:
    """Zero-padded device layout shared by both engines.

    X (d, n_slots, m) float32 and Y (d, n_slots[, k]) are the silo datasets
    padded on the sample axis; w (d, n_slots) float32 holds 1.0 on REAL
    samples and 0.0 on padding; sizes (d,) int64 are the real sample counts
    (kept integral — float32 counts silently corrupt FedAvg weights above
    2^24 samples; they are converted to float only at the normalization
    sites, see _norm_weights).
    n_slots = num_batches * batch_size ≥ max_i n_i, so every minibatch has a
    static shape and an epoch is exactly one permutation of the slot axis.

    The silo axis may carry trailing EMPTY silos (sizes 0, all-padding) and
    the slot axis trailing all-padding batches — how the plan cache buckets
    ragged tenant shapes onto shared executables (pad_silo_data's
    min_silos / min_batches).
    """
    X: np.ndarray
    Y: np.ndarray
    w: np.ndarray
    sizes: np.ndarray
    n_slots: int
    batch_size: int
    num_batches: int

    @property
    def num_silos(self) -> int:
        return self.X.shape[0]

    @property
    def has_padding(self) -> bool:
        return bool(np.any(self.sizes < self.n_slots))


def pad_silo_data(silo_data: Sequence[Tuple[np.ndarray, np.ndarray]],
                  batch_size: Optional[int] = None,
                  fill: float = 0.0,
                  min_batches: int = 0,
                  min_silos: int = 0) -> PaddedSilos:
    """Stack ragged per-silo (X_i, Y_i) into the padded engine layout.

    batch_size=None means full-batch (FedSGD): one batch of n_max slots.
    `fill` sets the value written into padded X rows — 0.0 in production;
    the padding-leak property test passes garbage to prove masks win.
    min_batches / min_silos round the layout UP to a shape bucket (extra
    all-padding batches / extra zero-size silos) so different tenants share
    one compiled executable (the plan cache, DESIGN.md §6). Empty silos get
    sample weight zero everywhere, so they are exact no-ops.
    """
    sizes = np.array([np.asarray(x).shape[0] for x, _ in silo_data], np.int64)
    n_max = int(sizes.max())
    if batch_size is None:
        bs, nb = max(n_max, 1), 1
    else:
        bs = int(batch_size)
        nb = -(-n_max // bs)
    nb = max(nb, int(min_batches), 1)
    n_slots = bs * nb
    d = max(len(silo_data), int(min_silos))
    if d > len(silo_data):
        sizes = np.concatenate([sizes, np.zeros(d - len(silo_data), np.int64)])
    x0, y0 = np.asarray(silo_data[0][0]), np.asarray(silo_data[0][1])
    X = np.full((d, n_slots) + x0.shape[1:], fill, np.float32)
    Y = np.zeros((d, n_slots) + y0.shape[1:], y0.dtype)
    w = np.zeros((d, n_slots), np.float32)
    for i, (xi, yi) in enumerate(silo_data):
        n = np.asarray(xi).shape[0]
        X[i, :n] = np.asarray(xi, np.float32)
        Y[i, :n] = np.asarray(yi)
        w[i, :n] = 1.0
    return PaddedSilos(X=X, Y=Y, w=w, sizes=sizes, n_slots=n_slots,
                       batch_size=bs, num_batches=nb)


def _norm_weights(sizes: np.ndarray) -> np.ndarray:
    """Per-silo FedAvg weights from integral sample counts: normalized on
    host in float64 (exact for any realistic count) and only THEN cast to
    float32 for the device — sizes themselves are never stored as float32,
    which would corrupt counts above 2^24."""
    s = np.asarray(sizes, np.float64)
    return (s / s.sum()).astype(np.float32)


# Tiny-epsilon guard for loss denominators. The old clamp max(Σw, 1.0)
# silently DEFLATED the reported loss whenever an epoch's (or batch's) real
# sample-weight mass was positive but < 1 — e.g. fractional per-sample
# weights fed through a hand-built PaddedSilos/plan. For {0,1} masks the two
# forms are identical (mass is 0 or ≥ 1), so this is numerics-neutral on
# every production layout; tests/test_fed_robust.py pins the corrected
# fractional-weight value on both engines.
_DEN_EPS = 1e-12


def make_dropout_schedule(seed: int, rounds: int, num_silos: int,
                          rate: float,
                          sizes: Optional[np.ndarray] = None) -> np.ndarray:
    """Per-round silo availability mask, (rounds, num_silos) float32 {0,1}.

    Drawn ON HOST (numpy; never inside a compiled program, let alone a
    shard_map manual region) so both engines and every
    sharding of the plan consume the identical schedule. Each (round, silo)
    is an independent Bernoulli(1 - rate) draw; empty silos (sizes 0) are
    never available, and every round is guaranteed at least one available
    REAL silo (the max-draw silo is resurrected) so round weights stay
    normalizable. Stragglers are modeled as round-grained dropout: a silo
    that misses the boundary simply doesn't contribute this round."""
    real = (np.ones(num_silos, bool) if sizes is None
            else np.asarray(sizes) > 0)
    if not real.any():
        raise ValueError("dropout schedule needs at least one real silo")
    rng = np.random.default_rng(np.asarray([seed, 0xD120], np.uint64))
    u = rng.random((rounds, num_silos))
    av = (u >= rate) & real[None, :]
    dead = ~av.any(axis=1)
    if dead.any():
        best = np.argmax(np.where(real[None, :], u, -1.0), axis=1)
        av[dead, best[dead]] = True
    return av.astype(np.float32)


def _round_weights(sizes: np.ndarray, av: Optional[np.ndarray],
                   rounds: int) -> np.ndarray:
    """Per-ROUND aggregation weights, (rounds, d) float32: the sample-count
    weights masked by that round's availability and renormalized over the
    silos that are actually present. With full availability every row equals
    `_norm_weights(sizes)` bit-for-bit (same float64 normalize-then-cast),
    so the no-dropout path is unchanged. Computed on host and fed to plans
    as an ARGUMENT — dropout never enters the executable, so every dropout
    pattern shares one compiled plan."""
    s = np.asarray(sizes, np.float64)
    m = np.broadcast_to(s[None, :], (rounds, len(s))).copy()
    if av is not None:
        m = m * np.asarray(av, np.float64)
    tot = m.sum(axis=1, keepdims=True)
    if np.any(tot <= 0):
        bad = int(np.argmax(tot[:, 0] <= 0))
        raise ValueError(
            f"round {bad} has zero available sample mass — the availability "
            "schedule must keep at least one real silo per round "
            "(make_dropout_schedule guarantees this)")
    return (m / tot).astype(np.float32)


# --------------------------------------------------------------------------
# Robust aggregation statistics (hostile-world boundary, DESIGN.md §8)
# --------------------------------------------------------------------------

ROBUST_AGGREGATORS = ("median", "trimmed_mean", "krum")
AGGREGATORS = ("fedavg", "fedprox", "fedsgd") + ROBUST_AGGREGATORS

_MASK_BIG = 1e30        # sentinel pushed into masked-out sort slots; finite
                        # so downstream arithmetic never meets inf/nan


def _masked_sort(vals: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Sort (d, ...) along the silo axis with masked-out silos pushed to the
    top: valid entries occupy sorted positions [0, k) for k = Σ mask."""
    m = mask.reshape((-1,) + (1,) * (vals.ndim - 1))
    v = jnp.where(m > 0, vals.astype(jnp.float32), _MASK_BIG)
    return jnp.sort(v, axis=0)


def masked_median(vals: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Coordinate-wise median over silos with mask=1 (dropped / empty /
    padded silos excluded exactly). k may be a traced scalar."""
    s = _masked_sort(vals, mask)
    k = jnp.sum(mask).astype(jnp.int32)
    lo = jnp.maximum((k - 1) // 2, 0)
    hi = jnp.maximum(k // 2, 0)
    take = lambda i: lax.dynamic_index_in_dim(s, i, 0, keepdims=False)
    return 0.5 * (take(lo) + take(hi))


def masked_trimmed_mean(vals: jnp.ndarray, mask: jnp.ndarray,
                        trim_frac: float) -> jnp.ndarray:
    """Coordinate-wise mean over the valid silos with the floor(k·trim_frac)
    smallest AND largest values dropped per coordinate; the trim is clamped
    so at least one value survives."""
    d = vals.shape[0]
    s = _masked_sort(vals, mask)
    k = jnp.sum(mask).astype(jnp.int32)
    t = jnp.floor(k.astype(jnp.float32) * float(trim_frac)).astype(jnp.int32)
    t = jnp.clip(t, 0, jnp.maximum((k - 1) // 2, 0))
    idx = jnp.arange(d, dtype=jnp.int32)
    keep = ((idx >= t) & (idx < k - t)).astype(jnp.float32)
    kept = jnp.tensordot(keep, s, axes=(0, 0), precision=HIGHEST)
    return kept / jnp.maximum(k - 2 * t, 1).astype(jnp.float32)


def krum_select(flat: jnp.ndarray, mask: jnp.ndarray,
                krum_f: int) -> jnp.ndarray:
    """Krum selection index over (d, P) flattened silo updates: each valid
    silo is scored by the sum of its squared distances to its k−f−2 nearest
    valid peers; the lowest score wins (Blanchard et al., NeurIPS'17).
    Distances between params and between deltas coincide (the shared
    round-start offset cancels), so callers may pass either."""
    d = flat.shape[0]
    f32 = flat.astype(jnp.float32)
    sq = jnp.sum(f32 * f32, axis=1)
    dist = sq[:, None] + sq[None, :] - 2.0 * jnp.matmul(
        f32, f32.T, precision=HIGHEST)
    valid = mask > 0
    pair = valid[:, None] & valid[None, :] & ~jnp.eye(d, dtype=bool)
    dist = jnp.where(pair, jnp.maximum(dist, 0.0), _MASK_BIG)
    k = jnp.sum(mask).astype(jnp.int32)
    nn = jnp.clip(k - int(krum_f) - 2, 1, jnp.maximum(k - 1, 1))
    sd = jnp.sort(dist, axis=1)
    neighbor = (jnp.arange(d, dtype=jnp.int32)[None, :] < nn)
    scores = jnp.sum(jnp.where(neighbor, sd, 0.0), axis=1)
    scores = jnp.where(valid, scores, jnp.inf)
    return jnp.argmin(scores)


def robust_aggregate(stacked: Any, mask: jnp.ndarray, aggregator: str, *,
                     trim_frac: float = 0.2, krum_f: int = 1) -> Any:
    """Robust boundary over a (d, ...) silo-stacked pytree: aggregate only
    the silos with mask=1 (available AND real), ignoring sample weights —
    the classical Byzantine-robust estimators are unweighted by design, so a
    poisoned silo cannot buy influence with a large claimed sample count."""
    if aggregator == "median":
        return jax.tree.map(
            lambda a: masked_median(a, mask).astype(a.dtype), stacked)
    if aggregator == "trimmed_mean":
        return jax.tree.map(
            lambda a: masked_trimmed_mean(a, mask, trim_frac).astype(a.dtype),
            stacked)
    if aggregator == "krum":
        leaves = jax.tree_util.tree_leaves(stacked)
        flat = jnp.concatenate(
            [l.reshape(l.shape[0], -1).astype(jnp.float32) for l in leaves],
            axis=1)
        best = krum_select(flat, mask, krum_f)
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, best, 0, keepdims=False),
            stacked)
    raise ValueError(f"unknown robust aggregator {aggregator!r}; "
                     f"choose one of {ROBUST_AGGREGATORS}")


def apply_silo_scale(stacked: Any, ref: Any, scale: jnp.ndarray) -> Any:
    """Per-silo delta scaling at the boundary: silo i submits
    ref + scale_i·(p_i − ref). The gradient-scaling attacker's injection
    point (core/privacy.py) — and an EXACT no-op at scale=1 (the update is
    written p + (scale−1)·(p − ref), so honest silos add literal 0.0)."""
    def leaf(s, g):
        sc = (scale.astype(jnp.float32) - 1.0).reshape(
            (-1,) + (1,) * (s.ndim - 1))
        delta = s.astype(jnp.float32) - g.astype(jnp.float32)[None]
        return (s.astype(jnp.float32) + sc * delta).astype(s.dtype)
    return jax.tree.map(leaf, stacked, ref)


def round_perms(key, rnd, num_silos: int, epochs: int, n_slots: int,
                silo_ids: Optional[jnp.ndarray] = None):
    """Minibatch schedule for one round: a (d, epochs, n_slots) permutation
    stack derived purely from (seed, round, silo, epoch) via fold_in — the
    same indices whether `rnd` is a concrete int (host loop) or a traced
    scan counter (scan engine). `silo_ids` overrides the silo indices folded
    into the key: a mesh shard holding silos [4..7] of a sharded plan passes
    its GLOBAL ids so its streams match the single-device engine exactly."""
    kr = jax.random.fold_in(key, rnd)
    ids = jnp.arange(num_silos) if silo_ids is None else silo_ids

    def silo(i):
        ki = jax.random.fold_in(kr, i)
        return jax.vmap(
            lambda e: jax.random.permutation(jax.random.fold_in(ki, e),
                                             n_slots))(jnp.arange(epochs))

    return jax.vmap(silo)(ids)


def _detect_per_example(loss_fn, params, padded: PaddedSilos) -> bool:
    """A loss returning shape (batch,) is per-example (maskable); shape ()
    is a black-box batch mean (legacy; valid only without padding)."""
    bs = padded.batch_size
    x_s = jax.ShapeDtypeStruct((bs,) + padded.X.shape[2:], padded.X.dtype)
    y_s = jax.ShapeDtypeStruct((bs,) + padded.Y.shape[2:], padded.Y.dtype)
    out = jax.eval_shape(loss_fn, params, x_s, y_s)
    if out.shape == ():
        return False
    if out.shape == (bs,):
        return True
    raise ValueError(
        f"loss_fn must return a scalar batch mean or a (batch,)-shaped "
        f"per-example vector; got shape {out.shape}")


def _make_batch_loss(loss_fn, per_example: bool, fedprox_mu: float):
    """Masked batch objective shared by every aggregator and engine.

    Per-example losses are weighted by the sample mask (padded slots
    contribute exactly zero to value and gradient); scalar losses are used
    verbatim (the caller guarantees no padding). FedProx adds the proximal
    pull toward the round-start global params."""
    def batch_loss(p, x, y, w, ref):
        if per_example:
            l = loss_fn(p, x, y)
            # tiny-eps denominator guard (see _DEN_EPS): identical to the
            # old max(Σw, 1) for {0,1} masks (mass 0 or ≥ 1), but no longer
            # deflates loss/gradient under fractional sample weights
            loss = jnp.sum(w * l) / jnp.maximum(jnp.sum(w), _DEN_EPS)
        else:
            loss = loss_fn(p, x, y)
        if fedprox_mu:
            loss = loss + fedprox_regularizer(p, ref, fedprox_mu)
        return loss

    return batch_loss


def _make_sgd_step(batch_loss, opt: Optimizer, masked: bool = False):
    """masked=True additionally suppresses the optimizer update for batches
    with ZERO real samples: without the guard an all-padding batch would
    still advance the step counter, decay momentum, and coast parameters on
    stale Adam state — so small ragged silos would take extra effective
    steps. With it, all-padding batches are exact no-ops and a silo's
    training is the sequence of its real-sample batches only."""
    def step(p, opt_state, x, y, w, ref):
        loss, grads = jax.value_and_grad(batch_loss)(p, x, y, w, ref)
        updates, new_state = opt.update(grads, opt_state, p)
        new_p = apply_updates(p, updates)
        if masked:
            has_real = jnp.sum(w) > 0
            new_p = jax.tree.map(
                lambda a, b: jnp.where(has_real, a, b), new_p, p)
            new_state = jax.tree.map(
                lambda a, b: jnp.where(has_real, a, b), new_state, opt_state)
        return new_p, new_state, loss

    return step


def _weighted_silo_mean(stacked: Any, wn: jnp.ndarray) -> Any:
    """Sample-weighted mean over the leading silo dim (wn sums to 1)."""
    return jax.tree.map(
        lambda a: jnp.tensordot(wn, a.astype(jnp.float32), axes=(0, 0),
                                precision=HIGHEST).astype(a.dtype), stacked)


def _stack_trees(trees: Sequence[Any]) -> Any:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


# ==========================================================================
# 1a. Mesh plumbing for sharded plans (DESIGN.md §7)
# ==========================================================================

def default_silo_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes the padded silo dim shards over. When the mesh has both
    "pod" and "data" axes the silo dim spans them jointly and the round
    boundary aggregates hierarchically (intra-pod reduce over "data" first,
    cross-pod over "pod" second — the scarce-DCI comm structure of TFL,
    arXiv:1912.11187). A "model" axis is never a silo axis: model-parallel
    rows inside one silo group stay replicated w.r.t. the silo stack."""
    names = tuple(mesh.axis_names)
    both = tuple(a for a in ("pod", "data") if a in names)
    return both if both else names[:1]


def _mesh_axis_sizes(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def num_silo_shards(mesh, silo_axes: Optional[Sequence[str]] = None) -> int:
    """How many ways a sharded plan splits the silo axis (the padded silo
    count must be a multiple of this; run_federated pads it up)."""
    axes = tuple(silo_axes) if silo_axes else default_silo_axes(mesh)
    sizes = _mesh_axis_sizes(mesh)
    missing = [a for a in axes if a not in sizes]
    if missing:
        raise ValueError(f"silo axes {missing} not in mesh axes "
                         f"{tuple(mesh.axis_names)}")
    return int(np.prod([sizes[a] for a in axes]))


def _psum_tree(tree: Any, axes: Sequence[str]) -> Any:
    """Hierarchical all-reduce at the round boundary: innermost (intra-node)
    axis first, outer (cross-node) axes after. For axes=("pod", "data") that
    is one psum over "data" inside each pod, then one over "pod" across the
    DCI — exactly one weighted all-reduce per leaf per level, and the ONLY
    collectives a sharded plan with a WEIGHTED aggregator contains."""
    for ax in reversed(tuple(axes)):
        tree = jax.tree.map(lambda a: lax.psum(a, ax), tree)
    return tree


def _all_gather_tree(tree: Any, axes: Sequence[str]) -> Any:
    """Hierarchical tiled all-gather of the silo dim at a ROBUST round
    boundary (DESIGN.md §8): robust statistics are order statistics over the
    full cross-shard silo population, which a psum of partial sums cannot
    express — every shard must see every silo's submission. Same
    innermost-axis-first order as _psum_tree; after the gather each shard
    holds the full (d, …) stack and computes the identical robust aggregate
    redundantly (replicated output, no further collective)."""
    for ax in reversed(tuple(axes)):
        tree = jax.tree.map(
            lambda a: lax.all_gather(a, ax, axis=0, tiled=True), tree)
    return tree


# ==========================================================================
# 1b. The compiled-plan cache: shape-bucketed executable reuse
# ==========================================================================

def bucket_pow2(n: int) -> int:
    """Round n up to the next power of two (the default bucket policy):
    ≤ 2× padding waste, log-many buckets over any tenant population."""
    return 1 << (max(int(n), 1) - 1).bit_length()


def _tree_signature(tree: Any) -> Tuple:
    """Hashable (structure, leaf shapes/dtypes) fingerprint of a pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (str(treedef),
            tuple((tuple(np.shape(l)), str(jnp.asarray(l).dtype))
                  for l in leaves))


class PlanCache:
    """LRU cache of compiled FL plans keyed on the full compile signature.

    A plan (make_fl_plan) takes all tenant data as arguments, so two
    run_federated calls whose padded layouts land in the same shape bucket
    — (num_silos, num_batches, batch_size, feature/target shapes, params
    signature) — and share the same static config (aggregator, rounds,
    epochs, reset_opt, collect, per_example, fedprox_mu, loss/opt identity)
    reuse ONE jitted callable and therefore ONE XLA executable. Bucketing
    (bucket_silos / bucket_batches, default next-pow2) rounds the silo and
    batch axes UP so a new tenant's ragged shapes hit an existing
    executable instead of compiling a fresh one.

    Counters: hits / misses / evictions; a miss builds (and on first call
    compiles) a new plan, so `misses` == number of executables built
    through this cache.
    """

    def __init__(self, max_plans: int = 64,
                 bucket_silos: Callable[[int], int] = bucket_pow2,
                 bucket_batches: Callable[[int], int] = bucket_pow2):
        from collections import OrderedDict
        self._plans: "OrderedDict[Tuple, Tuple]" = OrderedDict()
        self.max_plans = max_plans
        self.bucket_silos = bucket_silos
        self.bucket_batches = bucket_batches
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
    """The process-wide plan cache used by ``run_federated(cache=True)``
    and the FedDCL.fit() API."""
    global _DEFAULT_PLAN_CACHE
    if _DEFAULT_PLAN_CACHE is None:
        _DEFAULT_PLAN_CACHE = PlanCache()
    return _DEFAULT_PLAN_CACHE


def clear_plan_cache() -> None:
    if _DEFAULT_PLAN_CACHE is not None:
        _DEFAULT_PLAN_CACHE.clear()


# ==========================================================================
# 2. The unified federated engine
# ==========================================================================

@dataclass
class FLResult:
    params: Any
    history: List[Dict[str, float]]
    cache_stats: Optional[Dict[str, int]] = None   # set when a PlanCache ran


def fedavg_average(params_list: Sequence[Any], weights: Sequence[float]) -> Any:
    w = np.asarray(weights, np.float64)
    w = w / max(w.sum(), _DEN_EPS)
    return jax.tree.map(
        lambda *ps: sum(wi * p.astype(jnp.float32) for wi, p in zip(w, ps)).astype(ps[0].dtype),
        *params_list,
    )


def run_federated(
    loss_fn: Callable[[Any, jnp.ndarray, jnp.ndarray], jnp.ndarray],
    init_params: Any,
    silo_data: Sequence[Tuple[np.ndarray, np.ndarray]],
    *,
    opt: Optimizer,
    rounds: int,
    local_epochs: int,
    batch_size: int = 32,
    aggregator: str = "fedavg",
    fedprox_mu: float = 0.0,
    seed: int = 0,
    eval_fn: Optional[Callable[[Any], Dict[str, float]]] = None,
    engine: str = "host",
    per_example: Optional[bool] = None,
    reset_opt_per_round: bool = True,
    pad_fill: float = 0.0,
    cache: Any = None,
    loss_id: Optional[Tuple] = None,
    opt_id: Optional[Tuple] = None,
    mesh=None,
    silo_axes: Optional[Sequence[str]] = None,
    eval_chunk: int = 8,
    dropout_rate: float = 0.0,
    availability: Optional[np.ndarray] = None,
    silo_scale: Optional[Sequence[float]] = None,
    trim_frac: float = 0.2,
    krum_f: int = 1,
) -> FLResult:
    """Federated training over host-resident silo datasets — the ONE trainer
    behind FedAvg / FedProx / FedSGD / FedDCL and (via baselines.sgd_train)
    Centralized / Local / DC.

    loss_fn takes (params, x, y) and returns either a (batch,) per-example
    loss vector (preferred: ragged silos are zero-padded and masked) or a
    scalar batch mean (legacy; only valid when no padding is needed, i.e.
    every silo has the same size divisible by batch_size). `per_example` is
    auto-detected from the output shape when None.

    engine="host" is the paper-faithful per-batch-dispatch loop;
    engine="scan" compiles the whole schedule into one lax.scan program.
    Both use the same jax.random batch schedule and agree to float
    tolerance for the same seed.

    reset_opt_per_round=False carries silo optimizer state across rounds
    (used by sgd_train, where rounds are plain epochs).

    cache=True (or a PlanCache instance) routes the scan engine through the
    shape-bucketed compiled-plan cache (DESIGN.md §6): the padded layout is
    rounded UP to the cache's silo/batch buckets and the compiled
    executable is shared with every other call whose compile signature
    matches — a sweep's 2nd–Nth configs then cost milliseconds. Because
    bucketing changes n_slots (and so the minibatch schedule), the bucketed
    layout is the canonical layout of a cached run: two cached runs agree
    bitwise, and they agree with the host engine on the SAME bucketed
    layout to engine tolerance. loss_id / opt_id give the loss/optimizer a
    stable cache identity (e.g. ("mlp_per_example_loss", task) /
    ("adamw", lr)); when omitted, object identity is used, which only hits
    when the caller reuses the exact same callables. cache_stats on the
    result records {hit, hits, misses, evictions, plans}.

    mesh (scan engine only) runs the FL phase sharded: the padded silo
    stack is placed over the mesh's silo axes (silo_axes, default
    `default_silo_axes` — ("pod", "data") jointly when both exist) via
    shard_map, with hierarchical round-boundary psums as the ONLY
    collectives (DESIGN.md §7). The silo count is padded up to a multiple
    of the silo-shard count with empty no-op silos, so results match the
    unsharded engine to float tolerance. eval_chunk bounds the eval path's
    memory: with eval_fn, per-round params stream to host eval_chunk
    rounds per dispatch instead of materializing a (rounds, |params|)
    stack on device.

    HOSTILE-WORLD options (DESIGN.md §8): aggregator may also be one of
    `ROBUST_AGGREGATORS` — "median" / "trimmed_mean" (trim_frac per tail) /
    "krum" (krum_f tolerated Byzantine silos) compute an UNWEIGHTED robust
    statistic over the available silos' submissions instead of the
    sample-weighted mean (sharded: via a cross-silo all_gather instead of
    the psum). dropout_rate draws a per-(round, silo) Bernoulli availability
    schedule on host (`make_dropout_schedule`; `availability` passes an
    explicit (rounds, num_real_silos) {0,1} matrix instead); unavailable
    silos train nothing that round (exact no-op under the §4 mask rules)
    and carry zero aggregation weight. silo_scale (num_real_silos,)
    multiplies each silo's submitted round delta — the gradient-scaling
    attacker's injection point (core/privacy.py); 1.0 is an exact no-op.
    """
    if aggregator not in AGGREGATORS:
        raise ValueError(f"unknown aggregator {aggregator!r}; "
                         f"choose one of {AGGREGATORS}")
    if engine not in ("host", "scan"):
        raise ValueError(f"unknown engine {engine!r}; choose 'host' or 'scan'")
    if mesh is not None and engine != "scan":
        raise ValueError("mesh=... requires engine='scan' — the host engine "
                         "is a per-batch dispatch loop and cannot shard the "
                         "silo axis")
    with obs.span("feddcl.fl.prepare"):
        plan_cache: Optional[PlanCache] = None
        if cache is not None and cache is not False:
            if engine != "scan":
                raise ValueError("cache=... requires engine='scan' — the "
                                 "plan cache stores compiled scan-engine "
                                 "executables")
            plan_cache = cache if isinstance(cache, PlanCache) \
                else default_plan_cache()
        axes: Optional[Tuple[str, ...]] = None
        shards = 1
        if mesh is not None:
            axes = tuple(silo_axes) if silo_axes else default_silo_axes(mesh)
            shards = num_silo_shards(mesh, axes)

        def shard_multiple(d: int) -> int:
            """Round a silo count up to the silo-shard count (extra silos are
            empty → exact no-ops under the mask rules)."""
            return -(-d // shards) * shards

        if plan_cache is not None:
            n_max = max(np.asarray(x).shape[0] for x, _ in silo_data)
            if aggregator == "fedsgd":
                bs_eff: Optional[int] = plan_cache.bucket_batches(n_max)
                min_nb = 1
            else:
                bs_eff = batch_size
                min_nb = plan_cache.bucket_batches(-(-n_max // batch_size))
            padded = pad_silo_data(
                silo_data, bs_eff, fill=pad_fill, min_batches=min_nb,
                min_silos=shard_multiple(
                    plan_cache.bucket_silos(len(silo_data))))
        else:
            padded = pad_silo_data(
                silo_data, None if aggregator == "fedsgd" else batch_size,
                fill=pad_fill,
                min_silos=shard_multiple(len(silo_data)) if shards > 1 else 0)
        if per_example is None:
            per_example = _detect_per_example(loss_fn, init_params, padded)
        if not per_example and padded.has_padding:
            raise ValueError(
                f"silo sizes {padded.sizes.astype(int).tolist()} need "
                f"padding to {padded.n_slots} slots, which a scalar "
                "(batch-mean) loss cannot mask — pass a per-example loss "
                "(returning a (batch,) vector, e.g. "
                "models.mlp.mlp_per_example_loss) or equal-size silos "
                "divisible by batch_size")
        if availability is not None and dropout_rate:
            raise ValueError("pass either dropout_rate or an explicit "
                             "availability matrix, not both")
        av: Optional[np.ndarray] = None
        if availability is not None:
            av = np.asarray(availability, np.float32)
            if av.shape[0] != rounds or av.shape[1] > padded.num_silos:
                raise ValueError(
                    f"availability must be (rounds, "
                    f"num_silos≤{padded.num_silos}) for rounds={rounds}; "
                    f"got {av.shape}")
            if av.shape[1] < padded.num_silos:
                # bucket-padding silos are empty → never available
                av = np.concatenate(
                    [av, np.zeros((rounds, padded.num_silos - av.shape[1]),
                                  np.float32)], axis=1)
        elif dropout_rate:
            # draw over the REAL silo count so the schedule is invariant to
            # bucket/shard padding (a d=6 tenant gets the same draws whether
            # the layout pads to 6, 8, or 16 silos), then zero-pad the
            # columns
            d_real = len(silo_data)
            av = make_dropout_schedule(seed, rounds, d_real,
                                       float(dropout_rate),
                                       sizes=padded.sizes[:d_real])
            if padded.num_silos > d_real:
                av = np.concatenate(
                    [av, np.zeros((rounds, padded.num_silos - d_real),
                                  np.float32)], axis=1)
        scale_vec: Optional[np.ndarray] = None
        if silo_scale is not None:
            s = np.asarray(silo_scale, np.float32).reshape(-1)
            if s.shape[0] > padded.num_silos:
                raise ValueError(f"silo_scale has {s.shape[0]} entries for "
                                 f"{padded.num_silos} silos")
            scale_vec = np.ones(padded.num_silos, np.float32)
            scale_vec[:s.shape[0]] = s
        # dropout makes whole rounds all-padding for the dropped silos, so
        # the exact-no-op step guard must be on even when the layout itself
        # is dense
        needs_mask = padded.has_padding or (
            av is not None and not np.all(av > 0))
        robust = aggregator in ROBUST_AGGREGATORS
        mu = fedprox_mu if aggregator == "fedprox" else 0.0
        batch_loss = _make_batch_loss(loss_fn, per_example, mu)
        obs.count("feddcl.fl.sample_steps",
                  int(padded.sizes.sum()) * local_epochs * rounds)
        obs.count("feddcl.fl.slot_steps",
                  padded.num_silos * padded.n_slots * local_epochs * rounds)
        plan = was_hit = None
        if plan_cache is not None:
            mode = "chunk" if eval_fn is not None else "none"
            # mesh descriptor: a sharded and an unsharded plan must never
            # alias, nor two plans on meshes of different shape/axis
            # names/silo axes
            mesh_sig = None if mesh is None else (
                tuple(mesh.axis_names),
                tuple(int(s) for s in mesh.devices.shape), axes)
            key = (
                padded.num_silos, padded.num_batches, padded.batch_size,
                tuple(padded.X.shape[2:]), str(padded.X.dtype),
                tuple(padded.Y.shape[2:]), str(padded.Y.dtype),
                _tree_signature(init_params),
                # chunk plans step nr rounds per dispatch with rounds never
                # baked into the executable, so they are rounds-agnostic:
                # rounds=50 and rounds=200 share one cached plan
                aggregator, None if mode == "chunk" else rounds,
                local_epochs, bool(reset_opt_per_round),
                mode, bool(per_example), float(mu),
                # robust-config enters the EXECUTABLE (trim/f are trace-time
                # constants), so plans differing only there must never alias;
                # dropout/scale are runtime ARGUMENTS and stay out of the key
                (float(trim_frac), int(krum_f)) if robust else None,
                loss_id if loss_id is not None else ("id", id(loss_fn)),
                opt_id if opt_id is not None else ("id", id(opt)),
                mesh_sig,
            )
            plan, was_hit = plan_cache.lookup(
                key,
                lambda: make_fl_plan(
                    num_silos=padded.num_silos,
                    num_batches=padded.num_batches,
                    batch_size=padded.batch_size, opt=opt,
                    batch_loss=batch_loss, rounds=rounds,
                    local_epochs=local_epochs, aggregator=aggregator,
                    per_example=per_example,
                    reset_opt=reset_opt_per_round, collect=mode,
                    masked=True, mesh=mesh, silo_axes=axes,
                    trim_frac=trim_frac, krum_f=krum_f),
                pins=(loss_fn, opt))
        elif engine == "scan":
            plan = make_fl_plan(
                num_silos=padded.num_silos, num_batches=padded.num_batches,
                batch_size=padded.batch_size, opt=opt, batch_loss=batch_loss,
                rounds=rounds, local_epochs=local_epochs,
                aggregator=aggregator, per_example=per_example,
                reset_opt=reset_opt_per_round,
                collect="chunk" if eval_fn is not None else "none",
                masked=needs_mask, mesh=mesh, silo_axes=axes,
                trim_frac=trim_frac, krum_f=krum_f)
        if plan is not None:
            args = _plan_args(padded, seed, rounds, availability=av,
                              silo_scale=scale_vec)
    if plan is None:
        return _run_host(batch_loss, init_params, padded, opt=opt,
                         rounds=rounds, local_epochs=local_epochs,
                         aggregator=aggregator, seed=seed, eval_fn=eval_fn,
                         per_example=per_example,
                         reset_opt=reset_opt_per_round,
                         availability=av, silo_scale=scale_vec,
                         trim_frac=trim_frac, krum_f=krum_f,
                         masked=needs_mask)
    res = _run_scan(plan, init_params, args, rounds=rounds, eval_fn=eval_fn,
                    eval_chunk=eval_chunk)
    if plan_cache is not None:
        res.cache_stats = {"hit": was_hit, **plan_cache.stats()}
    return res


# --------------------------------------------------------------------------
# 2a. engine="host": NumPy-orchestrated reference (one dispatch per batch)
# --------------------------------------------------------------------------

def _run_host(batch_loss, init_params, padded: PaddedSilos, *, opt, rounds,
              local_epochs, aggregator, seed, eval_fn, per_example,
              reset_opt, availability=None, silo_scale=None,
              trim_frac: float = 0.2, krum_f: int = 1,
              masked: Optional[bool] = None) -> FLResult:
    d, nb, bs = padded.num_silos, padded.num_batches, padded.batch_size
    key = jax.random.PRNGKey(seed)
    if masked is None:
        masked = padded.has_padding
    step = jax.jit(_make_sgd_step(batch_loss, opt, masked=masked))
    grad_fn = jax.jit(jax.value_and_grad(batch_loss))
    X, Y, w = padded.X, padded.Y, padded.w
    robust = aggregator in ROBUST_AGGREGATORS
    wr = _round_weights(padded.sizes, availability, rounds)   # (rounds, d)
    scale = None if silo_scale is None else \
        jnp.asarray(np.asarray(silo_scale, np.float32))

    gp = init_params
    fedsgd_state = opt.init(gp) if aggregator == "fedsgd" else None
    opt_states: List[Any] = [opt.init(gp) for _ in range(d)] if not reset_opt else []
    history: List[Dict[str, float]] = []
    for rnd in range(rounds):
        wr_r = wr[rnd]
        if aggregator == "fedsgd":
            losses, grads = [], []
            for i in range(d):
                li, gi = grad_fn(gp, jnp.asarray(X[i]), jnp.asarray(Y[i]),
                                 jnp.asarray(w[i]), gp)
                losses.append(li)
                grads.append(gi)
            g = _stack_trees(grads)
            if scale is not None:
                g = jax.tree.map(
                    lambda a: (a.astype(jnp.float32) * scale.reshape(
                        (-1,) + (1,) * (a.ndim - 1))).astype(a.dtype), g)
            g = _weighted_silo_mean(g, jnp.asarray(wr_r))
            updates, fedsgd_state = opt.update(g, fedsgd_state, gp)
            gp = apply_updates(gp, updates)
            round_loss = float(jnp.sum(jnp.asarray(wr_r) * jnp.stack(losses)))
        else:
            perms = np.asarray(
                round_perms(key, rnd, d, local_epochs, padded.n_slots))
            locals_: List[Any] = []
            final_losses = np.zeros(d)
            for i in range(d):
                if wr_r[i] <= 0:
                    # dropped or empty silo (wr_r > 0 ⟺ real ∧ available):
                    # trains nothing this round — the scan engine reaches the
                    # same state via zeroed sample masks + the masked-step
                    # no-op guard
                    locals_.append(gp)
                    continue
                p = gp
                o = opt.init(p) if reset_opt else opt_states[i]
                for e in range(local_epochs):
                    idx = perms[i, e].reshape(nb, bs)
                    # keep per-batch losses on device; only the final-epoch
                    # weighted mean is pulled to host (ONE sync per silo per
                    # round, like the pre-engine loop)
                    ep_losses, ep_ws = [], []
                    for b in range(nb):
                        sl = idx[b]
                        p, o, loss = step(p, o, jnp.asarray(X[i][sl]),
                                          jnp.asarray(Y[i][sl]),
                                          jnp.asarray(w[i][sl]), gp)
                        if e == local_epochs - 1:
                            ep_losses.append(loss)
                            ep_ws.append(float(w[i][sl].sum())
                                         if per_example else float(bs))
                    if e == local_epochs - 1:
                        num = sum(l * bw for l, bw in zip(ep_losses, ep_ws))
                        final_losses[i] = float(num) / max(sum(ep_ws),
                                                           _DEN_EPS)
                locals_.append(p)
                if not reset_opt:
                    opt_states[i] = o
            sp = _stack_trees(locals_)
            if scale is not None:
                sp = apply_silo_scale(sp, gp, scale)
            if robust:
                mask = jnp.asarray((wr_r > 0).astype(np.float32))
                gp = robust_aggregate(sp, mask, aggregator,
                                      trim_frac=trim_frac, krum_f=krum_f)
            else:
                gp = _weighted_silo_mean(sp, jnp.asarray(wr_r))
            round_loss = float(np.sum(np.float64(wr_r) * final_losses))
        rec = {"round": rnd, "loss": round_loss}
        if eval_fn is not None:
            rec.update(eval_fn(gp))
        history.append(rec)
    return FLResult(params=gp, history=history)


# --------------------------------------------------------------------------
# 2b. engine="scan": the whole FL phase as one compiled program
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class StreamedPlan:
    """Chunked bounded-memory form of a compiled FL plan (collect="chunk").

    ``step(carry, X, Y, w, wr_chunk, scale, key, rnd0, nr)`` advances ``nr``
    rounds (static) starting at round ``rnd0`` (traced; ``wr_chunk`` is the
    matching (nr, d) slice of the per-round weights) and returns
    ``(carry, (losses, params_per_round))`` where the stacked params have
    leading dim ``nr`` — the CHUNK size, never the total rounds. The eval
    path's peak extra memory is chunk × |params| instead of the old
    rounds × |params| stack, and because total rounds never enters the
    compiled program, one chunk executable serves every round budget.
    ``carry_init(init_params)`` builds the opaque cross-chunk training
    state (a donation-safe private copy on accelerators — ``step`` donates
    its carry so chunks recycle buffers); ``carry_params(carry)`` reads the
    current global params out of it."""
    step: Callable
    carry_init: Callable
    carry_params: Callable


def _resolve_collect(collect, collect_params) -> str:
    mode = collect if collect is not None else \
        ("stack" if collect_params else "none")
    if mode not in ("none", "stack", "chunk"):
        raise ValueError(f"unknown collect mode {mode!r}; "
                         "choose 'none', 'stack', or 'chunk'")
    return mode


def make_fl_plan(*, num_silos: int, num_batches: int, batch_size: int,
                 opt: Optimizer, batch_loss, rounds: int, local_epochs: int,
                 aggregator: str = "fedavg", per_example: bool = True,
                 reset_opt: bool = True, collect_params: bool = False,
                 masked: bool = True, collect: Optional[str] = None,
                 mesh=None, silo_axes: Optional[Sequence[str]] = None,
                 trim_frac: float = 0.2, krum_f: int = 1):
    """Build a compiled whole-FL-phase PLAN: a jitted

        ``plan(init_params, X, Y, w, wr, scale, key) -> (final_params, ys)``

    where X (d, n_slots, …), Y, w are the padded silo stack, wr (rounds, d)
    the PER-ROUND normalized aggregation weights (``_round_weights`` —
    every row equals ``_norm_weights(sizes)`` when no silo drops out; a
    zero entry marks a silo unavailable that round and suppresses its local
    training entirely), scale (d,) the per-silo delta multiplier
    (``apply_silo_scale``; all-ones in honest runs, the attack injection
    point otherwise), key the PRNG key that seeds the batch schedule, and
    ys the (rounds,) loss vector. Unlike a data-closure runner, ALL tenant
    data enters as arguments, so one plan compiles ONE executable per
    input-shape set and every tenant whose padded shapes land in the same
    bucket reuses it — the unit the PlanCache stores. Because wr and scale
    are arguments too, every dropout pattern and every attack configuration
    shares the same executable.

    aggregator ∈ ROBUST_AGGREGATORS swaps the round boundary from the
    weighted mean to a robust statistic over the available silos
    (trim_frac / krum_f are its trace-time constants — part of the plan's
    cache identity). Sharded robust plans all_gather the silo submissions
    instead of psumming partial weighted sums (DESIGN.md §8).

    collect (back-compat bool ``collect_params`` maps onto it):
      "none"  — ys is the (rounds,) loss vector (default).
      "stack" — ys is (losses, per-round params stacked (rounds, |params|)).
                LEGACY: materializes the full stack on device; kept for the
                streamed-vs-stacked regression tests only.
      "chunk" — returns a StreamedPlan whose step scans a CHUNK of rounds
                and emits only that chunk's params — the bounded-memory
                eval path (_run_scan streams chunks to host and keeps only
                scalar metrics).

    mesh/silo_axes (DESIGN.md §7): with a mesh, the whole FL phase runs
    under shard_map with the padded silo dim sharded over silo_axes
    (default ``default_silo_axes``: ("pod", "data") jointly when both
    exist), params/PRNG replicated, and the entire local phase
    collective-free per shard — each shard trains its d/shards silos with
    their GLOBAL silo ids folded into the batch schedule, so the results
    match the single-device plan to float tolerance. The only collectives
    are the round-boundary weighted psums of fedavg_sync (one per leaf per
    silo-axis level, hierarchical: intra-pod first, cross-pod second).
    num_silos must be divisible by the silo-shard count (run_federated pads
    with empty no-op silos)."""
    d, nb, bs = num_silos, num_batches, batch_size
    n_slots = nb * bs
    mode = _resolve_collect(collect, collect_params)
    axes: Optional[Tuple[str, ...]] = None
    if mesh is not None:
        axes = tuple(silo_axes) if silo_axes else default_silo_axes(mesh)
        shards = num_silo_shards(mesh, axes)
        if d % shards:
            raise ValueError(
                f"num_silos={d} is not divisible by the {shards}-way silo "
                f"mesh {axes}; pad the silo stack (pad_silo_data min_silos, "
                "as run_federated does) so every shard holds d/shards silos")
    step = _make_sgd_step(batch_loss, opt, masked=masked)
    vstep = jax.vmap(step, in_axes=(0, 0, 0, 0, 0, None))
    gather = jax.vmap(lambda a, i: a[i])                 # (d, n_slots, …) × (d, B)

    def reduce_tree(stacked: Any, wn) -> Any:
        """fedavg_sync in plan form: the weighted mean over the GLOBAL silo
        axis — a local f32 tensordot over this shard's silos plus (when
        sharded) the hierarchical round-boundary psum; wn sums to 1 over
        all d silos, so the psum of partial weighted sums IS the mean."""
        part = jax.tree.map(
            lambda a: jnp.tensordot(wn, a.astype(jnp.float32), axes=(0, 0),
                                    precision=HIGHEST),
            stacked)
        if axes is not None:
            part = _psum_tree(part, axes)
        return jax.tree.map(lambda p, s: p.astype(s.dtype), part, stacked)

    def reduce_sum(x):
        return _psum_tree(x, axes) if axes is not None else x

    def local_phase(gp, so, perms, X, Y, w):
        """E epochs × nb batches of vmapped silo steps over this shard's
        silos (perms: this shard's (dl, E, n_slots) schedule slice); returns
        trained silo params/opt state and per-silo final-epoch loss.
        Contains NO collective and NO PRNG: everything is vmapped over the
        local silo dim with per-silo masks."""
        dl = perms.shape[0]
        bidx = perms.reshape(dl, local_epochs, nb, bs).transpose(1, 2, 0, 3)

        def epoch_body(c, eb):                            # eb: (nb, dl, bs)
            def batch_body(c2, ib):                       # ib: (dl, bs)
                sp2, so2 = c2
                xb, yb, wb = gather(X, ib), gather(Y, ib), gather(w, ib)
                sp2, so2, losses = vstep(sp2, so2, xb, yb, wb, gp)
                bw = jnp.sum(wb, axis=1) if per_example \
                    else jnp.full((dl,), float(bs))
                return (sp2, so2), (losses * bw, bw)

            c, (ls, ws) = lax.scan(batch_body, c, eb)
            # tiny-eps guard (_DEN_EPS): identical for {0,1} masks, no
            # silent deflation when an epoch's real weight mass is < 1
            ep_loss = jnp.sum(ls, 0) / jnp.maximum(jnp.sum(ws, 0), _DEN_EPS)
            return c, ep_loss

        (sp, so), ep_losses = lax.scan(
            epoch_body, (silo_replicate(gp, dl), so), bidx)
        return sp, so, ep_losses[-1]                      # (dl,)

    robust = aggregator in ROBUST_AGGREGATORS

    def boundary(sp, gp, wr_r, scale):
        """Round-boundary sync of this shard's trained silo params sp:
        apply the per-silo delta scaling (attack injection; exact no-op at
        scale=1), then either the weighted mean (one psum per leaf per
        level when sharded) or — for robust aggregators — a cross-silo
        all_gather followed by the masked robust statistic, computed
        redundantly per shard on identical gathered inputs (replicated
        output, no further collective)."""
        sp = apply_silo_scale(sp, gp, scale)
        if not robust:
            return reduce_tree(sp, wr_r)
        avail = (wr_r > 0).astype(jnp.float32)
        if axes is not None:
            sp, avail = _all_gather_tree((sp, avail), axes)
        return robust_aggregate(sp, avail, aggregator,
                                trim_frac=trim_frac, krum_f=krum_f)

    def round_step(carry, perms, X, Y, w, wr_r, scale):
        """One full round on this shard's silo slice (perms: this round's
        (dl, E, n_slots) schedule; wr_r: this round's (dl,) weight row —
        zero entries are silos unavailable this round, whose sample masks
        are zeroed so local training is an exact no-op). Returns
        (carry, round_loss, global_params)."""
        if aggregator == "fedsgd":
            gp, fs = carry
            losses, grads = jax.vmap(
                lambda x, y, wi: jax.value_and_grad(batch_loss)(gp, x, y,
                                                                wi, gp)
            )(X, Y, w)
            grads = jax.tree.map(
                lambda a: (a.astype(jnp.float32) * scale.reshape(
                    (-1,) + (1,) * (a.ndim - 1))).astype(a.dtype), grads)
            g = reduce_tree(grads, wr_r)
            updates, fs = opt.update(g, fs, gp)
            gp = apply_updates(gp, updates)
            return (gp, fs), reduce_sum(jnp.sum(wr_r * losses)), gp
        # availability suppression: w·1.0 is bit-exact for present silos,
        # absent silos get all-zero masks → every batch is an exact no-op
        # under the masked-step guard (run_federated forces masked=True
        # whenever any wr entry is zero)
        w_eff = w * (wr_r > 0).astype(w.dtype)[:, None]
        if reset_opt:
            gp = carry
            so = jax.vmap(opt.init)(silo_replicate(gp, X.shape[0]))
            sp, _, final_losses = local_phase(gp, so, perms, X, Y, w_eff)
            gp = boundary(sp, gp, wr_r, scale)
            return gp, reduce_sum(jnp.sum(wr_r * final_losses)), gp
        gp, so = carry
        sp, so, final_losses = local_phase(gp, so, perms, X, Y, w_eff)
        gp = boundary(sp, gp, wr_r, scale)
        return (gp, so), reduce_sum(jnp.sum(wr_r * final_losses)), gp

    own_state = aggregator == "fedsgd" or not reset_opt

    def carry_init_traced(gp, dl):
        if aggregator == "fedsgd":
            return (gp, opt.init(gp))
        if reset_opt:
            return gp
        return (gp, jax.vmap(opt.init)(silo_replicate(gp, dl)))

    def carry_params(carry):
        return carry[0] if own_state else carry

    def data_specs(X, Y, w):
        """silo-axis sharding for the padded tenant stacks: leading dim over
        the (possibly hierarchical) silo axes, everything else shard-local
        (shardingx.policy.batch_spec, federated tuple form). The last two
        entries cover wr (rounds, d — rounds replicated, silo dim sharded)
        and scale (d,)."""
        return (batch_spec(mesh, federated=True, silo_axis=axes, ndim=X.ndim),
                batch_spec(mesh, federated=True, silo_axis=axes, ndim=Y.ndim),
                batch_spec(mesh, federated=True, silo_axis=axes, ndim=w.ndim),
                P(None, axes), P(axes))

    def carry_specs(carry):
        rep = lambda t: jax.tree.map(lambda _: P(), t)
        if aggregator == "fedsgd":
            return (rep(carry[0]), rep(carry[1]))
        if reset_opt:
            return rep(carry)
        silo = jax.tree.map(
            lambda l: P(axes, *([None] * (l.ndim - 1))), carry[1])
        return (rep(carry[0]), silo)

    def round_body_of(key, emit, X, Y, w, scale):
        """Scan body over (rnd, wr) xs: rnd is the scalar round index and
        wr_r this round's (dl,) aggregation-weight row. The batch schedule
        is derived in-scan from (key, rnd, GLOBAL silo id) — a sharded plan
        offsets its local silos by the shard index, so every shard draws
        exactly the streams the single-device plan draws for those silos."""
        def round_body(c, x):
            rnd, wr_r = x
            pr = None
            if aggregator != "fedsgd":
                dl = X.shape[0]
                ids = jnp.arange(dl)
                if axes is not None:
                    ids = ids + lax.axis_index(axes) * dl
                pr = round_perms(key, rnd, dl, local_epochs, n_slots,
                                 silo_ids=ids)
            c, rl, gp = round_step(c, pr, X, Y, w, wr_r, scale)
            return c, emit(rl, gp)
        return round_body

    if mode in ("none", "stack"):
        emit = (lambda rl, gp: (rl, gp)) if mode == "stack" \
            else (lambda rl, gp: rl)

        @jax.jit
        def plan(init_params, X, Y, w, wr, scale, key):
            def whole(init_params, X, Y, w, wr, scale, key):
                carry0 = carry_init_traced(init_params, X.shape[0])
                c, ys = lax.scan(round_body_of(key, emit, X, Y, w, scale),
                                 carry0, (jnp.arange(rounds), wr))
                return carry_params(c), ys

            if axes is None:
                return whole(init_params, X, Y, w, wr, scale, key)
            sx, sy, sw, swr, ssc = data_specs(X, Y, w)
            return jax.shard_map(whole, mesh=mesh,
                                 in_specs=(P(), sx, sy, sw, swr, ssc, P()),
                                 out_specs=P(), check_vma=False)(
                init_params, X, Y, w, wr, scale, key)

        return plan

    # mode == "chunk": the bounded-memory streamed plan; wr arrives as this
    # chunk's (nr, d) ROW SLICE (the driver slices wr[rnd0:rnd0+nr]) so
    # total rounds still never enters the executable
    def chunk_step(carry, X, Y, w, wr, scale, key, rnd0, nr):
        emit = lambda rl, gp: (rl, gp)

        def whole(carry, X, Y, w, wr, scale, key, rnd0):
            return lax.scan(round_body_of(key, emit, X, Y, w, scale),
                            carry, (rnd0 + jnp.arange(nr), wr))

        if axes is None:
            return whole(carry, X, Y, w, wr, scale, key, rnd0)
        sx, sy, sw, swr, ssc = data_specs(X, Y, w)
        cs = carry_specs(carry)
        return jax.shard_map(whole, mesh=mesh,
                             in_specs=(cs, sx, sy, sw, swr, ssc, P(), P()),
                             out_specs=(cs, P()), check_vma=False)(
            carry, X, Y, w, wr, scale, key, rnd0)

    # CPU has no buffer donation; elsewhere chunks recycle carry buffers
    donate = () if jax.default_backend() == "cpu" else (0,)
    jitted_step = jax.jit(chunk_step, static_argnums=(8,),
                          donate_argnums=donate)

    def carry_init(init_params):
        # private copy so donation can never invalidate the caller's params
        gp = jax.tree.map(jnp.array, init_params)
        if aggregator == "fedsgd":
            return (gp, opt.init(gp))
        if reset_opt:
            return gp
        return (gp, jax.vmap(opt.init)(silo_replicate(gp, d)))

    return StreamedPlan(step=jitted_step, carry_init=carry_init,
                        carry_params=carry_params)


def _plan_args(padded: PaddedSilos, seed: int, rounds: int, *,
               availability: Optional[np.ndarray] = None,
               silo_scale: Optional[np.ndarray] = None):
    """Device arguments a plan consumes for one tenant's padded stack:
    (X, Y, w, wr, scale, key). availability (rounds, d) {0,1} folds into
    the per-round weights wr; silo_scale (d,) defaults to all-ones
    (honest)."""
    wr = _round_weights(padded.sizes, availability, rounds)
    scale = (np.ones(padded.num_silos, np.float32) if silo_scale is None
             else np.asarray(silo_scale, np.float32))
    return (jnp.asarray(padded.X), jnp.asarray(padded.Y),
            jnp.asarray(padded.w), jnp.asarray(wr), jnp.asarray(scale),
            jax.random.PRNGKey(seed))


def lower_fl_plan(plan, init_params, padded: PaddedSilos, *, rounds: int,
                  seed: int = 0, availability: Optional[np.ndarray] = None,
                  silo_scale: Optional[np.ndarray] = None,
                  eval_chunk: int = 8):
    """Lower a `make_fl_plan` plan over a tenant's padded stack WITHOUT
    executing it — the hook the artifact auditor drives
    (`repro.analysis.hlo_audit`): `collective_census(lowered)` checks the
    round-boundary communication structure and `assert_no_baked_data`
    checks that no tenant array was baked into the trace as a constant.
    Works for both plan forms: a plain jitted plan lowers over the full
    argument tuple; a `StreamedPlan` lowers its chunk step (one
    min(eval_chunk, rounds)-round dispatch, the unit that actually
    compiles)."""
    args = _plan_args(padded, seed, rounds, availability=availability,
                      silo_scale=silo_scale)
    if isinstance(plan, StreamedPlan):
        X, Y, w, wr, scale, key = args
        nr = min(int(eval_chunk), int(rounds))
        carry = plan.carry_init(init_params)
        return plan.step.lower(carry, X, Y, w, wr[:nr], scale, key,
                               jnp.int32(0), nr)
    return plan.lower(init_params, *args)


def make_scan_runner(batch_loss, padded: PaddedSilos, *, opt, rounds,
                     local_epochs, aggregator="fedavg", seed=0,
                     per_example=True, reset_opt=True,
                     collect_params=False, mesh=None,
                     silo_axes=None, availability=None, silo_scale=None,
                     trim_frac: float = 0.2, krum_f: int = 1) -> Callable:
    """Back-compat data-closure wrapper over make_fl_plan: a
    ``run(init_params) -> (final_params, ys)`` with this tenant's padded
    stack bound. Calling the SAME runner twice reuses the compiled
    executable — what benchmarks/fed_bench.py times as the warm FL phase.
    With mesh, the plan runs sharded (the padded silo count must already be
    a multiple of the silo-shard count)."""
    dropout = availability is not None and not np.all(
        np.asarray(availability) > 0)
    plan = make_fl_plan(
        num_silos=padded.num_silos, num_batches=padded.num_batches,
        batch_size=padded.batch_size, opt=opt, batch_loss=batch_loss,
        rounds=rounds, local_epochs=local_epochs, aggregator=aggregator,
        per_example=per_example, reset_opt=reset_opt,
        collect_params=collect_params,
        masked=padded.has_padding or dropout,
        mesh=mesh, silo_axes=silo_axes, trim_frac=trim_frac, krum_f=krum_f)
    args = _plan_args(padded, seed, rounds, availability=availability,
                      silo_scale=silo_scale)
    return lambda init_params: plan(init_params, *args)


def _fetch(tree):
    """Device arrays read to the host (counted as ``feddcl.device.fetches``,
    one per array)."""
    out = jax.device_get(tree)
    obs.count("feddcl.device.fetches", len(jax.tree.leaves(out)))
    return out


def _run_scan(plan, init_params, args, *, rounds: int, eval_fn=None,
              eval_chunk: int = 8) -> FLResult:
    """Drive a compiled plan over one tenant's plan arguments (`_plan_args`).

    With eval_fn, the plan is a StreamedPlan: the FL phase runs in
    eval_chunk-round dispatches that each emit only that chunk's per-round
    params, which are fetched to host ONCE per chunk (one device_get for
    the whole chunk tree, not one transfer per leaf per round), evaluated,
    and dropped — peak extra memory is eval_chunk × |params| regardless of
    rounds. Without eval_fn, one dispatch runs the whole phase and only
    the (rounds,) loss vector comes back."""
    history: List[Dict[str, float]] = []
    with obs.span("feddcl.fl.plan"):
        if isinstance(plan, StreamedPlan):
            X, Y, w, wr, scale, key = args
            carry = plan.carry_init(init_params)
            rnd0 = 0
            while rnd0 < rounds:
                nr = min(eval_chunk, rounds - rnd0)
                carry, chunk = plan.step(carry, X, Y, w, wr[rnd0:rnd0 + nr],
                                         scale, key, jnp.int32(rnd0), nr)
                # feddcl-lint: disable=R008  one transfer per eval_chunk rounds (the batched form the rule asks for), not one per round
                host_ls, host_ps = _fetch(chunk)
                for j in range(nr):
                    rec = {"round": rnd0 + j, "loss": float(host_ls[j])}
                    if eval_fn is not None:
                        rec.update(eval_fn(
                            jax.tree.map(lambda a: a[j], host_ps)))
                    history.append(rec)
                rnd0 += nr
            return FLResult(params=plan.carry_params(carry), history=history)

        gp, ys = plan(init_params, *args)
        if eval_fn is not None:
            # one host fetch for the whole (rounds, |params|) stack, not a
            # device round trip per leaf per round; the stacked mode itself
            # remains the legacy memory-heavy path kept for regression tests
            round_losses, host_params = _fetch(ys)
        else:
            round_losses = _fetch(ys)
    for rnd in range(rounds):
        rec = {"round": rnd, "loss": float(round_losses[rnd])}
        if eval_fn is not None:
            rec.update(eval_fn(jax.tree.map(lambda a: a[rnd], host_params)))
        history.append(rec)
    return FLResult(params=gp, history=history)


# ==========================================================================
# 3. Mesh-level federated collectives (production / dry-run form)
# ==========================================================================

def silo_replicate(params: Any, num_silos: int) -> Any:
    """Give every leaf a leading silo dim (identical start, paper Step 4)."""
    return jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (num_silos,) + p.shape), params)


def silo_vmap_step(step_fn: Callable) -> Callable:
    """vmap a per-silo (params, opt_state, batch) -> (params, opt_state,
    metrics) step over the leading silo dim. The resulting HLO contains no
    collective over the silo mesh axis — verified by tests/test_federated.py.
    """
    return jax.vmap(step_fn, in_axes=0, out_axes=0)


def scan_local_steps(local_step: Callable, silo_params: Any,
                     silo_opt_state: Any, batches: Any):
    """Run H silo-local steps as ONE lax.scan — the launch-tier form of the
    scan engine's inner loop. `batches` is a pytree with leading dim H (then
    the per-step silo batch layout); returns (params, opt_state, metrics)
    with metrics stacked over H."""
    def body(c, b):
        sp, so = c
        sp, so, m = local_step(sp, so, b)
        return (sp, so), m

    (sp, so), ms = lax.scan(body, (silo_params, silo_opt_state), batches)
    return sp, so, ms


def fedavg_sync(silo_params: Any, weights: Optional[jnp.ndarray] = None) -> Any:
    """Round boundary: average parameters across the silo dim and broadcast
    back. Under GSPMD with the silo dim sharded over the silo mesh axis this
    lowers to exactly one all-reduce over that axis per leaf."""
    def avg(p):
        pf = p.astype(jnp.float32)
        if weights is None:
            mean = jnp.mean(pf, axis=0, keepdims=True)
        else:
            w = (weights /
                 jnp.maximum(jnp.sum(weights), _DEN_EPS)).astype(jnp.float32)
            mean = jnp.tensordot(w, pf, axes=(0, 0), precision=HIGHEST)[None]
        return jnp.broadcast_to(mean, p.shape).astype(p.dtype)

    return jax.tree.map(avg, silo_params)


def robust_sync(silo_params: Any, aggregator: str,
                mask: Optional[jnp.ndarray] = None, *,
                trim_frac: float = 0.2, krum_f: int = 1) -> Any:
    """Robust round boundary in fedavg_sync's broadcast-back form: compute
    the masked robust statistic over the silo dim and broadcast it back so
    every silo restarts the next round from the same point. aggregator may
    also be a weighted one ("fedavg"/"fedprox"/"fedsgd"), which falls back
    to fedavg_sync — launch/steps.py routes every configured aggregator
    through this one entry point."""
    if aggregator not in ROBUST_AGGREGATORS:
        return fedavg_sync(silo_params)
    d = jax.tree_util.tree_leaves(silo_params)[0].shape[0]
    m = jnp.ones((d,), jnp.float32) if mask is None else \
        mask.astype(jnp.float32)
    agg = robust_aggregate(silo_params, m, aggregator,
                           trim_frac=trim_frac, krum_f=krum_f)
    return jax.tree.map(
        lambda a, p: jnp.broadcast_to(a[None], p.shape).astype(p.dtype),
        agg, silo_params)


def fedprox_regularizer(params: Any, ref_params: Any, mu: float) -> jnp.ndarray:
    return 0.5 * mu * sum(
        jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(ref_params)))
