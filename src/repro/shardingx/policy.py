"""Per-tensor PartitionSpec resolution for the production meshes.

Handles the awkward real-world cases the assigned architectures hit:
  * GQA kv_heads (8, 4, 2) smaller than the 16-wide model axis — falls back
    to head_dim sharding, then to replication;
  * RWKV6's 40 heads (not divisible by 16) — shards head_dim instead;
  * granite's vocab 49155 = 3·5·29·113 — not divisible by ANY mesh axis, so
    the embedding shards d_model on the model axis instead;
  * stacked scan-over-layers parameters (leading layer dim) — rules are
    written against trailing (negative) dims;
  * federated training — a leading silo dim sharded over the silo axis,
    with FSDP restricted to the intra-silo data axis.

Design choices (DESIGN.md §5): tensor parallelism over "model", FSDP over
"data" only (cross-pod gathers would ride the scarce DCI), "pod" is pure
data parallel in baseline mode and the silo axis in federated mode.
"""
from __future__ import annotations

import re
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

REPLICATE_BELOW = 4096          # leaves smaller than this stay replicated

# name -> (model-axis dim priority, data/FSDP-axis dim priority), negative
# indices relative to the trailing (per-layer) shape.
_RULES_3D = {
    # attention / rwkv projections (d, H, hd) — prefer heads, fall to head_dim
    "wq": ([-2, -1], [-3]), "wk": ([-2, -1], [-3]), "wv": ([-2, -1], [-3]),
    "w_q": ([-2, -1], [-3]),
    "w_r": ([-2, -1], [-3]), "w_k": ([-2, -1], [-3]), "w_v": ([-2, -1], [-3]),
    # output projections (H, hd, d)
    "wo": ([-3, -2], [-1]), "w_o": ([-3, -2], [-1]),
    # MoE experts (E, d, f) / (E, f, d) — expert parallelism on model axis
    "w_gate": ([-3], [-1]), "w_up": ([-3], [-1]), "w_down": ([-3], [-2]),
    # MLA up-projections (rank, H, x)
    "w_uq": ([-2], [-3]), "w_uk": ([-2], [-3]), "w_uv": ([-2], [-3]),
    # rwkv lora tails
    "decay_lora_b": ([-1], [-3]), "mix_lora_a": ([-1], [-3]),
    "mix_lora_b": ([-1], [-2]),
}

_RULES_2D_UP = {"w_gate", "w_up", "w_k", "w_g", "w_in", "w_dq", "w_dkv",
                "decay_lora_a", "proj", "w_r"}
_RULES_2D_DOWN = {"w_down", "w_v", "w_out", "lm_head"}
_EMBED = {"embed"}


def _divisible(shape: Sequence[int], dim: int, size: int) -> bool:
    return size > 1 and shape[dim] % size == 0


def _resolve(name: str, shape: Tuple[int, ...], trailing: int,
             model_axis: Optional[str], model_size: int,
             data_axis: Optional[str], data_size: int,
             fsdp: bool) -> list:
    """Return spec entries for the trailing `trailing` dims."""
    spec: list = [None] * trailing
    if int(np.prod(shape[-trailing:] or (1,))) < REPLICATE_BELOW or trailing == 0:
        return spec

    def t2a(neg: int) -> int:  # negative trailing index -> index into spec
        return trailing + neg

    model_dims, data_dims = [], []
    if trailing >= 3 and name in _RULES_3D:
        model_dims, data_dims = _RULES_3D[name]
    elif trailing >= 2 and name in _EMBED:
        model_dims, data_dims = [-2, -1], [-2, -1]
    elif trailing >= 2 and name in _RULES_2D_DOWN:
        model_dims, data_dims = [-2], [-1]
    elif trailing >= 2 and (name in _RULES_2D_UP or trailing == 2):
        model_dims, data_dims = [-1], [-2]

    model_at = None
    if model_axis:
        for nd in model_dims:
            if -nd <= trailing and _divisible(shape, nd, model_size):
                spec[t2a(nd)] = model_axis
                model_at = t2a(nd)
                break
    if fsdp and data_axis:
        for nd in data_dims:
            a = t2a(nd)
            if -nd <= trailing and a != model_at and _divisible(shape, nd, data_size):
                spec[a] = data_axis
                break
        else:
            # try stacking data onto the model dim (e.g. embed vocab over both)
            if model_at is not None and shape[model_at - trailing] % (model_size * data_size) == 0:
                spec[model_at] = (data_axis, model_axis)
    return spec


_MOE_EXPERT_NAMES = {"w_gate", "w_up", "w_down"}


def param_specs(shapes: Any, mesh: Mesh, *, fsdp: bool = True,
                moe_fsdp: bool = True,
                silo_dim: bool = False, silo_axis: Optional[str] = None,
                stacked_prefixes: Tuple[str, ...] = ("layers", "dense_layers",
                                                     "tail_layers")) -> Any:
    """Tree of PartitionSpec matching a tree of ShapeDtypeStructs/arrays.

    silo_dim: params carry a leading silo dim (federated mode) sharded over
    silo_axis; FSDP then uses the remaining data axis only if distinct.
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_axis = "model" if "model" in axis_sizes else None
    if silo_dim and silo_axis is None:
        silo_axis = "pod" if "pod" in axis_sizes else "data"
    data_axis = "data" if "data" in axis_sizes else None
    if silo_dim and silo_axis == "data":
        data_axis = None                      # data axis consumed by silos

    def one(path, leaf) -> P:
        shape = tuple(leaf.shape)
        names = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
        name = names[-1] if names else ""
        if name in ("scale",) and len(names) >= 2:
            name = names[-2]                  # rmsnorm dicts
        offset = 1 if silo_dim else 0
        stacked = any(n in stacked_prefixes for n in names)
        trailing = len(shape) - offset - (1 if stacked and len(shape) - offset >= 1 else 0)
        trailing = max(trailing, 0)
        leaf_fsdp = fsdp
        # expert-parallel MoE (shard_map path) needs expert weights sharded
        # exactly P(model-on-E) — no FSDP on the d/f dims
        if not moe_fsdp and name in _MOE_EXPERT_NAMES and trailing >= 3:
            leaf_fsdp = False
        entries = _resolve(name, shape, trailing, model_axis,
                           axis_sizes.get(model_axis or "", 1),
                           data_axis, axis_sizes.get(data_axis or "", 1),
                           leaf_fsdp)
        head: list = []
        if silo_dim:
            head.append(silo_axis if shape[0] > 1 else None)
        if stacked and len(shape) - offset >= 1:
            head.append(None)                 # layer-stack dim
        return P(*(head + entries))

    return jax.tree_util.tree_map_with_path(one, shapes)


def batch_spec(mesh: Mesh, *, federated: bool,
               silo_axis: Optional[Any] = None, ndim: int = 2) -> P:
    """Spec for (B, S) token batches — or federated silo stacks.

    federated with a STRING silo_axis (launch-tier LLM batches, (d, b, S)):
    silo dim over silo_axis, intra-silo batch dim over the leftover "data"
    axis, ndim counting the per-silo batch rank. federated with a TUPLE
    silo_axis (core.federated sharded plans, (d, n_slots, …) tabular
    stacks): the leading silo dim spans ALL the named axes jointly —
    ("pod", "data") on a multipod mesh — and ndim is the FULL array rank;
    every non-silo dim stays shard-local.
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if federated:
        if silo_axis is not None and not isinstance(silo_axis, str):
            return P(tuple(silo_axis), *([None] * (ndim - 1)))
        silo_axis = silo_axis or ("pod" if "pod" in axis_sizes else "data")
        rest = "data" if ("data" in axis_sizes and silo_axis != "data") else None
        return P(silo_axis, rest, *([None] * (ndim - 1)))
    batch_axes = tuple(a for a in ("pod", "data") if a in axis_sizes)
    return P(batch_axes if batch_axes else None, *([None] * (ndim - 1)))


def to_shardings(spec_tree: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
