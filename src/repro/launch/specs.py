"""input_specs(): ShapeDtypeStruct stand-ins + shardings for every
(architecture × input shape × mesh × mode) — the dry-run's contract.

No device allocation happens here: parameter/optimizer/cache shapes come
from jax.eval_shape over the real constructors, so the dry-run lowers the
EXACT production program.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import (FederatedConfig, InputShape, ModelConfig,
                                TrainConfig)
from repro.core.federated import silo_replicate
from repro.launch import steps as steps_lib
from repro.launch.mesh import num_silos, silo_axis_name
from repro.models import backbone as bb
from repro.shardingx.policy import batch_spec, param_specs, to_shardings

# decode keeps params tensor-parallel-only unless they cannot fit one model
# shard (deepseek-v3: 671B bf16 / 16 shards = 84 GB ≫ HBM -> FSDP too).
DECODE_FSDP_BYTES = 12e9

# the train step's metrics an MoE config adds (backbone.loss_fn)
MOE_METRICS = {"moe_aux": 0., "expert_rows": 0., "expert_rows_max": 0.}


@dataclass
class LoweringPlan:
    name: str
    kind: str                       # train | fed_round | prefill | decode
    cfg: ModelConfig
    step_fn: Callable
    args: Tuple[Any, ...]           # ShapeDtypeStruct trees
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...] = ()


def resolve_arch_for_shape(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    """Long-context policy (DESIGN.md §8): at 500k decode every attention
    path runs sliding-window (ring cache); SSM/hybrid state paths unchanged."""
    if shape.name == "long_500k" and cfg.family != "ssm":
        return cfg.with_overrides(attn_variant="sliding", sliding_window=8192)
    return cfg


def decode_cache_len(cfg: ModelConfig, shape: InputShape) -> int:
    if cfg.attn_variant == "sliding":
        return min(shape.seq_len, cfg.sliding_window)
    return shape.seq_len + (cfg.prefix_len if cfg.prefix_frontend else 0)


# --------------------------------------------------------------------------
# cache sharding
# --------------------------------------------------------------------------

def cache_specs(state_shapes: Any, mesh: Mesh,
                replicate_model: bool = False,
                model_on_seq: bool = False) -> Any:
    """Decode-state PartitionSpecs. Arrays are (L, B, ...):
      batch dim over ("pod","data") when divisible; for B == 1 (long-context)
      the ring/cache length dim (index 2) is sequence-sharded instead;
      the model axis lands on the innermost divisible dim of index >= 3."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch_axes = tuple(a for a in ("pod", "data") if a in sizes)
    bt = 1
    for a in batch_axes:
        bt *= sizes[a]
    msize = sizes.get("model", 1)

    def one(path, leaf):
        shape = tuple(leaf.shape)
        spec = [None] * len(shape)
        if len(shape) < 2:
            return P(*spec)
        model_at = None
        if model_on_seq and len(shape) >= 3 and msize > 1 \
                and shape[2] % msize == 0 and shape[2] >= msize:
            spec[2] = "model"                 # cache length dim
            model_at = 2
        elif not replicate_model:
            for i in range(len(shape) - 1, 2, -1):
                if msize > 1 and shape[i] % msize == 0:
                    spec[i] = "model"
                    model_at = i
                    break
        if batch_axes:
            if shape[1] % bt == 0 and shape[1] > 1:
                spec[1] = batch_axes
            elif len(shape) >= 3 and 2 != model_at and shape[2] % bt == 0:
                spec[2] = batch_axes
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, state_shapes)


# --------------------------------------------------------------------------
# plan builders
# --------------------------------------------------------------------------

def _params_shapes(cfg: ModelConfig, dtype) -> Any:
    return jax.eval_shape(
        lambda: bb.init_params(cfg, jax.random.PRNGKey(0), dtype))


def _opt_specs(pspecs: Any) -> Any:
    return {"step": P(), "m": pspecs, "v": pspecs}


def make_plan(cfg_raw: ModelConfig, shape: InputShape, mesh: Mesh, *,
              mode: str = "baseline", tc: Optional[TrainConfig] = None,
              use_pallas: bool = False, moe_impl: Optional[str] = None) -> LoweringPlan:
    """mode: baseline | feddcl | feddcl_sync (train shapes) — decode/prefill
    ignore mode. moe_impl overrides the MoE dispatch (hillclimb: "ep")."""
    cfg = resolve_arch_for_shape(cfg_raw, shape)
    if moe_impl and cfg.moe is not None:
        cfg = cfg.with_overrides(moe=dataclasses.replace(cfg.moe, impl=moe_impl))
    if mode in ("feddcl", "feddcl_sync") and cfg.moe is not None \
            and cfg.moe.impl == "ep":
        # shard_map does not nest under the silo vmap — fed plans use gspmd
        cfg = cfg.with_overrides(moe=dataclasses.replace(cfg.moe, impl="gspmd"))
    tc = tc or TrainConfig(model=cfg, shape=shape)
    pdtype = jnp.dtype(tc.param_dtype)
    cdtype = jnp.dtype(tc.compute_dtype)

    if shape.kind == "train":
        if mode == "feddcl":
            return _fed_local_plan(cfg, shape, mesh, tc, use_pallas)
        if mode == "feddcl_sync":
            return _fed_sync_plan(cfg, shape, mesh, tc)
        return _train_plan(cfg, shape, mesh, tc, use_pallas)
    if shape.kind == "prefill":
        return _prefill_plan(cfg, shape, mesh, tc, use_pallas)
    return _decode_plan(cfg, shape, mesh, tc)


def _batch_shapes(cfg: ModelConfig, batch: int, seq: int, cdtype) -> Dict[str, Any]:
    d = {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    if cfg.prefix_frontend:
        d["prefix_embeds"] = jax.ShapeDtypeStruct(
            (batch, cfg.prefix_len, cfg.d_model), cdtype)
    return d


def _train_plan(cfg, shape, mesh, tc, use_pallas) -> LoweringPlan:
    pdtype = jnp.dtype(tc.param_dtype)
    cdtype = jnp.dtype(tc.compute_dtype)
    step, opt = steps_lib.make_train_step(cfg, tc, use_pallas=use_pallas)
    pshapes = _params_shapes(cfg, pdtype)
    oshapes = jax.eval_shape(opt.init, pshapes)
    bshapes = _batch_shapes(cfg, shape.global_batch, shape.seq_len, cdtype)

    # NOTE: EP expert weights stay FSDP-sharded (policy default); moe_ep.py
    # declares matching in_specs and all-gathers them inside the shard_map.
    pspecs = param_specs(pshapes, mesh, fsdp=tc.fsdp)
    ospecs = _opt_specs(pspecs)
    bspec = batch_spec(mesh, federated=False)
    bspecs = {k: (bspec if v.ndim == 2 else
                  P(*(tuple(bspec)[:1] + (None,) * (v.ndim - 1))))
              for k, v in bshapes.items()}
    mspecs = jax.tree.map(lambda _: P(), {"loss": 0., "ce": 0., "grad_norm": 0.,
                                          **(MOE_METRICS if cfg.moe else {}),
                                          **({"mtp": 0.} if cfg.mtp_depth else {})})
    return LoweringPlan(
        name=f"{cfg.name}:{shape.name}:train",
        kind="train", cfg=cfg, step_fn=step,
        args=(pshapes, oshapes, bshapes),
        in_shardings=tuple(to_shardings(s, mesh) for s in (pspecs, ospecs, bspecs)),
        out_shardings=tuple(to_shardings(s, mesh) for s in (pspecs, ospecs, mspecs)),
        donate_argnums=(0, 1),
    )


def _fed_common(cfg, shape, mesh, tc):
    d = num_silos(mesh)
    silo_ax = silo_axis_name(mesh)
    pdtype = jnp.dtype(tc.param_dtype)
    pshapes = _params_shapes(cfg, pdtype)
    sp_shapes = jax.eval_shape(lambda p: silo_replicate(p, d), pshapes)
    pspecs = param_specs(sp_shapes, mesh, fsdp=tc.fsdp, silo_dim=True,
                         silo_axis=silo_ax)
    return d, silo_ax, sp_shapes, pspecs


def _fed_local_plan(cfg, shape, mesh, tc, use_pallas) -> LoweringPlan:
    """FedDCL local step (d silos × local batch, zero cross-silo traffic)."""
    cdtype = jnp.dtype(tc.compute_dtype)
    d, silo_ax, sp_shapes, pspecs = _fed_common(cfg, shape, mesh, tc)
    assert shape.global_batch % d == 0, (shape.global_batch, d)
    local_b = shape.global_batch // d

    vstep, opt = steps_lib.make_federated_local_step(cfg, tc,
                                                     use_pallas=use_pallas)
    so_shapes = jax.eval_shape(lambda p: jax.vmap(opt.init)(p), sp_shapes)
    b1 = _batch_shapes(cfg, local_b, shape.seq_len, cdtype)
    bshapes = {k: jax.ShapeDtypeStruct((d,) + v.shape, v.dtype)
               for k, v in b1.items()}

    ospecs = {"step": P(silo_ax), "m": pspecs, "v": pspecs}
    inner_data = "data" if (silo_ax != "data" and "data" in mesh.axis_names) else None
    bspecs = {k: P(silo_ax, inner_data, *([None] * (v.ndim - 2)))
              for k, v in bshapes.items()}
    mspecs = jax.tree.map(lambda _: P(silo_ax),
                          {"loss": 0., "ce": 0., "grad_norm": 0.,
                           **(MOE_METRICS if cfg.moe else {}),
                           **({"mtp": 0.} if cfg.mtp_depth else {})})
    return LoweringPlan(
        name=f"{cfg.name}:{shape.name}:feddcl-local(d={d})",
        kind="fed_local", cfg=cfg, step_fn=vstep,
        args=(sp_shapes, so_shapes, bshapes),
        in_shardings=tuple(to_shardings(s, mesh) for s in (pspecs, ospecs, bspecs)),
        out_shardings=tuple(to_shardings(s, mesh) for s in (pspecs, ospecs, mspecs)),
        donate_argnums=(0, 1),
    )


def _fed_sync_plan(cfg, shape, mesh, tc) -> LoweringPlan:
    """FedDCL round boundary: the single cross-silo all-reduce."""
    d, silo_ax, sp_shapes, pspecs = _fed_common(cfg, shape, mesh, tc)
    sync = steps_lib.make_fedavg_sync_step(tc)
    _, opt = steps_lib.make_federated_local_step(cfg, tc)
    so_shapes = jax.eval_shape(lambda p: jax.vmap(opt.init)(p), sp_shapes)
    ospecs = {"step": P(silo_ax), "m": pspecs, "v": pspecs}
    return LoweringPlan(
        name=f"{cfg.name}:{shape.name}:feddcl-sync(d={d},H={tc.federated.local_steps})",
        kind="fed_sync", cfg=cfg, step_fn=sync,
        args=(sp_shapes, so_shapes),
        in_shardings=tuple(to_shardings(s, mesh) for s in (pspecs, ospecs)),
        out_shardings=tuple(to_shardings(s, mesh) for s in (pspecs, ospecs)),
        donate_argnums=(0, 1),
    )


def _decode_params_fsdp(cfg: ModelConfig) -> bool:
    return bb.count_params_analytic(cfg) * 2 / 16 > DECODE_FSDP_BYTES


def _prefill_plan(cfg, shape, mesh, tc, use_pallas) -> LoweringPlan:
    cdtype = jnp.dtype(tc.compute_dtype)
    cache_len = decode_cache_len(cfg, shape)
    step = steps_lib.make_prefill_step(cfg, cache_len=cache_len,
                                       compute_dtype=cdtype,
                                       use_pallas=use_pallas)
    pshapes = _params_shapes(cfg, jnp.bfloat16)
    bshapes = _batch_shapes(cfg, shape.global_batch, shape.seq_len, cdtype)
    bshapes.pop("labels")

    pspecs = param_specs(pshapes, mesh, fsdp=_decode_params_fsdp(cfg))
    bspec = batch_spec(mesh, federated=False)
    bspecs = {k: (bspec if v.ndim == 2 else
                  P(*(tuple(bspec)[:1] + (None,) * (v.ndim - 1))))
              for k, v in bshapes.items()}
    out_shapes = jax.eval_shape(step, pshapes, bshapes)
    state_specs = cache_specs(out_shapes[1], mesh)
    out_specs = (P(), state_specs, P())
    return LoweringPlan(
        name=f"{cfg.name}:{shape.name}:prefill",
        kind="prefill", cfg=cfg, step_fn=step,
        args=(pshapes, bshapes),
        in_shardings=tuple(to_shardings(s, mesh) for s in (pspecs, bspecs)),
        out_shardings=to_shardings(out_specs, mesh),
    )


def _decode_plan(cfg, shape, mesh, tc) -> LoweringPlan:
    cdtype = jnp.dtype(tc.compute_dtype)
    cache_len = decode_cache_len(cfg, shape)
    B = shape.global_batch
    step = steps_lib.make_serve_step(cfg, compute_dtype=cdtype)
    pshapes = _params_shapes(cfg, jnp.bfloat16)
    sshapes = jax.eval_shape(
        lambda: bb.init_decode_state(cfg, B, cache_len, jnp.bfloat16))
    tshape = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    posshape = jax.ShapeDtypeStruct((B,), jnp.int32)

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch_axes = tuple(a for a in ("pod", "data") if a in sizes)
    bt = 1
    for a in batch_axes:
        bt *= sizes[a]
    tok_spec = P(batch_axes if B % bt == 0 and B > 1 else None, None)
    pos_spec = P(batch_axes if B % bt == 0 and B > 1 else None)

    pspecs = param_specs(pshapes, mesh, fsdp=_decode_params_fsdp(cfg))
    sspecs = cache_specs(sshapes, mesh,
                         replicate_model=cfg.decode_expand_kv,
                         model_on_seq=cfg.decode_cache_seq)
    logits_spec = P(tuple(tok_spec)[0], None, None)
    return LoweringPlan(
        name=f"{cfg.name}:{shape.name}:decode",
        kind="decode", cfg=cfg, step_fn=step,
        args=(pshapes, sshapes, tshape, posshape),
        in_shardings=(to_shardings(pspecs, mesh), to_shardings(sspecs, mesh),
                      NamedSharding(mesh, tok_spec), NamedSharding(mesh, pos_spec)),
        out_shardings=(NamedSharding(mesh, logits_spec),
                       to_shardings(sspecs, mesh)),
        donate_argnums=(1,),
    )
