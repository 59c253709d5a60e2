"""Expert-parallel MoE via shard_map + all_to_all (the hillclimbed path).

The baseline GSPMD dispatch (layers.apply_moe_gspmd) scatters token rows
into an expert-major buffer and lets the partitioner reshard — which it does
by replicating the (T·k, d) operand (measured: granite train_4k temp 92 GiB
/dev, 2.2 TB/dev collectives). This path makes the exchange explicit:

  tokens stay sharded over the batch axes; experts are sharded over "model";
  each device routes its local tokens, packs per-expert capacity buffers,
  and ONE tiled all_to_all over the model axis moves exactly
  E·cap_local·d bytes to the expert owners (and one back).

Falls back to the GSPMD path when no multi-device mesh is active (CPU tests)
or when tracing under vmap (federated silo dim — shard_map does not nest
under vmap; the fed plans pin impl="gspmd").
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax._src import mesh as mesh_lib
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig


def _physical_mesh():
    """The mesh of an enclosing `with mesh:` block (jax exposes this legacy
    context only privately), or None."""
    pm = mesh_lib.thread_resources.env.physical_mesh
    return None if pm.empty else pm


def apply_moe_ep(p, x: jnp.ndarray, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    from repro.models.layers import _router_probs, apply_mlp, moe_aux_loss

    mesh = _physical_mesh()
    mo = cfg.moe
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh else {}
    M = sizes.get("model", 1)
    if mesh is None or M <= 1 or mo.num_experts % M:
        from repro.models.layers import apply_moe_gspmd
        return apply_moe_gspmd(p, x, cfg)

    batch_axes = tuple(a for a in ("pod", "data") if sizes.get(a, 1) > 1)
    B, S, d = x.shape
    E, k = mo.num_experts, mo.top_k
    E_loc = E // M

    has_bias = "router_bias" in p

    data_axis = "data" if sizes.get("data", 1) > 1 else None

    def local_fn(xl, router, router_bias, wg, wu, wd):
        # xl: (B_loc, S_loc, d) — this device's token block.
        # Expert weights arrive FSDP-sharded on their wide dim (P('model',
        # ·,'data')) — deepseek's experts are 96% of its 671B params, so
        # keeping them data-sharded at rest is mandatory (measured: 647
        # GiB/dev without). Gather per layer, exactly like FSDP elsewhere.
        if data_axis is not None:
            wg = lax.all_gather(wg, data_axis, axis=2, tiled=True)
            wu = lax.all_gather(wu, data_axis, axis=2, tiled=True)
            wd = lax.all_gather(wd, data_axis, axis=1, tiled=True)
        Bl, Sl = xl.shape[0], xl.shape[1]
        T_loc = Bl * Sl
        x2d = xl.reshape(T_loc, d)
        pr = {"router": router}
        if has_bias:
            pr["router_bias"] = router_bias
        gates, idx, probs = _router_probs(pr, x2d, mo)
        cap = max(int(mo.capacity_factor * T_loc * k / E), 1)

        flat_e = idx.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        starts = jnp.searchsorted(sorted_e, jnp.arange(E))
        pos_sorted = jnp.arange(T_loc * k) - starts[sorted_e]
        pos = jnp.zeros((T_loc * k,), jnp.int32).at[order].set(
            pos_sorted.astype(jnp.int32))
        keep = pos < cap
        slot = jnp.where(keep, pos, cap)

        src = jnp.repeat(x2d, k, axis=0)
        buf = jnp.zeros((E, cap + 1, d), x.dtype).at[flat_e, slot].set(src)
        buf = buf[:, :cap]                                   # (E, cap, d)

        # ONE exchange: (E, cap, d) -> (E_loc, M*cap, d)
        recv = lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                              tiled=True)

        g = jnp.einsum("ecd,edf->ecf", recv, wg.astype(x.dtype))
        u = jnp.einsum("ecd,edf->ecf", recv, wu.astype(x.dtype))
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd.astype(x.dtype))

        # reverse exchange back to token owners: (E_loc, M*cap, d) -> (E, cap, d)
        back = lax.all_to_all(y, "model", split_axis=1, concat_axis=0,
                              tiled=True)
        back = jnp.concatenate([back, jnp.zeros((E, 1, d), y.dtype)], axis=1)
        got = back[flat_e, slot]                             # (T_loc*k, d)
        w = (gates.reshape(-1) * keep.astype(jnp.float32)).astype(x.dtype)
        out = jnp.sum((got * w[:, None]).reshape(T_loc, k, d), axis=1)
        aux = moe_aux_loss(probs, idx, mo)
        return out.reshape(Bl, Sl, d), aux[None]

    # tokens are sharded over batch AND (sequence-wise) over model: without
    # the model split every model-peer in a data row would route the SAME
    # replicated tokens — 16× duplicated dispatch+expert work (measured:
    # granite compute 496→1234 ms before this fix).
    if S % M:
        from repro.models.layers import apply_moe_gspmd
        return apply_moe_gspmd(p, x, cfg)
    x_spec = P(batch_axes if batch_axes else None, "model", None)
    d_ax = "data" if sizes.get("data", 1) > 1 else None
    gate_spec = P("model", None, d_ax)     # (E, d, f): FSDP on f
    down_spec = P("model", d_ax, None)     # (E, f, d): FSDP on f
    rb = p.get("router_bias")
    aux_axes = tuple(batch_axes) + ("model",)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, P(), P(), gate_spec, gate_spec, down_spec),
        out_specs=(x_spec, P(aux_axes)), check_vma=False,
    )
    out, aux = fn(x, p["router"], rb if rb is not None else jnp.zeros((0,)),
                  p["w_gate"], p["w_up"], p["w_down"])
    aux = jnp.mean(aux)
    if mo.num_shared_experts:
        out = out + apply_mlp(p["shared"], x.reshape(-1, d)).reshape(B, S, d)
    return out, aux
