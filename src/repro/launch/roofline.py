"""Roofline-term extraction from a compiled dry-run artifact.

Hardware model: per-chip peaks keyed by the jax `device_kind` of the chip
the program was compiled for (`PEAKS`); a kind missing from the table is an
error, never a default.

Terms (seconds, per step, per chip — cost_analysis() on the partitioned
module is PER-DEVICE, verified empirically in this container):
    compute    = flops_per_device / peak
    memory     = bytes_per_device / hbm_bw
    collective = collective_bytes_per_device / link_bw

collective bytes are parsed from the post-SPMD HLO: the sum of result-shape
bytes of every all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute (documented approximation: ring all-reduce moves ~2× its
buffer; we report raw buffer bytes and the per-kind breakdown so any factor
can be applied downstream).
"""
from __future__ import annotations

import math
import re
from typing import Any, Dict

# Published peaks per chip. Source for "TPU v5 lite" (v5e): Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
# of inter-chip interconnect over 4 links (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,   # bf16 FLOP/s per chip
        "hbm_bw": 819e9,        # B/s per chip
        "link_bw": 50e9,        # B/s per link
    },
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of `device_kind`; raises on a kind
    with no published entry."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?P<shapes>\(?[^)=]*?\)?)\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shapes_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shapes_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


_RG_RE = re.compile(
    r"replica_groups=(\{\{[\d, {}]*\}\}|\{\}|\[[\d,]+\]<=\[[\d,]+\](?:T\(([\d,]+)\))?)")


def parse_replica_groups(attr: str, num_devices: int = 0):
    """Decode an HLO replica_groups attribute into explicit device groups.
    Handles the explicit form {{0,1},{2,3}} and the iota form
    [G,S]<=[dims](T(perm)) used by newer XLA."""
    import numpy as np

    attr = attr.strip()
    if attr == "{}":
        return [list(range(num_devices))]
    if attr.startswith("{{"):
        return [[int(x) for x in g.replace("{", "").replace("}", "").split(",")
                 if x.strip()] for g in attr[2:-2].split("},{")]
    m = re.match(r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", attr)
    if not m:
        return []
    gshape = [int(x) for x in m.group(1).split(",")]
    ishape = [int(x) for x in m.group(2).split(",")]
    arr = np.arange(int(np.prod(ishape))).reshape(ishape)
    if m.group(3):
        arr = arr.transpose([int(x) for x in m.group(3).split(",")])
    arr = arr.reshape(gshape)
    return arr.tolist()


def iter_collectives(hlo_text: str, num_devices: int = 0):
    """Yield (op_kind, result_bytes, groups) for every collective in the
    post-SPMD HLO ('-done' halves of async pairs skipped)."""
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        g = _RG_RE.search(line)
        groups = parse_replica_groups(g.group(1), num_devices) if g else []
        yield m.group("op"), _shape_bytes(m.group("shapes")), groups


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device bytes by collective kind (result-shape bytes, `-done` ops
    skipped so async pairs aren't double-counted)."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        b = _shape_bytes(m.group("shapes"))
        out[m.group("op")] = out.get(m.group("op"), 0) + b
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def model_flops(cfg, shape, kind: str, local_steps: int = 1) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), N = active,
    non-embedding params; D = tokens processed by the lowered program."""
    from repro.models.backbone import count_params_analytic

    n = count_params_analytic(cfg, active_only=True, include_embed=False)
    if kind in ("train", "fed_local"):
        # fed_local processes the full global batch (d silos × local batch)
        toks = shape.global_batch * shape.seq_len
        return 6.0 * n * toks
    if kind == "fed_sync":
        return 0.0
    if kind == "prefill":
        toks = shape.global_batch * shape.seq_len
        return 2.0 * n * toks
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def cross_block_bytes(hlo_text: str, block: int, num_devices: int) -> int:
    """Per-device bytes of collectives whose replica groups span more than
    one contiguous device block of `block` devices — i.e. traffic that must
    cross the silo/pod boundary (devices are laid out silo-major)."""
    total = 0
    for _op, nbytes, groups in iter_collectives(hlo_text, num_devices):
        for grp in groups:
            if len({d // block for d in grp}) > 1:
                total += nbytes
                break
    return total


def analyze(compiled, cfg, shape, kind: str, *, chips: int, device_kind: str,
            local_steps: int = 1, silo_block: int = 0) -> Dict[str, Any]:
    hw = peaks_for(device_kind)
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    coll_dev = float(coll.get("total", 0))

    compute_s = flops_dev / hw["peak_flops"]
    memory_s = bytes_dev / hw["hbm_bw"]
    collective_s = coll_dev / hw["link_bw"]
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)

    mf = model_flops(cfg, shape, kind, local_steps)
    hlo_global = flops_dev * chips
    xs_bytes = (cross_block_bytes(hlo, silo_block, chips)
                if silo_block else None)
    return {
        **({"cross_silo_bytes_per_device": xs_bytes,
            "silo_block": silo_block} if xs_bytes is not None else {}),
        "arch": cfg.name,
        "device_kind": device_kind,
        "shape": shape.name,
        "kind": kind,
        "chips": chips,
        "flops_per_device": flops_dev,
        "hlo_flops_global": hlo_global,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_dev,
        "collective_breakdown": coll,
        **terms,
        "dominant": dominant,
        "model_flops": mf,
        "useful_flops_ratio": (mf / hlo_global) if hlo_global else 0.0,
        "roofline_bound_s": max(terms.values()),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
    }


def fmt_row(r: Dict[str, Any]) -> str:
    return (f"{r['arch']:>22s} {r['shape']:>11s} {r['kind']:>9s} "
            f"C={r['compute_s']*1e3:9.3f}ms M={r['memory_s']*1e3:9.3f}ms "
            f"X={r['collective_s']*1e3:9.3f}ms dom={r['dominant'][:-2]:>10s} "
            f"useful={r['useful_flops_ratio']*100:5.1f}% "
            f"mem/dev={(r['memory']['argument_bytes']+r['memory']['temp_bytes'])/2**30:6.2f}GiB")
