"""Model FLOP/s utilization of the FL phases in the traced window: 6
operations per parameter per real sample trained, over the chip's bf16
peak. The plan runs float32 at the highest precision (several MXU passes
per product), so this understates the passes the MXU makes."""
from bench import flops
from bench.reference.mlp import dims


def read(ctx):
    c = ctx["counters"]
    if not c.get("samples"):
        return None
    rate = c["samples"] / c["seconds"]
    pk = flops.peaks(ctx["device_kind"])
    return 100.0 * flops.mlp_train_per_sample(dims(ctx["cfg"])) * rate / \
        pk["bf16_flops_per_s"]
