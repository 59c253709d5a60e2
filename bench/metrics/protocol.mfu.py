"""Share of the chip's bf16 peak that the builds of the traced window
reached: the dense linear algebra one build of steps 2-3 needs
(bench/flops.py) times builds per second."""
from bench import flops


def read(ctx):
    c = ctx["counters"]
    if not c.get("builds"):
        return None
    pk = flops.peaks(ctx["device_kind"])
    rate = c["builds"] / c["seconds"]
    return 100.0 * flops.protocol_build(ctx["cfg"]) * rate / \
        pk["bf16_flops_per_s"]
