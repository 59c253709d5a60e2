"""The Gram kernel's share of its roofline over the traced window: the
least time the chip could take for the Gram reductions the builds needed
(each group's stacked anchors and the stacked bases, flops and bytes from
bench/flops.py; both memory-bound at the bf16 peak on a v5e) over the
device time of every Gram kernel call, found by its name. Silent when the
trace shows fewer kernel calls than the builds needed, i.e. when part of
the Gram work no longer runs in this kernel."""
from bench import flops

KERNEL = "gram_cross"


def read(ctx):
    c, red = ctx["counters"], ctx["trace"]
    builds = c.get("builds", 0)
    spent, calls = red.op_seconds(KERNEL)
    shapes = flops.protocol_grams(ctx["cfg"])
    if not builds or calls < builds * len(shapes) or spent <= 0:
        return None
    pk = flops.peaks(ctx["device_kind"])
    least = sum(flops.least_seconds(*flops.gram(*s), pk)[0] for s in shapes)
    return 100.0 * builds * least / spent
