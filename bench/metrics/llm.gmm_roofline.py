"""The held experts' grouped-matmul kernel's share of its roofline over
the traced window: the least time the chip could take for the grouped
products the window's rows needed (the program's counter
``feddcl.llm.expert_rows``; flops and bytes from the widths,
bench/flops_llm.py) over the device time of every kernel call, found by
its name. Silent when the program has no such counter, or when the trace
shows fewer kernel calls than the window's MoE layer-steps need, i.e. when
part of the expert work no longer runs in this kernel."""
from bench import flops, flops_llm

KERNEL = "grouped_matmul"


def read(ctx):
    try:
        from repro import obs
    except ImportError:                  # a program without counters
        return None
    c = obs.snapshot()["counters"]
    rows, tokens = c.get("feddcl.llm.expert_rows"), c.get("feddcl.llm.tokens")
    if not rows or not tokens:
        return None
    cfg = ctx["cfg"]
    layer_steps = tokens / flops_llm.tokens_per_step(cfg, ctx["traffic"]) \
        * flops_llm.moe_layers(cfg)
    spent, calls = ctx["trace"].op_seconds(KERNEL)
    if calls < layer_steps * flops_llm.gmm_calls_per_layer_step() or \
            spent <= 0:
        return None
    pk = flops.peaks(ctx["device_kind"])
    least, _ = flops.least_seconds(
        *flops_llm.grouped_matmuls(cfg, rows, layer_steps), pk)
    return 100.0 * least / spent
