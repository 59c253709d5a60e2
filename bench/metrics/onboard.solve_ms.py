"""Mean wall time of an admission's incremental protocol update
(`FedDCLSetup.onboard_user`: the newcomer's map, blocked Gram, factor,
basis, central target, every G and X̂) over the traced window, from the
program's own span."""

SPAN = "feddcl.onboard.user"


def read(ctx):
    try:
        from repro import obs
    except ImportError:                  # a program without spans
        return None
    s = obs.snapshot()["spans"].get(SPAN)
    if not s or not s["n"]:
        return None
    return 1e3 * s["total_s"] / s["n"]
