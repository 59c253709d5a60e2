"""Mean wall time of a build's step 3 (intra-group bases, central
target, every G and X̂: the device backend and its host glue) over the
traced window, from the program's own span."""

SPAN = "feddcl.protocol.step3"


def read(ctx):
    try:
        from repro import obs
    except ImportError:                  # a program without spans
        return None
    s = obs.snapshot()["spans"].get(SPAN)
    if not s or not s["n"]:
        return None
    return 1e3 * s["total_s"] / s["n"]
