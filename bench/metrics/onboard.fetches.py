"""Device arrays the admissions of the traced window read to the host, per
admission, from the program's own counter."""

COUNTER = "feddcl.device.fetches"


def read(ctx):
    try:
        from repro import obs
    except ImportError:                  # a program without counters
        return None
    n = obs.snapshot()["counters"].get(COUNTER)
    units = ctx["counters"].get("admissions")
    if not n or not units:
        return None
    return n / units
