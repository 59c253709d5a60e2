"""Rows the server packed into each step over the traced window, from its
own counters (rows_served, steps)."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps"):
        return None
    return c["rows_served"] / c["steps"]
