"""Share of the FL plan's slot-steps that train a real row over the traced
window: real rows × local epochs × rounds over padded silos × padded
batches × batch × local epochs × rounds, from the program's own counters
(the rest is padding of the plan cache's power-of-two layout)."""


def read(ctx):
    try:
        from repro import obs
    except ImportError:                  # a program without counters
        return None
    c = obs.snapshot()["counters"]
    real, slots = c.get("feddcl.fl.sample_steps"), c.get("feddcl.fl.slot_steps")
    if not real or not slots:
        return None
    return 100.0 * real / slots
