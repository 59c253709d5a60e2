"""What every traffic driver shares: the traced window, and relative gaps.

A driver module ``bench/drivers/<kind>.py`` defines ``Driver(cfg, traffic,
seed)`` with

- ``setup()``: build the deployment from the seed and warm every shape the
  window will use (all of it counts as set-up time);
- ``window(seconds, trace_dir)``: run the traffic for that long and return
  ``{"end_to_end": {metric: value}, **counters}``; a trace directory means
  the window runs under the profiler;
- ``notes()``: numbers printed before the result line (generator lateness,
  sample counts);
- ``release()``: drop the program's state before the reference runs;
- ``check(limits)``: compare what the window produced with the plain
  reference, ``{name: {"value": v, "limit": limits[name]}}``;
- ``controls()``: the same numbers for the control (the reference on
  bfloat16 operands in the program's place) and for the faults the driver
  can plant in it, ``{kind: {name: value}}`` (bench/calibrate.py);
- ``attempted_failed()``: operations attempted, and those that failed.
"""
from __future__ import annotations

import contextlib
import time
from typing import Optional

import numpy as np

WINDOW_SPAN = "bench.window"


@contextlib.contextmanager
def window_span(trace_dir: Optional[str]):
    """The measured window: under the profiler when a trace directory is
    given, always inside the ``bench.window`` span the trace reduction
    measures."""
    import jax
    if trace_dir is not None:
        jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()


@contextlib.contextmanager
def gc_pauses():
    """Yields a list that collects the duration of every garbage
    collection pass while the block runs."""
    import gc
    out, start = [], [0.0]

    def listen(phase, info):
        if phase == "start":
            start[0] = time.perf_counter()
        else:
            out.append(time.perf_counter() - start[0])
    gc.callbacks.append(listen)
    try:
        yield out
    finally:
        gc.callbacks.remove(listen)


def span(name: str):
    """A benchmark span around a call into one layer of the program."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def rel_gap(a, b) -> float:
    """‖a − b‖ / ‖b‖ (Frobenius), b the reference."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def check_entry(value: float, limits: dict, name: str) -> dict:
    return {"value": float(value), "limit": float(limits[name])}


def now() -> float:
    return time.perf_counter()
