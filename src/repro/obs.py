"""The program's spans and counters: where a traced run's time goes.

    with obs.span("feddcl.protocol.step2"):
        ...
    obs.count("feddcl.device.fetches")
    obs.snapshot()  # {"spans": {name: {n, total_s, self_s, max_s}},
                    #  "counters": {name: int}}

Both record only while the JAX profiler is collecting
(``jax.profiler.trace`` / ``start_trace``). Off, a call costs one
``TraceAnnotation.is_enabled()`` check: no allocation, no annotation, no
clock reading. On, each span is also a ``TraceAnnotation``, so it sits in
the profiler's trace (Perfetto, the xplane) on the host's clock beside the
device ops, and it adds to an in-process table per name: calls, total
wall time, self time (total less the time its child spans on the same
thread cover) and the longest call. The table is process-wide, as the
profiler is; ``clear()`` empties it.

Spans are host-side only: a span inside a jitted function would run once,
at trace time.

Names are ``feddcl.<layer>.<step>``. They are the benchmark's contract
with the program: its per-layer readers (``bench/metrics/``) look them up,
so renaming a span or a counter silences the metric that reads it.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List

from jax.profiler import TraceAnnotation

_OFF = contextlib.nullcontext()
_lock = threading.Lock()
_spans: Dict[str, List[int]] = {}   # name -> [n, total_ns, self_ns, max_ns]
_counters: Dict[str, int] = {}
_local = threading.local()           # .stack: the open spans of a thread


class _Span:
    __slots__ = ("name", "ann", "t0", "child_ns")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.ann = TraceAnnotation(self.name)
        self.ann.__enter__()
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(self)
        self.child_ns = 0
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter_ns() - self.t0
        stack = _local.stack
        stack.pop()
        if stack:
            stack[-1].child_ns += dt
        with _lock:
            rec = _spans.setdefault(self.name, [0, 0, 0, 0])
            rec[0] += 1
            rec[1] += dt
            rec[2] += dt - self.child_ns
            rec[3] = max(rec[3], dt)
        self.ann.__exit__(*exc)


def span(name: str):
    """A context manager that times its block as span `name` while the
    profiler collects, and does nothing otherwise."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return _Span(name)


def count(name: str, n: int = 1) -> None:
    """Add `n` to counter `name` while the profiler collects."""
    if not TraceAnnotation.is_enabled():
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + int(n)


def snapshot() -> dict:
    """A copy of the table: every span's calls and seconds, and every
    counter."""
    with _lock:
        return {
            "spans": {k: {"n": n, "total_s": tot * 1e-9,
                          "self_s": own * 1e-9, "max_s": mx * 1e-9}
                      for k, (n, tot, own, mx) in _spans.items()},
            "counters": dict(_counters)}


def clear() -> None:
    """Empty the table."""
    with _lock:
        _spans.clear()
        _counters.clear()
