"""From a profiler trace to the benchmark's device numbers.

A traced run writes one ``.xplane.pb`` under its trace directory. This
module reads it with ``jax.profiler.ProfileData`` into intervals on one
clock (nanoseconds):

- host spans: the benchmark's own ``TraceAnnotation`` spans (``bench.*``);
- device ops: every leaf operation that ran on a TPU (the ``XLA Ops`` line
  of each ``/device:TPU:<n>`` plane; a while loop's own interval is left
  out, its body's ops are there). An event's name is the op's HLO text;
  its label is the op's name (a Pallas call is named after its kernel),
  with the library routine a custom call runs.

and reduces them over the traced window, the ``bench.window`` span:

- ``busy_s``: the union of each chip's op intervals inside the window,
  averaged over the chips; ``window_s`` the window's length; the device's
  idle share is 1 - busy_s / window_s;
- per-op device time, summed by label;
- idle gaps: each interval of the window in which no op ran on a chip,
  attributed to the innermost benchmark span (other than the window) that
  covers the gap's midpoint, summed by span name.

Only numbers measured on the chip come out of here; the reduction itself
is pure and is tested on hand-made and recorded intervals (bench/tests).
"""
from __future__ import annotations

import glob
import pathlib
import re
import shutil
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# HLO ops that only hold other ops: their interval covers their body's
CONTAINERS = ("while", "conditional", "call")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
OPCODE = re.compile(r"\}?\s*([a-z][a-z0-9-]*)\(")


class BuildCounter:
    """Executables JAX builds (compiled, or read from the persistent
    cache) while the ``with`` block runs: 0 means the window ran only
    programs it already held."""

    def __init__(self) -> None:
        self.count = 0

    def _listen(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1

    def __enter__(self) -> "BuildCounter":
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._listen)


@dataclass
class Op:
    chip: str
    label: str
    start: float
    end: float


@dataclass
class Span:
    name: str
    start: float
    end: float


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    chips: int
    op_s: Dict[str, float] = field(default_factory=dict)
    op_n: Dict[str, int] = field(default_factory=dict)
    gap_s: Dict[str, float] = field(default_factory=dict)
    span_s: Dict[str, float] = field(default_factory=dict)
    span_n: Dict[str, int] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, needle: str) -> Tuple[float, int]:
        """Device seconds (summed over chips) and count of the ops whose
        label holds `needle`."""
        s = sum(v for k, v in self.op_s.items() if needle in k)
        n = sum(v for k, v in self.op_n.items() if needle in k)
        return s, n

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gap_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _union(start: np.ndarray, end: np.ndarray, lo: float, hi: float):
    """Sorted disjoint union of intervals clipped to [lo, hi]."""
    s, e = np.maximum(start, lo), np.minimum(end, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if not len(s):
        return s, e
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    return s[new], reach[np.r_[np.flatnonzero(new)[1:] - 1, len(s) - 1]]


def _gaps(bs: np.ndarray, be: np.ndarray, lo: float, hi: float):
    """The complement of a sorted disjoint union inside [lo, hi]."""
    g0, g1 = np.r_[lo, be], np.r_[bs, hi]
    keep = g1 > g0
    return g0[keep], g1[keep]


def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    iv = np.asarray(intervals, np.float64).reshape(-1, 2)
    s, e = _union(iv[:, 0], iv[:, 1], lo, hi)
    return list(zip(s.tolist(), e.tolist()))


def gaps_of(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    b = np.asarray(busy, np.float64).reshape(-1, 2)
    g0, g1 = _gaps(b[:, 0], b[:, 1], lo, hi)
    return list(zip(g0.tolist(), g1.tolist()))


def _attribute(g0: np.ndarray, g1: np.ndarray,
               spans: Sequence[Span]) -> List[str]:
    """For each gap, the innermost span that covers its midpoint;
    "outside spans" where none does."""
    mids = 0.5 * (g0 + g1)
    names = ["outside spans"] + [sp.name for sp in spans]
    lab = np.zeros(len(mids), np.int64)
    # longest first, so a shorter (inner) span overwrites its parent
    for k in sorted(range(len(spans)),
                    key=lambda k: spans[k].start - spans[k].end):
        i0 = np.searchsorted(mids, spans[k].start, "left")
        i1 = np.searchsorted(mids, spans[k].end, "right")
        lab[i0:i1] = k + 1
    return [names[i] for i in lab]


def reduce(spans: Sequence[Span], ops: Sequence[Op]) -> Reduced:
    """Reduce one traced window from a list of ops."""
    chips: Dict[str, Tuple[list, list, list]] = {}
    for o in ops:
        c = chips.setdefault(o.chip, ([], [], []))
        c[0].append(o.start)
        c[1].append(o.end)
        c[2].append(o.label)
    return reduce_arrays(spans, {k: (np.asarray(a, np.float64),
                                     np.asarray(b, np.float64), lab)
                                 for k, (a, b, lab) in chips.items()})


def reduce_arrays(spans: Sequence[Span], chips: Dict[str, tuple]) -> Reduced:
    """Reduce one traced window; `chips`: chip -> (op starts, op ends,
    op labels)."""
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(win)}")
    if not chips:
        raise ValueError("no device op in the trace")
    lo, hi = win[0].start, win[0].end
    red = Reduced(window_s=(hi - lo) * 1e-9, busy_s=0.0, chips=len(chips))
    inside = [s for s in spans if s.start >= lo and s.end <= hi
              and s.name != WINDOW_SPAN]
    for sp in inside:
        red.span_s[sp.name] = red.span_s.get(sp.name, 0.0) + (
            sp.end - sp.start) * 1e-9
        red.span_n[sp.name] = red.span_n.get(sp.name, 0) + 1
    n = len(chips)
    for chip, (start, end, labels) in sorted(chips.items()):
        bs, be = _union(start, end, lo, hi)
        red.busy_s += float(np.sum(be - bs)) * 1e-9 / n
        g0, g1 = _gaps(bs, be, lo, hi)
        for key, dt in zip(_attribute(g0, g1, inside), (g1 - g0).tolist()):
            red.gap_s[key] = red.gap_s.get(key, 0.0) + dt * 1e-9 / n
        dur = np.minimum(end, hi) - np.maximum(start, lo)
        table, idx = np.unique(np.asarray(labels, dtype=object).astype(str),
                               return_inverse=True)
        on = dur > 0
        secs = np.bincount(idx[on], weights=dur[on], minlength=len(table))
        cnt = np.bincount(idx[on], minlength=len(table))
        for lab, sec, c in zip(table.tolist(), secs.tolist(), cnt.tolist()):
            if c:
                red.op_s[lab] = red.op_s.get(lab, 0.0) + sec * 1e-9
                red.op_n[lab] = red.op_n.get(lab, 0) + int(c)
    return red


def op_label(text: str) -> Tuple[str, Optional[str]]:
    """(label, opcode) of an XLA Ops event, whose name is the op's HLO
    text: the op's name (a Pallas call is named after its kernel), with
    the library routine a custom call runs (e.g. EighTpu)."""
    name, _, rest = text.partition(" = ")
    m = OPCODE.search(rest)
    opcode = m.group(1) if m else None
    t = TARGET.search(rest) if opcode == "custom-call" else None
    if t and t.group(1) != "tpu_custom_call":
        return f"{name} {t.group(1)}", opcode
    return name, opcode


def read_xplane(path: str) -> Tuple[List[Span], Dict[str, tuple]]:
    """Benchmark spans, and each TPU's leaf ops (starts, ends, labels), of
    one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans: List[Span] = []
    chips: Dict[str, tuple] = {}
    labels: Dict[str, Tuple[str, Optional[str]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/device:TPU:"):
            starts, ends, labs = [], [], []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    text = ev.name
                    if text not in labels:
                        labels[text] = op_label(text)
                    label, opcode = labels[text]
                    if opcode in CONTAINERS:
                        continue
                    t = ev.start_ns
                    starts.append(t)
                    ends.append(t + ev.duration_ns)
                    labs.append(label)
            if starts:
                chips[plane.name] = (np.asarray(starts, np.float64),
                                     np.asarray(ends, np.float64), labs)
    return spans, chips


def reduce_dir(trace_dir: pathlib.Path, keep: bool = False) -> Reduced:
    """Reduce the one trace under `trace_dir`, then delete the directory
    (traces are large; only the reduction is kept) unless `keep`."""
    files = glob.glob(str(pathlib.Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, "
                         f"found {len(files)}")
    try:
        return reduce_arrays(*read_xplane(files[0]))
    finally:
        if not keep:
            shutil.rmtree(trace_dir, ignore_errors=True)
