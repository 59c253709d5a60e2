"""Compiled-artifact auditor: enforce the privacy and performance
invariants on the EXECUTABLE, not just the source (DESIGN.md §9).

Three tools, each generalizing a check that previously lived as ad-hoc
code inside individual tests:

`collective_census(lowered)` — the collective-op histogram of a compiled
    module. A sharded weighted plan must hold exactly
    {all-reduce: leaves+1} per hierarchy level, a robust plan
    {all-reduce: 1, all-gather: leaves+1}, and an UNSHARDED plan no
    collective at all (tests/test_fed_sharded.py, tests/test_fed_robust.py,
    benchmarks/fed_bench.py --sharded all consume this one function now).

`assert_no_baked_data(lowered)` — the artifact-level privacy check. Before
    data-as-arguments plans (PR 3) the jitted runner closed over tenant
    arrays and XLA baked them into the executable as large dense
    constants: raw silo data INSIDE the compiled artifact, the exact
    non-sharing guarantee FedDCL exists to provide (arXiv 2409.18356)
    broken where no source-level review would see it. This walks the
    lowered StableHLO for large non-splat constants and raises
    `BakedDataError` naming them. Splat constants (zeros/ones fills from
    padding or init) carry no information and pass at any size.

`CompileCounter` — a recompile sentinel: counts executable builds inside
    a `with` block through a public `jax.monitoring` listener. Warm-path
    tests assert `count == 0` directly instead of inferring "no recompile"
    from a 29–60× timing ratio that goes flaky on loaded CI runners
    (tests/test_plan_cache.py).
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Tuple

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "all-to-all",
                    "collective-permute", "reduce-scatter")


def _as_compiled_text(lowered: Any) -> str:
    """Compiled-HLO text from a jax Lowered/Compiled/str. Async collective
    forms appear post-compile, so the census always counts the compiled
    module (what actually runs), not the StableHLO input."""
    if isinstance(lowered, str):
        return lowered
    if hasattr(lowered, "compile"):           # jax.stages.Lowered
        lowered = lowered.compile()
    if hasattr(lowered, "as_text"):           # jax.stages.Compiled
        return lowered.as_text()
    raise TypeError(
        f"expected a jax Lowered/Compiled or HLO text, got {type(lowered)}")


def collective_census(lowered: Any,
                      kinds: Tuple[str, ...] = COLLECTIVE_KINDS
                      ) -> Dict[str, int]:
    """Histogram of collective operands in a compiled module, keyed by kind,
    zero-count kinds omitted. Each collective instruction counts once per
    operand: XLA's combiner passes merge several same-kind collectives into
    ONE tuple-shaped instruction (`(f32[8], f32[4,8], …) all-reduce(%a, %b,
    …)`), so counting operands keeps the pinned counts (one per param leaf
    plus one for the loss) independent of how the compiler grouped them.
    TPU layouts carry parentheses (`{1,0:T(8,128)}`), so a tuple type is
    matched lazily up to the `) kind(` that closes it. Async `-start` forms
    count once (`-done` lines don't match, so start/done pairs aren't
    double-counted). Pre-optimization HLO (`Lowered.as_text(dialect="hlo")`)
    passes as a string and counts the collectives the program asks for."""
    txt = _as_compiled_text(lowered)
    out: Dict[str, int] = {}
    for kind in kinds:
        n = sum(max(args.count("%"), 1) for args in re.findall(
            rf"= (?:\(.*?\)|\S+) {kind}(?:-start)?\(([^()]*)\)", txt))
        if n:
            out[kind] = n
    return out


class BakedDataError(AssertionError):
    """The lowered program embeds a large dense constant — tenant data (or
    another runtime-sized array) was captured by closure and baked into
    the executable instead of entering as an argument."""


def _stablehlo_text(lowered: Any) -> str:
    if isinstance(lowered, str):
        return lowered
    if hasattr(lowered, "as_text"):           # Lowered: StableHLO pre-compile
        return lowered.as_text()
    raise TypeError(
        f"expected a jax Lowered or StableHLO text, got {type(lowered)}")


_CONST_RE = re.compile(
    r"(?:stablehlo\.constant|mhlo\.constant)\s+"
    r"(dense<[^>]*>|dense_resource<[^>]*>)\s*:\s*tensor<([^>]*)>")


def _tensor_elems(tensor_sig: str) -> Tuple[int, str]:
    """("64x32xf32") -> (2048, "f32"); scalar signatures have no dims."""
    parts = tensor_sig.split("x")
    dims = [p for p in parts if p.isdigit()]
    dtype = parts[-1]
    n = 1
    for d in dims:
        n *= int(d)
    return n, dtype


def find_baked_constants(lowered: Any, min_elems: int = 1024
                         ) -> List[Dict[str, Any]]:
    """Large NON-SPLAT dense constants in the lowered StableHLO.

    A splat (`dense<0.0e+00> : tensor<128x64xf32>`) encodes one value —
    a padding/init fill, not data. A non-splat literal (an element list
    `dense<[...]>`, a raw hex blob `dense<"0x...">`, or an elided
    `dense_resource<...>` — MLIR elides literals precisely because they
    are big) of `min_elems` or more elements is a baked array."""
    txt = _stablehlo_text(lowered)
    found: List[Dict[str, Any]] = []
    for m in _CONST_RE.finditer(txt):
        literal, sig = m.group(1), m.group(2)
        body = literal[literal.index("<") + 1:-1]
        non_splat = (literal.startswith("dense_resource")
                     or body.startswith("[") or body.startswith('"'))
        if not non_splat:
            continue
        elems, dtype = _tensor_elems(sig)
        if elems >= min_elems:
            found.append({"elements": elems, "dtype": dtype,
                          "type": f"tensor<{sig}>",
                          "literal_head": literal[:48]})
    return found


def assert_no_baked_data(lowered: Any, min_elems: int = 1024) -> None:
    """Raise `BakedDataError` if the lowered program embeds any non-splat
    dense constant of >= min_elems elements — the PR 3 artifact-level
    privacy leak (tenant arrays inside the compiled plan). Passing means:
    every runtime-sized array reaches the executable as an ARGUMENT."""
    baked = find_baked_constants(lowered, min_elems=min_elems)
    if baked:
        detail = ", ".join(
            f"{b['type']} ({b['elements']} elems)" for b in baked[:8])
        raise BakedDataError(
            f"lowered program embeds {len(baked)} dense constant(s) of "
            f">={min_elems} elements: {detail} — data captured by closure "
            "is baked into the executable (the non-sharing guarantee "
            "broken at the artifact level); pass arrays as plan arguments "
            "(core/federated.make_fl_plan)")


BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Count executable builds inside a `with` block.

    Listens (`jax.monitoring`) for the duration event JAX records around
    every executable it obtains for a lowered computation: a fresh XLA
    compile or a persistent-compilation-cache disk read. In-memory hits (the
    jit C++ cache, plan-cache hits) obtain no executable and record nothing,
    so `count == 0` IS "the warm path built nothing", with none of the
    timing-ratio flakiness. Counters nest; the listener is removed on exit
    even on error."""

    def __init__(self) -> None:
        self.count = 0

    def _listener(self, event: str, duration: float, **kwargs) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1

    def __enter__(self) -> "CompileCounter":
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listener)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._listener)
        return None
