"""The readers of the program's own spans and counters (``repro.obs``), on
hand-made tables: each reads its number, and reads nothing (``None``)
where its span or counter is missing or empty, or where the program has no
such table at all.

    PYTHONPATH=src python -m pytest bench/tests/test_program_readers.py -q
"""
from __future__ import annotations

import sys

import pytest

from bench import common, run

SPAN = {"n": 4, "total_s": 0.2, "self_s": 0.05, "max_s": 0.08}
TABLE = {
    "spans": {"feddcl.protocol.step2": SPAN, "feddcl.protocol.step3": SPAN,
              "feddcl.onboard.user": SPAN, "feddcl.serve.refresh_tables": SPAN,
              "feddcl.fl.prepare": SPAN},
    "counters": {"feddcl.device.fetches": 52,
                 "feddcl.fl.sample_steps": 20000,
                 "feddcl.fl.slot_steps": 32768},
}
COUNTERS = {"builds": 4, "admissions": 4}

# metric -> (its value on TABLE, the table entry it needs)
CASES = {
    "protocol.step2_ms": (50.0, ("spans", "feddcl.protocol.step2")),
    "protocol.step3_ms": (50.0, ("spans", "feddcl.protocol.step3")),
    "protocol.fetches": (13.0, ("counters", "feddcl.device.fetches")),
    "onboard.solve_ms": (50.0, ("spans", "feddcl.onboard.user")),
    "onboard.tables_ms": (50.0, ("spans", "feddcl.serve.refresh_tables")),
    "onboard.fetches": (13.0, ("counters", "feddcl.device.fetches")),
    "fl.prepare_ms": (50.0, ("spans", "feddcl.fl.prepare")),
    "fl.slot_use": (100.0 * 20000 / 32768, ("counters",
                                            "feddcl.fl.slot_steps")),
}


def _read(name, monkeypatch, table):
    monkeypatch.syspath_prepend(str(common.ROOT / "src"))
    from repro import obs
    monkeypatch.setattr(obs, "snapshot", lambda: table)
    return run.load_reader(name)({"counters": dict(COUNTERS)})


def test_every_program_reader_is_a_metric_of_its_own_cell():
    spec = common.load_json(common.ROOT / "BENCHMARK.json")
    mine = {m["name"]: m for m in spec["per_layer"] if m["name"] in CASES}
    assert set(mine) == set(CASES)
    for m in mine.values():
        assert m["source"] == "host_clock" and len(m["workloads"]) == 1


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_reads_the_program_table(name, monkeypatch):
    assert _read(name, monkeypatch, TABLE) == pytest.approx(CASES[name][0])


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_is_silent_without_its_entry(name, monkeypatch):
    kind, key = CASES[name][1]
    missing = {k: {e: v for e, v in TABLE[k].items() if e != key}
               for k in TABLE}
    assert _read(name, monkeypatch, missing) is None
    empty = {k: dict(v) for k, v in TABLE.items()}
    empty[kind][key] = dict(SPAN, n=0) if kind == "spans" else 0
    assert _read(name, monkeypatch, empty) is None


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_is_silent_on_a_program_without_the_table(name, monkeypatch):
    """A checkout whose program has no ``repro.obs`` (an earlier commit)."""
    assert _read(name, monkeypatch, TABLE) is not None
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    with pytest.raises(ImportError):
        from repro import obs  # noqa: F401
    assert run.load_reader(name)({"counters": dict(COUNTERS)}) is None
