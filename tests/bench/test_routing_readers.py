"""The readers of the GET receive windows' live slots,
``get_live_slot_share`` and ``get_owner_max_share``: known shares on
hand-made traces of four chips and of one, 25% for an even split over
four owners, and nothing where the ``kv.counters`` spans lack ``routed``
(a program that does not count its live slots)."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, roofline, trace  # noqa: E402
from bench.metrics import _program  # noqa: E402

MS = 1e6                                          # ns
READERS = ("get_live_slot_share", "get_owner_max_share")


def _traced(counters, chips=4):
    """A 100 ms window of GET calls 20 ms apart, the i-th over [20 i + 1,
    20 i + 10] ms with its ``kv.get_many`` inside and its ``kv.counters``
    (``counters[i]``, a dict of extra args or None for no span) emitted
    when the next call begins; one more call follows, past the last
    counters."""
    S = trace.Span
    spans = [S("bench.window", 0, 100 * MS, {})]
    program = []
    for i, extra in enumerate(list(counters) + [None]):
        t0 = 20 * i * MS
        spans.append(S("bench.get", t0 + MS, t0 + 10 * MS, {"live": 1}))
        program.append(S("kv.get_many", t0 + 2 * MS, t0 + 3 * MS,
                         {"seq": i + 1, "width": 256}))
        if extra is not None:
            program.append(S("kv.counters", t0 + 20.5 * MS, t0 + 20.5 * MS,
                             {"seq": i + 1, "kind": "get", "trips": 49,
                              "steps": 1, "lanes": 1, **extra}))
    program.sort(key=lambda s: s.start)
    f = lambda *x: np.asarray(x, float) * MS  # noqa: E731
    modules = {c: (f(1), f(90)) for c in range(chips)}
    t = trace.Traced(spans, modules, {}, {}, roofline.peak("TPU v5 lite"),
                     8, 7)
    return _program.attach(t, _program.Program(program, {}))


def _routed(per_owner, slots):
    return {"routed": sum(per_owner), "routed_max": max(per_owner),
            "slots": slots, "owners": len(per_owner)}


def _read(t):
    return {m: harness.reader(m)(t) for m in READERS}


def test_shares_on_a_hand_made_four_chip_trace():
    """Three full 256-row calls over four owners of 256 slots each, split
    (64, 64, 64, 64), (100, 60, 50, 46) and (256, 0, 0, 0)."""
    t = _traced([_routed([64] * 4, 256), _routed([100, 60, 50, 46], 256),
                 _routed([256, 0, 0, 0], 256)])
    read = _read(t)
    assert read["get_live_slot_share"] == pytest.approx(
        100 * 3 * 256 / (3 * 4 * 256))
    assert read["get_owner_max_share"] == pytest.approx(
        100 * (64 / 256 + 100 / 256 + 1) / 3)


def test_an_even_split_over_four_owners_reads_25_percent():
    t = _traced([_routed([64, 64, 64, 64], 256)] * 4)
    assert _read(t) == pytest.approx({"get_live_slot_share": 25.0,
                                      "get_owner_max_share": 25.0})


def test_a_padded_one_chip_call_reads_its_live_rows():
    """One owner of a 64-slot window: a call with 11 live rows and one
    with 5, as a GET call of ``ycsb-a.1chip`` is padded."""
    t = _traced([_routed([11], 64), _routed([5], 64)], chips=1)
    read = _read(t)
    assert read["get_live_slot_share"] == pytest.approx(100 * 16 / 128)
    assert read["get_owner_max_share"] == pytest.approx(100.0)


def test_calls_without_live_rows_count_slots_but_no_owner_share():
    t = _traced([_routed([0, 0, 0, 0], 256), _routed([8, 8, 8, 8], 256)])
    read = _read(t)
    assert read["get_live_slot_share"] == pytest.approx(100 * 32 / 2048)
    assert read["get_owner_max_share"] == pytest.approx(25.0)


def test_nothing_without_routed_counters():
    """The parent's ``kv.counters`` spans (no ``routed``), a window of
    calls whose counters were never emitted, and a program that does not
    trace itself."""
    parent = _traced([{"image_words": 528}] * 3)
    assert _read(parent) == {m: None for m in READERS}
    silent = _traced([None, None])
    assert _read(silent) == {m: None for m in READERS}
    t = _traced([_routed([64] * 4, 256)])
    bare = _program.attach(
        trace.Traced(t.spans, t.modules, {}, {}, t.peak, 8, 7),
        _program.Program([], {}))
    assert _read(bare) == {m: None for m in READERS}
