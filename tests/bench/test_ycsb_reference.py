"""The closed loop and the reference, driven with a plain in-memory
service: call order, re-issue of rows the service did not serve, latency
from first issue, and the table comparison."""
import json
import pathlib
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import loop, reference, ycsb  # noqa: E402


class FakeClock:
    """Advances one second per call of the service, nothing otherwise."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class DictService:
    """A correct single-shard service; ``refuse`` maps a key to how many
    times it answers ``ok=False`` (not served) before serving it."""

    def __init__(self, keys, vals, clock, refuse=None, refuse_calls=0):
        self.table = {int(k): np.array(v) for k, v in zip(keys, vals)}
        self.clock = clock
        self.refuse = dict(refuse or {})
        self.refuse_calls = refuse_calls

    def _served(self, k):
        if self.refuse_calls > 0:
            return False
        if self.refuse.get(k, 0) > 0:
            self.refuse[k] -= 1
            return False
        return True

    def get_many(self, q):
        self.clock.t += 1.0
        q = np.asarray(q)
        ok = np.array([[k != 0 and self._served(int(k)) for k in row]
                       for row in q])
        self.refuse_calls -= 1
        found = np.array([[ok[s, j] and int(k) in self.table
                           for j, k in enumerate(row)]
                          for s, row in enumerate(q)])
        vals = np.zeros(q.shape + (4,), np.int32)
        for s, j in zip(*np.nonzero(found)):
            vals[s, j] = self.table[int(q[s, j])]
        return SimpleNamespace(found=found, values=vals, ok=ok)

    def set_many(self, k, v):
        self.clock.t += 1.0
        k = np.asarray(k)
        ok = np.array([[x != 0 and self._served(int(x)) for x in row]
                       for row in k])
        for s, j in zip(*np.nonzero(ok)):
            self.table[int(k[s, j])] = np.array(v[s, j])
        return SimpleNamespace(applied=ok.copy(), ok=ok)


def _loop(svc, clock, mix="ycsb-a", records=64, shards=1, clients=8):
    keys, vals = ycsb.records(3, records, 4)
    m = ycsb.Mix.load(mix)
    stream = ycsb.OpStream(m, records, 4, seed=11)
    shape = loop.Shape(shards, 8 * shards, 4 * shards, 4)
    return keys, vals, loop.ClosedLoop(svc, shape, stream, keys, clients,
                                       clock=clock)


def _check(keys, vals, closed):
    ref = reference.Reference(keys, vals)
    tally = reference.Tally()
    updated = reference.replay(ref, closed.calls, tally)
    return ref, tally, updated


def test_a_correct_service_matches_and_every_answer_is_compared():
    clock = FakeClock()
    keys, vals = ycsb.records(3, 64, 4)
    svc = DictService(keys, vals, clock)
    _, _, closed = _loop(svc, clock)
    closed.serve_window(20).drain()
    ref, tally, updated = _check(keys, vals, closed)
    n_answered = sum(len(v) for v in closed.latency.values())
    assert tally["get_compared"] + tally["update_compared"] == n_answered
    assert tally["get_mismatches"] == tally["update_mismatches"] == 0
    assert updated and closed.failed == 0
    assert closed.answered_in_window == closed.attempted
    table_k = np.array(list(svc.table))[None]
    table_v = np.array(list(svc.table.values()))[None]
    reference.compare_table(ref, table_k, table_v, tally)
    assert tally["table_mismatches"] == 0


def test_updates_apply_in_call_order_and_source_major_rows():
    """A GET call precedes the SET call of its step, and two updates of
    one key in one SET call leave the later row's value."""
    keys = np.array([5, 6], np.int32)
    vals = np.array([[1, 1, 1, 1], [2, 2, 2, 2]], np.int32)
    ref = reference.Reference(keys, vals)
    k = np.array([[5, 5], [6, 0]], np.int32)
    v = np.arange(16, dtype=np.int32).reshape(2, 2, 4)
    get = loop.Call(ycsb.READ, np.array([[5], [6]], np.int32), None, 0, 1,
                    True, ok=np.ones((2, 1), bool),
                    found=np.ones((2, 1), bool),
                    values=np.array([[[1] * 4], [[2] * 4]], np.int32))
    put = loop.Call(ycsb.UPDATE, k, v, 1, 2, True,
                    ok=np.array([[1, 1], [1, 0]], bool),
                    applied=np.array([[1, 1], [1, 0]], bool))
    tally = reference.Tally()
    assert reference.replay(ref, [get, put], tally) == {5, 6}
    assert tally["get_mismatches"] == 0 and tally["update_compared"] == 3
    assert ref.get(5) == (True, (4, 5, 6, 7))
    assert ref.get(6) == (True, (8, 9, 10, 11))
    # the same GET after the SET now disagrees with the reference
    tally = reference.Tally()
    reference.read_back(ref, [get], tally)
    assert tally["readback_mismatches"] == 2


def test_unserved_rows_are_reissued_and_timed_from_first_issue():
    clock = FakeClock()
    keys, vals = ycsb.records(3, 64, 4)
    svc = DictService(keys, vals, clock, refuse_calls=2)
    _, _, closed = _loop(svc, clock, mix="ycsb-c", clients=8)
    closed.serve_window(10).drain()
    assert closed.reissued == 16 and closed.failed == 0
    # the 8 first requests, issued at 0, were refused by two calls of 1 s
    # each and answered by the third
    lat = closed.latency[ycsb.READ]
    assert lat[:8] == [3.0] * 8 and set(lat[8:]) == {1.0}
    assert closed.attempted == len(lat)
    _, tally, _ = _check(keys, vals, closed)
    assert tally["get_mismatches"] == 0
    assert tally["get_compared"] == len(lat)


def test_a_request_never_served_is_failed():
    clock = FakeClock()
    keys, vals = ycsb.records(3, 64, 4)
    svc = DictService(keys, vals, clock, refuse={int(keys[0]): 10**9})
    _, _, closed = _loop(svc, clock, mix="ycsb-c", records=64)
    closed.serve_window(200).drain(limit_s=5)
    assert closed.failed >= 1
    assert closed.attempted >= closed.answered_in_window + closed.failed


def test_table_comparison_finds_each_fault():
    keys = np.array([3, 4, 5], np.int32)
    vals = np.arange(12, dtype=np.int32).reshape(3, 4)
    ref = reference.Reference(keys, vals)

    def table(rows):
        k = np.zeros(8, np.int32)
        v = np.zeros((8, 4), np.int32)
        for i, (key, val) in rows.items():
            k[i], v[i] = key, val
        return k[None], v[None]

    good = {0: (3, vals[0]), 2: (4, vals[1]), 7: (5, vals[2])}
    cases = {
        "right": (good, 0),
        "lost key": ({0: (3, vals[0]), 2: (4, vals[1])}, 1),
        "wrong value": ({**good, 2: (4, vals[0])}, 1),
        "unknown key": ({**good, 5: (9, vals[0])}, 1),
        "two copies": ({**good, 5: (3, vals[0])}, 1),
    }
    for name, (rows, want) in cases.items():
        tally = reference.Tally()
        reference.compare_table(ref, *table(rows), tally)
        assert tally["table_mismatches"] == want, name
    k, v = table(good)
    v[0, 4] = [0, 0, 7, 0]                   # a value left in an empty bucket
    tally = reference.Tally()
    reference.compare_table(ref, k, v, tally)
    assert tally["table_mismatches"] == 1


@pytest.mark.parametrize("kinds", [ycsb.KIND_STREAM, 1, 2])
@pytest.mark.parametrize("extra_clients,waits_a_second_set", [(0, False),
                                                               (16, True)])
def test_no_update_of_ycsb_a_waits_a_second_set_call(
        extra_clients, waits_a_second_set, kinds, monkeypatch):
    """At ``ycsb-a``'s clients and ``ycsb-1x2p20``'s call widths every
    update is answered by the first SET call after its issue, whatever
    the order of kinds: one call (issued after a GET call) or a GET and a
    SET call (issued after a SET call).  With more clients than the SET
    call has rows, some wait longer."""
    monkeypatch.setattr(ycsb, "KIND_STREAM", kinds)
    cfg = json.loads((ROOT / "bench/configs/ycsb-1x2p20.json").read_text())
    mix = ycsb.Mix.load("ycsb-a")
    clock = FakeClock()
    keys, vals = ycsb.records(3, 512, 4)
    stream = ycsb.OpStream(mix, 512, 4, seed=2**31 + 3)
    shape = loop.Shape(1, cfg["get_width"], cfg["set_width"], 4)
    closed = loop.ClosedLoop(DictService(keys, vals, clock), shape, stream,
                             keys, mix.clients + extra_clients, clock=clock)
    closed.serve_window(400.0)
    lat = closed.latency[ycsb.UPDATE]
    assert len(lat) > 1000
    assert (max(lat) > 2.0) == waits_a_second_set
