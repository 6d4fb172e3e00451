"""The program's own spans, scopes and counters in the trace reduction:
``bench/metrics/_program.py`` finds them in the window's trace beside the
benchmark's spans, the readers that use them give known answers on a
hand-made trace and nothing where the program does not trace itself, and
the readers that were there before read the kept chip fixture as they
did."""
import gc
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, roofline, trace  # noqa: E402
from bench.metrics import _program  # noqa: E402

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
MS = 1e6                                          # ns
OLD = ("device_idle_share", "service_host_ms", "get_device_ms",
       "update_device_ms", "a2a_ms", "get_roofline")
NEW = ("kv_host_ms", "get_stall_ms", "host_gc_ms", "get_route_ms",
       "get_vm_step_ms", "get_vm_lane_share", "update_row_ms")


def _hand_made(chips=1):
    """A 100 ms window: a GET call over [10, 50] ms (its ``kv.get_many``
    [11, 13], ops [14, 42] of which ``kv.route`` [14, 16] and [40, 42] and
    ``kv.get.vm`` [16, 40]), a SET call over [54, 90] (``kv.set_many``
    [56, 88] holding a ``kv.sync`` [60, 86], ops [61, 81] of which
    ``kv.set.scan`` [62, 80]) and a GET call over [92, 99] (``kv.get.vm``
    [95, 98]).  Each call's counters come at the start of the next;
    collections of the heap over [20, 25] and [99.5, 101]."""
    S = trace.Span
    spans = [S("bench.window", 0, 100 * MS, {}),
             S("bench.get", 10 * MS, 50 * MS, {"live": 40}),
             S("bench.set", 54 * MS, 90 * MS, {"live": 6}),
             S("bench.get", 92 * MS, 99 * MS, {"live": 30})]
    program = [
        S("kv.get_many", 11 * MS, 13 * MS, {"seq": 1, "width": 64}),
        S("host.gc", 20 * MS, 25 * MS, {"generation": 2, "collected": 0}),
        S("kv.counters", 55 * MS, 55 * MS,
          {"seq": 1, "kind": "get", "trips": 4, "steps": 12, "lanes": 16}),
        S("kv.set_many", 56 * MS, 88 * MS, {"seq": 2, "width": 16}),
        S("kv.sync", 60 * MS, 86 * MS, {"what": "status"}),
        S("kv.counters", 92.5 * MS, 92.5 * MS,
          {"seq": 2, "kind": "set", "scanned": 6, "scanned_max": 6,
           "escalated": 1}),
        S("kv.get_many", 93 * MS, 94 * MS, {"seq": 3, "width": 64}),
        S("host.gc", 99.5 * MS, 101 * MS, {"generation": 0, "collected": 3}),
    ]
    f = lambda *x: np.asarray(x, float) * MS  # noqa: E731
    modules = {c: (f(14, 61, 95), f(42, 81, 98)) for c in range(chips)}
    ops = {c: (["fusion.1", "while.2", "fusion.3", "while.4", "while.5"],
               f(14, 16, 61, 62, 95), f(16, 42, 62, 80, 98))
           for c in range(chips)}
    scoped = {c: (["kv.route", "kv.get.vm", "kv.route", "kv.route",
                   "kv.set.scan", "kv.route", "kv.get.vm"],
                  f(14, 16, 40, 61, 62, 80, 95), f(16, 40, 42, 62, 80, 81, 98))
              for c in range(chips)}
    t = trace.Traced(spans, modules, ops, {}, roofline.peak("TPU v5 lite"),
                     8, 4)
    return _program.attach(t, _program.Program(program, scoped))


@pytest.mark.parametrize("chips", [1, 4])
def test_new_readers_on_a_hand_made_trace(chips):
    t = _hand_made(chips)
    read = {m: harness.reader(m)(t) for m in NEW}
    assert read["kv_host_ms"] == pytest.approx((2 + (32 - 26) + 1) / 3)
    assert read["get_stall_ms"] == pytest.approx(((37 - 28) + (5 - 3)) / 2)
    assert read["host_gc_ms"] == pytest.approx((5 + 0.5) / 3)
    assert read["get_route_ms"] == pytest.approx((4 + 0) / 2)
    assert read["get_vm_step_ms"] == pytest.approx(24 / 4)
    assert read["get_vm_lane_share"] == pytest.approx(100 * 12 / 16)
    assert read["update_row_ms"] == pytest.approx(18 / 6)


def test_calls_the_trace_holds_no_device_event_of_are_left_out():
    """A GET call whose programs the trace did not record (its span lasts,
    the device shows nothing): its idle time is the trace's gap, not a
    stall, and it has no route or VM time to divide."""
    t = _hand_made()
    p = _program.of(t)
    modules = {0: (t.modules[0][0][:2], t.modules[0][1][:2])}
    scoped = {0: tuple(x[:6] for x in p.scoped[0])}
    blind = _program.attach(
        trace.Traced(t.spans, modules, t.ops, {}, t.peak, 8, 4),
        _program.Program(p.spans, scoped))
    read = {m: harness.reader(m)(blind) for m in NEW}
    assert read["get_stall_ms"] == pytest.approx(37 - 28)
    assert read["get_route_ms"] == pytest.approx(4)
    assert read["get_vm_step_ms"] == pytest.approx(24 / 4)
    assert read["update_row_ms"] == pytest.approx(18 / 6)


def test_program_fields_round_trip_through_json():
    t = _hand_made(2)
    p = _program.of(t)
    doc = json.loads(json.dumps({**t.to_json(), **p.to_json()}))
    back = _program.attach(trace.Traced.from_json(doc, t.peak, 8, 4),
                           _program.Program.from_json(doc))
    assert back.to_json() == t.to_json()
    assert _program.of(back).to_json() == p.to_json()
    assert [s.name for s in _program.of(back).spans] == \
        [s.name for s in p.spans]
    assert {m: harness.reader(m)(back) for m in NEW} == \
        {m: harness.reader(m)(t) for m in NEW}


def test_new_readers_find_nothing_where_the_program_does_not_trace(
        tmp_path, monkeypatch):
    """A trace of a program without spans, scopes or counters of its own
    (as the benchmark's first commit traced it), and a window whose trace
    is not on disk."""
    monkeypatch.setattr(_program, "TRACE_DIR", tmp_path)
    t = _hand_made()
    bare = trace.Traced(t.spans, t.modules, t.ops, t.a2a, t.peak, 8, 4)
    for m in NEW:
        assert harness.reader(m)(bare) is None, m
    assert _program.of(bare).spans == [] and _program.of(bare).scoped == {}
    nothing = _program.attach(
        trace.Traced(t.spans, t.modules, t.ops, t.a2a, t.peak, 8, 4),
        _program.Program([], {}))
    for m in NEW:
        assert harness.reader(m)(nothing) is None, m


def test_old_fixture_reads_as_before(tmp_path, monkeypatch):
    """The chip fixture kept from before the program traced itself loads
    unchanged: the readers that were there read the values they read
    then, and the new ones find nothing."""
    monkeypatch.setattr(_program, "TRACE_DIR", tmp_path)
    doc = json.loads((FIXTURES / "ycsb-a.1chip.trace.json").read_text())
    t = trace.Traced.from_json(doc, roofline.peak("TPU v5 lite"), 8, 4)
    p = _program.Program.from_json(doc)
    assert p.spans == [] and p.scoped == {}
    want = {"device_idle_share": 11.51289228330129,
            "service_host_ms": 125.98608499999999,
            "get_device_ms": 1180.0856306666667,
            "update_device_ms": 657.092765,
            "a2a_ms": None,
            "get_roofline": 1.3161024189711988e-07}
    for m in OLD:
        assert harness.reader(m)(t) == pytest.approx(want[m], rel=1e-12), m
    for m in NEW:
        assert harness.reader(m)(t) is None, m
    assert t.breakdown()["device_ops"][0] == ["while.186",
                                              pytest.approx(3.413281099)]


def test_readers_on_a_trace_recorded_on_the_chip():
    """A 7 s window of a ``--trace 1`` run of ``ycsb-a.1chip`` on a TPU v5e
    (3 GET and 3 SET calls), with the program's spans, counters and
    scoped ops, reduced by :func:`bench.trace.load` and
    :func:`bench.metrics._program.load` and kept in their compact form."""
    doc = json.loads(
        (FIXTURES / "ycsb-a.1chip.program.trace.json").read_text())
    t = trace.Traced.from_json(doc, roofline.peak("TPU v5 lite"), 8, 7)
    p = _program.Program.from_json(doc)
    _program.attach(t, p)
    assert t.n_chips == 1 and 0 < t.busy_s() <= t.window_s
    assert len(t.calls("bench.get")) == 3 and len(t.calls("bench.set")) == 3
    assert set(p.scoped[0][0]) == {"kv.route", "kv.get.vm", "kv.set.scan"}
    assert [c["trips"] for c in p.counters("get").values()] == [49] * 3
    read = {m: harness.reader(m)(t) for m in OLD + NEW}
    assert read["a2a_ms"] is None                 # one chip: no exchange
    assert all(read[m] is not None for m in NEW), read
    # a GET call: 49 trips of the VM loop at ~31.6 ms; on one chip the
    # route is a few microseconds; a SET call's scan ~62.5 ms a live row
    assert 30 < read["get_vm_step_ms"] < 33
    assert read["get_vm_lane_share"] == pytest.approx(100.0)
    assert read["get_route_ms"] < 0.1
    assert 55 < read["update_row_ms"] < 70
    assert read["get_vm_step_ms"] * 49 < read["get_device_ms"]
    assert 0 < read["kv_host_ms"] < read["service_host_ms"]
    assert read["get_stall_ms"] >= 0 and read["host_gc_ms"] >= 0
    assert trace.Traced.from_json(t.to_json()).to_json() == t.to_json()
    assert _program.Program.from_json(p.to_json()).to_json() == p.to_json()


def _small_service():
    from repro.rdma import failure

    svc = failure.ShardedKVService.start(
        [(k, [k, k + 1]) for k in range(1, 200)], n_shards=1,
        buckets_per_shard=1 << 10, val_words=2)
    q = np.zeros((1, 16), np.int32)
    q[0, :10] = np.arange(1, 11)
    sk = np.zeros((1, 8), np.int32)
    sk[0, :3] = [3, 500, 501]
    sv = np.ones((1, 8, 2), np.int32)
    return svc, q, sk, sv


def _traced_window(path, calls) -> trace.Traced:
    """Trace ``calls()`` inside a ``bench.window`` span as ``bench/run.py``
    does, under ``path``, and reduce it with :func:`bench.trace.load`; the
    trace stays on disk."""
    import jax

    tracer = trace.Tracer(path)
    tracer.start()
    with jax.profiler.TraceAnnotation("bench.window"):
        calls()
    tracer.stop()
    return trace.load(tracer.xplane())


def test_service_spans_reach_the_loader(tmp_path, monkeypatch):
    """Under a profiler trace on the CPU, a 2^10-bucket service's calls
    leave ``kv.get_many``, ``kv.set_many``, ``kv.sync``, ``kv.counters``
    and a forced collection's ``host.gc`` in the window's
    :class:`bench.metrics._program.Program`, found as a reader finds it,
    beside the benchmark's own spans."""
    import jax

    monkeypatch.setattr(_program, "TRACE_DIR", tmp_path)
    svc, q, sk, sv = _small_service()
    jax.device_get(svc.get_many(q).found)        # compiles outside the trace
    jax.device_get(svc.set_many(sk, sv).applied)

    def calls():
        with jax.profiler.TraceAnnotation("bench.get", live=10):
            jax.device_get(svc.get_many(q).found)
        with jax.profiler.TraceAnnotation("bench.set", live=3):
            jax.device_get(svc.set_many(sk, sv).applied)
        gc.collect()
        with jax.profiler.TraceAnnotation("bench.get", live=10):
            jax.device_get(svc.get_many(q).found)

    t = _traced_window(tmp_path / "cell", calls)
    assert [s.name for s in t.spans] == ["bench.window", "bench.get",
                                         "bench.set", "bench.get"]
    p = _program.of(t)
    names = {s.name for s in p.spans}
    assert {"kv.get_many", "kv.set_many", "kv.sync", "kv.counters",
            "host.gc"} <= names
    gets = p.spans_in("kv.get_many", *t.window)
    sets = p.spans_in("kv.set_many", *t.window)
    assert [s.args["width"] for s in gets] == [16, 16]
    assert sets[0].args["seq"] == gets[0].args["seq"] + 1
    got = p.counters("get")[gets[0].args["seq"]]
    assert got["trips"] > 0 and 0 < got["steps"] <= got["lanes"]
    assert p.counters("set")[sets[0].args["seq"]]["scanned"] == 3
    sync = p.spans_in("kv.sync", sets[0].start, sets[0].end)
    assert [s.args["what"] for s in sync] == ["status"]
    assert any(s.name == "host.gc" and s.args["generation"] == 2
               for s in p.spans)
    assert harness.reader("kv_host_ms")(t) > 0
    assert harness.reader("get_vm_lane_share")(t) > 0


def test_readers_find_the_trace_of_their_own_window(tmp_path, monkeypatch):
    """Two traces on disk, as a run that was cut leaves one behind: each
    window reads the program's spans of its own trace, the older one
    included, and a window of neither trace reads nothing."""
    import jax

    monkeypatch.setattr(_program, "TRACE_DIR", tmp_path)
    svc, q, _, _ = _small_service()
    jax.device_get(svc.get_many(q).found)

    def one_get():
        with jax.profiler.TraceAnnotation("bench.get", live=10):
            jax.device_get(svc.get_many(q).found)

    old = _traced_window(tmp_path / "old", one_get)
    new = _traced_window(tmp_path / "new", lambda: (one_get(), one_get()))
    seqs = [[s.args["seq"] for s in _program.of(t).spans_in(
        "kv.get_many", *t.window)] for t in (old, new)]
    assert len(seqs[0]) == 1 and len(seqs[1]) == 2
    assert seqs[1][0] > seqs[0][0]
    other = trace.Traced([trace.Span("bench.window", 0.0, 1.0, {})],
                         {}, {}, {})
    assert _program.of(other).spans == []
    assert harness.reader("kv_host_ms")(other) is None


def _vint(x: int) -> bytes:
    out = bytearray()
    while True:
        b, x = x & 0x7F, x >> 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _msg(field: int, payload: bytes) -> bytes:
    return _vint(field << 3 | 2) + _vint(len(payload)) + payload


def _int(field: int, value: int) -> bytes:
    return _vint(field << 3) + _vint(value)


def test_scopes_of_top_level_ops_come_from_the_programs_hlo(tmp_path):
    """A program compiled here, with ops in two ``kv.*`` scopes, written
    into a trace's metadata plane as the profiler writes it: the loader
    names the scope of each of its top-level ops, and of no op of a
    program whose HLO the trace lacks."""
    import jax
    import jax.numpy as jnp

    def body(x):
        with jax.named_scope("kv.route"):
            y = jnp.sort(x)
        with jax.named_scope("kv.get.vm"):
            return jax.lax.fori_loop(0, 3, lambda i, v: v * 2 + 1, y)

    module = jax.jit(body).lower(jnp.ones(8)).compile() \
        .runtime_executable().hlo_modules()[0]
    hlo = module.as_serialized_hlo_module_proto()
    stat = _int(1, 7) + _msg(6, _msg(1, hlo))
    event = _int(1, 1) + _msg(2, b"jit_body(1)") + _msg(5, stat)
    plane = (_msg(2, _program.METADATA_PLANE.encode())
             + _msg(4, _int(1, 1) + _msg(2, event))
             + _msg(5, _int(1, 7)
                    + _msg(2, _int(1, 7)
                           + _msg(2, _program.HLO_STAT.encode()))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg(1, _msg(2, b"/host:CPU")) + _msg(1, plane))
    scopes = _program.program_scopes(path)
    assert list(scopes) == ["jit_body(1)"]
    by_op = {op.split(".")[0]: s for op, s in scopes["jit_body(1)"].items()}
    assert by_op["sort"] == "kv.route" and by_op["while"] == "kv.get.vm"

    sort_op = next(op for op in scopes["jit_body(1)"] if op.startswith("sort"))
    ops = ([sort_op, "copy.1", sort_op], np.asarray([1.0, 3.0, 12.0]),
           np.asarray([2.0, 4.0, 13.0]))
    programs = (["jit_body(1)", "jit_other(2)"], np.asarray([0.0, 10.0]),
                np.asarray([5.0, 15.0]))
    names, s, e = _program._scoped_ops(ops, programs, scopes)
    assert names == ["kv.route"]
    assert s.tolist() == [1.0] and e.tolist() == [2.0]
