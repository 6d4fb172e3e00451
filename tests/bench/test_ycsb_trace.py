"""The trace reduction: interval arithmetic, every per-layer reader on a
hand-made trace with known answers, and every reader on a short trace
recorded on a TPU v5e and kept as a fixture."""
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, roofline, trace  # noqa: E402

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
MS = 1e6                                          # ns


def test_union_and_cover():
    m = trace._merge(np.array([5.0, 0.0, 2.0, 10.0]),
                     np.array([7.0, 3.0, 4.0, 11.0]))
    assert m.tolist() == [[0, 4], [5, 7], [10, 11]]
    assert trace._covered(m, 3, 10.5) == 1 + 2 + 0.5
    assert trace._covered(trace._merge(np.array([]), np.array([])), 0, 9) == 0


def _hand_made(chips=1):
    """A 100 ms window: a GET call over [10, 50] ms whose device ops run
    [12, 42] ms (an all-to-all [12, 14] among them), and a SET call over
    [60, 90] ms with ops [61, 81] ms."""
    S = trace.Span
    spans = [S("bench.window", 0, 100 * MS, {}),
             S("bench.get", 10 * MS, 50 * MS, {"live": 64}),
             S("bench.answers", 50 * MS, 50 * MS,
               {"kind": "get", "live": 64, "hits": 60}),
             S("bench.set", 60 * MS, 90 * MS, {"live": 16}),
             S("bench.answers", 90 * MS, 90 * MS,
               {"kind": "set", "live": 16, "hits": 16})]
    names = ["all-to-all.1", "fusion.7", "while.3"]
    starts = np.array([12, 14, 61]) * MS * 1.0
    ends = np.array([14, 42, 81]) * MS * 1.0
    modules = {c: (np.array([12, 61]) * MS * 1.0,
                   np.array([42, 81]) * MS * 1.0) for c in range(chips)}
    ops = {c: (names, starts, ends) for c in range(chips)}
    a2a = {c: (starts[:1], ends[:1]) for c in range(chips)}
    return trace.Traced(spans, modules, ops, a2a,
                        roofline.peak("TPU v5 lite"), 8, 4)


@pytest.mark.parametrize("chips", [1, 4])
def test_readers_on_a_hand_made_trace(chips):
    t = _hand_made(chips)
    read = {m: harness.reader(m)(t) for m in
            ("device_idle_share", "service_host_ms", "get_device_ms",
             "update_device_ms", "a2a_ms", "get_roofline")}
    assert t.window_s == pytest.approx(0.1)
    assert t.busy_s() == pytest.approx(0.05)
    assert read["device_idle_share"] == pytest.approx(50.0)
    assert read["get_device_ms"] == pytest.approx(30.0)
    assert read["update_device_ms"] == pytest.approx(20.0)
    assert read["service_host_ms"] == pytest.approx((10 + 10) / 2)
    assert read["a2a_ms"] == pytest.approx(2.0)
    least = roofline.get_bytes(64, 60, 8, 4) / (819e9 * chips)
    assert read["get_roofline"] == pytest.approx(100 * least / 0.030)
    b = t.breakdown()
    assert b["device_ops"][0] == ["fusion.7", pytest.approx(0.028)]
    gap, name = b["idle_gaps"][0][1], b["idle_gaps"][0][0]
    assert gap == pytest.approx(0.019) and name.endswith("bench.loop "
                                                         "(between calls)")


def test_readers_find_nothing_without_device_events():
    t = _hand_made()
    empty = trace.Traced(t.spans, {}, {}, {}, t.peak)
    for m in ("device_idle_share", "get_device_ms", "get_roofline",
              "a2a_ms", "service_host_ms", "update_device_ms"):
        assert harness.reader(m)(empty) is None


def test_readers_on_a_trace_recorded_on_the_chip():
    """The window of a ``--trace 1`` run of ``ycsb-a.1chip`` on a TPU v5e
    (3 GET and 2 SET calls), reduced by :func:`bench.trace.load` and kept
    in its compact form."""
    doc = json.loads((FIXTURES / "ycsb-a.1chip.trace.json").read_text())
    t = trace.Traced.from_json(doc, roofline.peak("TPU v5 lite"), 8, 4)
    assert t.n_chips == 1 and t.window_s > 0
    assert 0 < t.busy_s() <= t.window_s
    gets, sets = t.calls("bench.get"), t.calls("bench.set")
    assert gets and sets
    assert len(t.answers("get")) == len(gets)
    read = {m: harness.reader(m)(t) for m in
            ("device_idle_share", "service_host_ms", "get_device_ms",
             "update_device_ms", "a2a_ms", "get_roofline")}
    assert 0 <= read["device_idle_share"] < 100
    assert read["get_device_ms"] > 0 and read["update_device_ms"] > 0
    assert read["service_host_ms"] >= 0
    assert 0 < read["get_roofline"] < 100
    assert read["a2a_ms"] is None                 # one chip: no exchange
    b = t.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(s for _, s in b["device_ops"]) <= t.busy_s() * 1.0001
    # a GET call's programs run about 1.18 s, a SET call's about 0.66 s
    assert 1000 < read["get_device_ms"] < 1400
    assert 500 < read["update_device_ms"] < 800
    assert trace.Traced.from_json(t.to_json()).to_json() == t.to_json()


def test_host_spans_are_read_from_a_recorded_trace(tmp_path):
    """The ``bench.*`` spans and their arguments, as the harness writes
    them, come back from an ``.xplane.pb`` (recorded here on the CPU,
    which has no TPU plane)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    tracer = trace.Tracer(tmp_path / "t")
    tracer.start()
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.get", live=3):
            f(jnp.ones(4)).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.answers", kind="get",
                                          live=3, hits=2):
            pass
    tracer.stop()
    t = trace.load(tracer.xplane())
    assert [s.name for s in t.spans] == ["bench.window", "bench.get",
                                         "bench.answers"]
    assert t.answers("get") == [{"kind": "get", "live": 3, "hits": 2}]
    assert t.calls("bench.get")[0].args == {"live": 3}
    assert t.n_chips == 0 and t.window_s > 0
    tracer.remove()
    assert not (tmp_path / "t").exists()
