"""The serving path's own instrumentation (``repro.obs``): the VM steps
and scan counts carried in the results, the device scopes in the
compiled bodies, and that with no profiler trace active the service
reads no counter back from the device."""
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import obs
from repro.core import machine, programs
from repro.kvstore import hopscotch, store
from repro.rdma import failure

NB, V = 128, 2


@pytest.fixture(scope="module")
def mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("kv",))


def _table(keys):
    kv = store.ShardedKV.build(1, NB, V)
    for k in keys:
        assert kv.set(int(k), [k % 7, k % 11])
    return kv


def test_get_vm_steps_match_each_context_run_alone(mesh1):
    """Each context of the owner's receive window reports the WRs its
    chain ran: a hit, a miss and key 0 each as many as the same payload
    run alone, and a slot no request filled as many as a zero payload."""
    resident = [5, 9, 300, 4001]
    kv = _table(resident)
    dk, dv = kv.device_arrays()
    q = np.asarray([[5, 4001, 77, 0, 9, 0, 0, 0]], np.int32)
    live = np.asarray([[1, 1, 1, 1, 1, 0, 0, 0]], bool)
    res = store.sharded_get(mesh1, "kv", dk, dv, jnp.asarray(q),
                            live=jnp.asarray(live))
    steps = np.asarray(res.vm_steps)
    assert steps.shape == (1, 8) and steps.dtype == np.int32

    srv = programs.build_hopscotch_server(NB, V, 8)
    state = srv.device_state(dk[0], dv[0])
    pay = srv.device_payloads(jnp.asarray(q[0, :5]),
                              hopscotch.bucket_of(jnp.asarray(q[0, :5]), NB))

    def alone(row):
        one = jax.tree.map(lambda x: x[0], machine.deliver_many(
            state, srv.recv_wq, row[None]))
        one = one._replace(steps=jnp.zeros((), jnp.int32))
        return int(srv.engine.run(one, 256).steps)

    # the window holds the live rows in batch order, then zero padding
    for slot in range(5):
        assert steps[0, slot] == alone(pay[slot]), slot
    pad = alone(jnp.zeros_like(pay[0]))
    assert (steps[0, 5:] == pad).all()
    assert 0 < pad <= steps.max()


def test_get_routed_counts_the_live_slots_of_the_window(mesh1):
    """A call 8 wide with three resident keys, a miss, a key 0 and three
    rows the admission mask holds back: the owner's window got the four
    nonzero keys that were dispatched, whatever they answer; the TTL
    server's path counts the same."""
    kv = _table([5, 9, 300])
    dk, dv = kv.device_arrays()
    q = jnp.asarray([[5, 300, 77, 0, 9, 6, 7, 0]], jnp.int32)
    live = jnp.asarray([[1, 1, 1, 1, 1, 0, 0, 0]], bool)
    res = store.sharded_get(mesh1, "kv", dk, dv, q, live=live)
    assert np.asarray(res.routed).tolist() == [4]
    assert np.asarray(res.routed).dtype == np.int32
    exp = jnp.full(dk.shape, programs.NO_TTL, jnp.int32)
    ttl = store.sharded_get(mesh1, "kv", dk, dv, q, live=live, exp=exp,
                            now=1)
    assert np.asarray(ttl.routed).tolist() == [4]
    assert np.asarray(store.sharded_get(
        mesh1, "kv", dk, dv, jnp.zeros((1, 8), jnp.int32)).routed
    ).tolist() == [0]


def test_traced_get_counters_carry_the_routed_slots(monkeypatch, tmp_path):
    """While tracing, a GET call's ``kv.counters`` span reads its live
    window slots over owners (``routed``), the busiest owner's
    (``routed_max``), and the slots and owners of the receive windows."""
    svc = failure.ShardedKVService.start([(5, [1, 2]), (9, [3, 4])],
                                         n_shards=1, buckets_per_shard=NB,
                                         val_words=V)
    marks = []
    monkeypatch.setattr(obs, "mark", lambda name, **a: marks.append(a))
    q = np.asarray([[5, 6, 9, 0]], np.int32)
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.get_many(q)
        svc.get_many(q)
    finally:
        jax.profiler.stop_trace()
    (got,) = [m for m in marks if m.get("kind") == "get"]
    assert {k: got[k] for k in ("routed", "routed_max", "slots", "owners")
            } == {"routed": 3, "routed_max": 3, "slots": 4, "owners": 1}


def test_set_counts_scanned_and_escalated_rows(mesh1):
    """An update, a displacement-requiring insert, a fresh insert and a
    duplicate of the displaced key, in a call 8 wide: the writer scan runs
    the 4 live rows, and both rows of the full neighbourhood's key re-run
    through the displacer (the second becomes an update there)."""
    home = 40
    staggered = [store.keys_homed_at((home + d) % NB, 1, NB,
                                     start=200 + 97 * d, n_shards=1)[0]
                 for d in range(8)]
    kv = _table(staggered)
    dk, dv = kv.device_arrays()
    z = store.keys_homed_at(home, 1, NB, start=50000, n_shards=1)[0]
    sk = np.zeros((1, 8), np.int32)
    sk[0, :4] = [staggered[3], z, 77001, z]
    sv = np.stack([sk % 61, sk % 53], axis=-1).astype(np.int32)
    res, _, _ = store.sharded_set(mesh1, "kv", dk, dv, jnp.asarray(sk),
                                  jnp.asarray(sv))
    assert np.asarray(res.status)[0, :4].tolist() == [
        programs.SET_UPDATED, programs.SET_DISPLACED, programs.SET_INSERTED,
        programs.SET_UPDATED]
    assert np.asarray(res.scanned).tolist() == [4]
    assert np.asarray(res.escalated).tolist() == [2]


def test_counts_of_a_call_without_work_are_zero(mesh1):
    kv = _table([5])
    dk, dv = kv.device_arrays()
    sk = jnp.zeros((1, 4), jnp.int32)
    res, _, _ = store.sharded_set(mesh1, "kv", dk, dv, sk,
                                  jnp.zeros((1, 4, V), jnp.int32))
    assert np.asarray(res.scanned).tolist() == [0]
    assert np.asarray(res.escalated).tolist() == [0]


def test_scopes_name_the_ops_of_the_serving_bodies(mesh1):
    """``kv.route``, ``kv.get.vm`` and ``kv.set.scan`` reach the op
    metadata of the compiled GET and SET bodies."""
    kv = _table([5, 9])
    dk, dv = kv.device_arrays()
    q = jnp.zeros((1, 4), jnp.int32)
    get = store._mapped_get(mesh1, "kv", "redn", 1, 4, 8, V)
    text = get.lower(dk, dv, q, q != 0).compile().as_text()
    assert "kv.route/" in text and "kv.get.vm/" in text
    set_ = store._mapped_set(mesh1, "kv", 1, 4, 8, V, 512,
                             hopscotch.DEFAULT_MAX_SEARCH,
                             hopscotch.DEFAULT_MAX_MOVES)
    text = set_.lower(dk, dv, q, jnp.zeros((1, 4, V), jnp.int32),
                      q == 0).compile().as_text()
    assert "kv.set.scan/" in text and "kv.route/" in text


class _Unreadable:
    """Stands for a device counter; reading it to the host raises."""
    shape = (1, 4)

    def __array__(self, *args, **kwargs):
        raise AssertionError("a counter was read back from the device")


def test_counters_are_read_only_while_tracing(monkeypatch, tmp_path):
    """With no profiler trace active a call holds no counter and reads
    none back; inside a trace the previous call's counters are read when
    the next call begins."""
    svc = failure.ShardedKVService.start([(5, [1, 2])], n_shards=1,
                                         buckets_per_shard=NB, val_words=V)
    real = store.sharded_get

    def get_with_unreadable_steps(*args, **kwargs):
        return real(*args, **kwargs)._replace(vm_steps=_Unreadable())

    monkeypatch.setattr(store, "sharded_get", get_with_unreadable_steps)
    q = np.asarray([[5, 6, 0, 0]], np.int32)
    assert not obs.enabled()
    for _ in range(3):
        svc.get_many(q)
        assert svc._counters is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert obs.enabled()
        svc.get_many(q)
        assert svc._counters is not None
        with pytest.raises(AssertionError, match="counter was read"):
            svc.get_many(q)
    finally:
        jax.profiler.stop_trace()
    assert not obs.enabled()


def test_gc_spans_are_installed_once():
    failure.ShardedKVService.start([(5, [1, 2])], n_shards=1,
                                   buckets_per_shard=NB, val_words=V)
    obs.install_gc_spans()
    assert gc.callbacks.count(obs._gc_spans) == 1
    gc.collect()                    # no trace: the hook records nothing
    assert obs._gc_spans.open is None
