"""The GET server's shared read-only segment.

A program may declare one read-only segment of its address space; the
hopscotch GET server declares its table and value rows.  A segmented run
gives each context only the private image (code, scatter table, response
region, guard) and reads the segment from one array that every context
shares.  These tests hold the segmented run to the whole-image run it
replaces, bit for bit, and both to the ``HopscotchTable`` oracle; they
check that the declaration refuses programs that could write the segment,
and that a store a patched address aims into it halts the context and
reaches the GET path's caller as a breach, never as an answer.
"""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import obs
from repro.core import isa, machine, programs
from repro.core.assembler import Program, SegmentError
from repro.core.engine import ChainEngine
from repro.kvstore import hopscotch, store
from repro.rdma import failure

NB, H, V = 1 << 10, 8, 7
HERE = pathlib.Path(__file__).parent


@pytest.fixture(scope="module")
def mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("kv",))


@pytest.fixture(scope="module")
def table():
    """A 2^10-bucket table at load ~0.5 with values over all 32 bits:
    six keys homed three buckets before the end, so their neighborhoods
    wrap and one of them sits in the last bucket."""
    t = hopscotch.make_table(NB, V, H)
    rng = np.random.default_rng(20261018)
    wrapped = store.keys_homed_at(NB - 3, 6, NB, n_shards=1)
    for k in wrapped:
        assert t.insert(k, rng.integers(-2**31, 2**31, V).tolist())
    assert int(t.keys[NB - 1]) in wrapped and int(t.keys[0]) in wrapped
    while (t.keys != 0).sum() < NB // 2:
        k = int(rng.integers(1, 1 << 24))
        t.insert(k, rng.integers(-2**31, 2**31, V).tolist())
    return t


def _queries(t):
    """Hits (the last bucket's and the wrapped ones among them), key 0,
    and misses."""
    resident = t.keys[t.keys != 0]
    q = [int(t.keys[NB - 1]), int(t.keys[0]), int(t.keys[2])]
    q += resident[:: len(resident) // 24].tolist() + [0]
    q += [k for k in range(1 << 23, (1 << 23) + 64)
          if k not in set(resident.tolist())][:12]
    return jnp.asarray(q, jnp.int32)


def _deadlines(t, seed=5):
    """Deadlines around ``now`` = 0: expired, live and NO_TTL buckets."""
    rng = np.random.default_rng(seed)
    exp = rng.integers(-3, 4, NB).astype(np.int32)
    exp[rng.random(NB) < 0.3] = programs.NO_TTL
    return jnp.asarray(exp)


def _lookup(t, q, exp):
    keys, vals = t.as_device()
    if exp is None:
        return hopscotch.lookup(keys, vals, q, H)
    return hopscotch.lookup_ttl(keys, vals, exp, q, 0, H)


@pytest.mark.parametrize("ttl", [False, True], ids=["plain", "ttl"])
def test_device_segment_is_the_split_of_device_state(table, ttl):
    """Built without a scatter, the segment and the private image are the
    whole image's words in and outside ``[table_base, resp_region)``."""
    srv = programs.build_hopscotch_server(NB, V, H, ttl=ttl)
    assert srv.segment == (srv.table_base, srv.resp_region)
    keys, vals = table.as_device()
    exp = _deadlines(table) if ttl else None
    private, words = srv.device_segment(keys, vals, exp)
    want_private, want_words = machine.split_image(
        srv.device_state(keys, vals, exp), srv.segment)
    np.testing.assert_array_equal(np.asarray(words), np.asarray(want_words))
    for f in machine.VMState._fields:
        np.testing.assert_array_equal(np.asarray(getattr(private, f)),
                                      np.asarray(getattr(want_private, f)),
                                      err_msg=f)
    # about a thousand private words, against ~11 words a bucket shared
    assert private.mem.shape[-1] < 1400 < NB * (3 + V + 1) == words.size


@pytest.mark.parametrize("ttl", [False, True], ids=["plain", "ttl"])
def test_segmented_run_is_the_whole_image_run(table, ttl):
    """Every field of every context's state, the response region and the
    WRs run (``vm_steps``) are the whole-image run's; the segment comes
    out of the whole-image run unchanged; and the answers are the
    oracle's.  The batch ends with a zero payload, the capacity slot no
    request filled."""
    srv = programs.build_hopscotch_server(NB, V, H, ttl=ttl)
    keys, vals = table.as_device()
    exp = _deadlines(table) if ttl else None
    q = _queries(table)
    pay = srv.device_payloads(q, hopscotch.bucket_of(q, NB),
                              0 if ttl else None)
    pay = jnp.concatenate([pay, jnp.zeros_like(pay[:1])])
    full = srv.engine.run_many(srv.device_state(keys, vals, exp),
                               srv.recv_wq, pay, 256)
    private, words = srv.device_segment(keys, vals, exp)
    out, breach = srv.engine.run_many_segmented(
        private, srv.segment, words, srv.recv_wq, pay, 256)

    want, want_words = machine.split_image(full, srv.segment)
    for f in machine.VMState._fields:
        np.testing.assert_array_equal(np.asarray(getattr(out, f)),
                                      np.asarray(getattr(want, f)),
                                      err_msg=f)
    np.testing.assert_array_equal(np.asarray(want_words),
                                  np.broadcast_to(words, want_words.shape))
    assert not np.asarray(breach).any()

    resp = np.asarray(out.mem[:, srv.private_resp_region:
                              srv.private_resp_region + srv.resp_words])
    found, values = _lookup(table, q, exp)
    np.testing.assert_array_equal(resp[:-1, 0] > 0, np.asarray(found))
    np.testing.assert_array_equal(resp[:-1, 1:], np.asarray(values))
    assert not resp[-1].any()                     # the padded slot
    assert bool(found[0]) or ttl                  # the last bucket's key
    assert np.asarray(found).sum() >= (6 if ttl else 20)


def _sharded_table(t):
    kv = store.ShardedKV.build(1, NB, V, H)
    kv.tables[0] = t
    return kv


@pytest.mark.parametrize("ttl", [False, True], ids=["plain", "ttl"])
def test_sharded_get_one_device_matches_the_oracle(mesh1, table, ttl):
    """``sharded_get`` on one device, rows left out by ``live`` and a
    capacity above the live rows (zero-padded window slots): the oracle's
    answers, the WRs of each context as the whole-image run counts them,
    the private image's words, no breach."""
    kv = _sharded_table(table)
    dk, dv = kv.device_arrays()
    q = np.zeros((1, 48), np.int32)
    qs = np.asarray(_queries(table))
    q[0, :len(qs)] = qs
    live = q != 0
    live[0, qs.tolist().index(0)] = True            # key 0 is a live query
    exp = _deadlines(table)[None] if ttl else None
    res = store.sharded_get(mesh1, "kv", dk, dv, jnp.asarray(q),
                            live=jnp.asarray(live), capacity=64, exp=exp,
                            now=0 if ttl else None)
    found, values = _lookup(table, jnp.asarray(q[0]),
                            None if exp is None else exp[0])
    ok = np.asarray(res.ok[0])
    np.testing.assert_array_equal(ok, live[0])
    np.testing.assert_array_equal(np.asarray(res.found[0])[ok],
                                  np.asarray(found)[ok])
    np.testing.assert_array_equal(np.asarray(res.values[0])[ok],
                                  np.asarray(values)[ok])
    srv = programs.build_hopscotch_server(NB, V, H, ttl=ttl)
    assert np.asarray(res.image_words).tolist() == [srv.private0.mem.size]
    assert np.asarray(res.breached).tolist() == [0]
    assert np.asarray(res.dropped).tolist() == [0]

    steps = np.asarray(res.vm_steps)
    assert steps.shape == (1, 64)
    pay = srv.device_payloads(jnp.asarray(q[0][live[0]]),
                              hopscotch.bucket_of(jnp.asarray(
                                  q[0][live[0]]), NB), 0 if ttl else None)
    full = srv.engine.run_many(
        srv.device_state(dk[0], dv[0], None if exp is None else exp[0]),
        srv.recv_wq, jnp.concatenate([pay, jnp.zeros_like(pay[:1])]), 256)
    want = np.asarray(full.steps)
    np.testing.assert_array_equal(steps[0, :len(want) - 1], want[:-1])
    assert (steps[0, len(want) - 1:] == want[-1]).all()


def test_sharded_get_four_devices_matches_the_oracle():
    """The routed path: four shards on four host devices, each GET body
    reading its own shard's segment (a fresh process: the device count is
    fixed when JAX starts)."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(HERE.parent / "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run(
        [sys.executable, str(HERE / "multidevice" / "shared_segment_main.py")],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["devices"] == 4 and got["mismatches"] == 0
    assert got["served"] == got["queries"] > 100 and got["hits"] > 50
    assert got["breached"] == 0


def test_migrating_get_matches_the_two_frame_oracle(mesh1):
    """Mid-growth GETs read both frames' segments: at every watermark the
    answers are "the new frame's, else the old frame's"."""
    nb, h = 64, 4
    t = hopscotch.make_table(nb, 2, h)
    ks, k = [], 1
    while len(ks) < 40:
        if t.insert(k, [k % 7 + 1, -k]):
            ks.append(k)
        k += 3
    dk, dv = t.as_device()
    rs = store.begin_resize(dk[None], dv[None])
    q = jnp.asarray([ks + [999983, 0]], jnp.int32)
    seen = 0
    while True:
        g = store.sharded_get(mesh1, "kv", rs, q, neighborhood=h)
        fn, vn = hopscotch.lookup(rs.new_keys[0], rs.new_vals[0], q[0], h)
        fo, vo = hopscotch.lookup(rs.keys[0], rs.vals[0], q[0], h)
        np.testing.assert_array_equal(np.asarray(g.found[0]),
                                      np.asarray(fn | fo))
        np.testing.assert_array_equal(
            np.asarray(g.values[0]),
            np.where(np.asarray(fn)[:, None], np.asarray(vn),
                     np.asarray(vo)))
        assert bool(np.asarray(g.ok).all())
        assert np.asarray(g.breached).tolist() == [0]
        assert np.asarray(g.found[0]).sum() == len(ks)
        seen += 1
        if store.resize_done(rs):
            break
        rs, _ = store.sharded_resize(mesh1, "kv", rs, step=16,
                                     neighborhood=h)
    assert seen >= 3


# -- the declaration -----------------------------------------------------------

def _small_program(write):
    """A 256-word program whose top 16 data words are the segment;
    ``write(p, wq, rq, seg, free)`` posts the WRs under test and may
    name another segment."""
    p = Program(256)
    seg = p.alloc(16, list(range(100, 116)), "seg")
    free = p.word(0, "free")
    rq = p.add_wq(2)
    wq = p.add_wq(4, ordering=isa.ORD_DOORBELL)
    wq.wait(rq, 1)
    p.read_only(*(write(p, wq, rq, seg, free) or (seg, seg + 16)))
    return p


def _dst(p, wq, rq, seg, free):
    wq.write(src=free, dst=seg + 15, tag="into")


def _straddle(p, wq, rq, seg, free):
    wq.write(src=free, dst=seg - 2, ln=3, tag="over the edge")


def _ret_old(p, wq, rq, seg, free):
    wq.cas(dst=free, old=0, new=1, ret=seg + 4, tag="return-old")


def _scatter(p, wq, rq, seg, free):
    rq.recv(scatter_table=p.scatter_table([free, seg]), tag="recv")


def _code(p, wq, rq, seg, free):
    return 4, seg                               # WQ0's WAIT lies in it


@pytest.mark.parametrize("write", [_dst, _straddle, _ret_old, _scatter,
                                   _code],
                         ids=["dst", "straddling-copy", "return-old",
                              "scatter-entry", "code-word"])
def test_declaration_refuses_a_writable_segment(write):
    p = _small_program(write)
    with pytest.raises(SegmentError, match="segment"):
        p.finalize()


def test_declaration_admits_reads_of_the_segment():
    def read(p, wq, rq, seg, free):
        wq.write(src=seg, dst=free, ln=1)

    spec, state = _small_program(read).finalize()
    assert spec.num_wqs == 2


def _client_addressed_store():
    """The client names the store's destination: the RECV scatters the
    payload's word into a WRITE's dst, which no static check can see."""
    p = Program(256)
    seg = p.alloc(16, list(range(100, 116)), "seg")
    val = p.word(-7, "val")
    free = p.word(0, "free")
    rq = p.add_wq(2)
    wq = p.add_wq(4, ordering=isa.ORD_DOORBELL)
    wq.wait(rq, 1)
    wr = wq.write(src=val, dst=free, tag="client-addressed")
    rq.recv(scatter_table=p.scatter_table([wr.addr("dst")]))
    segment = p.read_only(seg, seg + 16)
    spec, state = p.finalize()
    return spec, state, segment, rq.index, free


def test_a_patched_store_into_the_segment_halts_the_context():
    """A store aimed into the segment is dropped, halts its context and
    raises its breach flag; a store aimed elsewhere runs as the
    whole-image run does."""
    spec, state, segment, rq, free = _client_addressed_store()
    private, words = machine.split_image(state, segment)
    pays = jnp.asarray([[segment.lo + 3], [free]], jnp.int32)
    eng = ChainEngine.for_spec(spec)
    out, breach = eng.run_many_segmented(private, segment, words, rq, pays,
                                         64)
    assert np.asarray(breach).tolist() == [True, False]
    assert np.asarray(out.halted).tolist() == [True, False]
    full = eng.run_many(state, rq, pays, 64)
    want, want_words = machine.split_image(full, segment)
    np.testing.assert_array_equal(np.asarray(want_words[1]),
                                  np.asarray(words))
    for f in machine.VMState._fields:
        np.testing.assert_array_equal(np.asarray(getattr(out, f))[1],
                                      np.asarray(getattr(want, f))[1],
                                      err_msg=f)
    assert int(out.mem[1, free]) == -7
    # the breaching context's private image took no store at all
    np.testing.assert_array_equal(
        np.asarray(out.mem[0, segment.lo:]),
        np.asarray(private.mem[segment.lo:]))


def test_get_path_reports_a_breach_and_never_answers_it(mesh1, table,
                                                         monkeypatch):
    """A GET server whose response WRITE a bad image aims into the
    segment: every hit breaches and comes back ``ok`` False, counted in
    ``breached`` and not as a drop; the misses still answer."""
    real = programs.build_hopscotch_server(NB, V, H)
    img = np.asarray(real.private0.mem).copy()
    for wq in real.prog.wqs:
        for slot, wr in enumerate(wq.wrs):
            if wr["tag"].startswith("hs.resp"):
                img[wq.base + slot * isa.WR_WORDS + isa.F_DST] = real.table_base
    bad = dataclasses.replace(
        real, private0=real.private0._replace(mem=jnp.asarray(img)))
    monkeypatch.setattr(programs, "build_hopscotch_server",
                        lambda *a, **k: bad)
    monkeypatch.setattr(store, "_MAPPED_CACHE", type(store._MAPPED_CACHE)())
    kv = _sharded_table(table)
    dk, dv = kv.device_arrays()
    q = _queries(table)
    q = q[q != 0][None]          # key 0 ghost-matches an empty bucket
    res = store.sharded_get(mesh1, "kv", dk, dv, q)
    found, _ = _lookup(table, q[0], None)
    hits = np.asarray(found)
    assert hits.sum() >= 20
    np.testing.assert_array_equal(np.asarray(res.ok[0]), ~hits)
    assert np.asarray(res.breached).tolist() == [int(hits.sum())]
    assert np.asarray(res.dropped).tolist() == [0]
    assert not np.asarray(res.found[0]).any()
    assert f"breached={int(hits.sum())}" in repr(res)


def test_pallas_backend_refuses_a_segmented_run():
    srv = programs.build_recycled_get_server()
    eng = ChainEngine(srv.spec, "pallas-interpret")
    segment = machine.Segment(srv.spec.mem_words - 8, srv.spec.mem_words)
    private, words = machine.split_image(srv.state, segment)
    with pytest.raises(ValueError, match="interp backend"):
        eng.run_many_segmented(private, segment, words, srv.loop_wq,
                               [[1]], 64)


def test_run_refuses_a_split_image_of_the_wrong_size():
    srv = programs.build_hopscotch_server(64, 2, 4)
    private, words = srv.device_segment(jnp.zeros(64, jnp.int32),
                                        jnp.zeros((64, 2), jnp.int32))
    with pytest.raises(ValueError, match="split image"):
        machine.run_segmented(srv.spec, srv.segment, private, words[1:], 64)


def test_verifier_certificates_do_not_drift():
    """Declaring the segment changes neither program nor certificate."""
    from benchmarks import verify_programs

    assert verify_programs.main(["--check"]) == 0


def test_traced_get_counters_carry_the_private_image_words(monkeypatch,
                                                           tmp_path):
    """While tracing, a GET call's ``kv.counters`` span reads the private
    image's words, far below the whole image's."""
    nb, v = 128, 2
    svc = failure.ShardedKVService.start([(5, [1, 2]), (9, [3, 4])],
                                         n_shards=1, buckets_per_shard=nb,
                                         val_words=v)
    marks = []
    monkeypatch.setattr(obs, "mark", lambda name, **a: marks.append(a))
    q = np.asarray([[5, 6, 9, 0]], np.int32)
    jax.profiler.start_trace(str(tmp_path))
    try:
        svc.get_many(q)
        svc.get_many(q)
    finally:
        jax.profiler.stop_trace()
    srv = programs.build_hopscotch_server(nb, v, 8)
    (got,) = [m for m in marks if m.get("kind") == "get"]
    assert got["image_words"] == srv.private0.mem.size
    assert got["image_words"] * 2 < srv.state0.mem.size
    assert got["trips"] == 49
