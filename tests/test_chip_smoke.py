"""``chip_smoke.py`` on the CPU at a small size: the same traffic, oracle
and comparisons as the chip run, and its refusal to run without a TPU."""
import importlib.util
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def clock(smoke):
    return smoke.CompileClock()


def test_kv_phase_matches_oracle(smoke, clock):
    tally = smoke.Tally(clock)
    rec = smoke.kv_phase(tally, n_shards=1, buckets=1 << 10, seed=0,
                         rounds=2)
    assert tally.mismatches == 0, list(tally.lines("kv"))
    assert tally.ops["get"] == 4 * smoke.GET_BATCH
    assert tally.ops["set"] == 2 * smoke.SET_BATCH
    assert tally.ops["delete"] == 2 * smoke.DEL_BATCH
    assert tally.ops["table_buckets"] == 1 << 10
    assert 0.45 < rec["load"] <= smoke.LOAD
    # every op compiled in its first call and never again
    assert all(sum(n for _, _, n in calls[1:]) == 0
               for calls in tally.calls.values())


def test_set_batches_exercise_the_displacer(smoke):
    """Each SET batch holds updates, fresh inserts and at least one insert
    the displacer chain must place — and nothing that needs a resize."""
    import numpy as np

    from repro.kvstore import hopscotch

    rng = np.random.default_rng(3)
    oracle, keys, _, _ = smoke.seed_store(rng, 1, 1 << 10, 4)
    qk, _, st = smoke.set_batch(rng, oracle, keys, 1, 16, 4)
    assert (st == hopscotch.SET_DISPLACED).any()
    assert (st == hopscotch.SET_UPDATED).sum() == 8
    assert not (st == hopscotch.SET_NEEDS_RESIZE).any()
    assert len(np.unique(qk)) == qk.size


def test_pallas_phase_interpret_matches_interpreter(smoke, clock):
    tally = smoke.Tally(clock)
    rec = smoke.pallas_phase(tally, seed=0, backend="pallas-interpret")
    assert tally.mismatches == 0, list(tally.lines("pallas"))
    assert rec["hits"] > 0
    assert tally.ops["pallas-interpret_vs_interp.mem"] == smoke.GET_BATCH


def test_refuses_to_run_without_tpu(smoke, capsys):
    assert smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert "platform=cpu" in out
    assert "needs a TPU" in err
    assert '"ok"' not in out
