"""The YCSB generator: deterministic per seed, within the records, and
with the skew of zipfian constant 0.99."""
import collections
import json
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import ycsb  # noqa: E402


def test_scrambled_zipfian_is_deterministic_and_in_range():
    z = ycsb.ScrambledZipfian(1000)
    a = z.draw(np.random.default_rng([2**31 + 7, 1]), 50_000)
    b = z.draw(np.random.default_rng([2**31 + 7, 1]), 50_000)
    c = z.draw(np.random.default_rng([2**31 + 8, 1]), 50_000)
    assert (a == b).all()
    assert (a != c).any()
    assert a.min() >= 0 and a.max() < 1000
    assert len(np.unique(a)) > 900          # the tail reaches most records


def test_zipfian_ranks_have_constant_099():
    """P(rank 0) = 1/zeta(n, 0.99) and P(rank 1) = 2^-0.99/zeta(n, 0.99),
    as YCSB's ZipfianGenerator draws them over its 10^10 items."""
    z = ycsb.ScrambledZipfian(1 << 19)
    r = z.ranks(np.random.default_rng(0), 1_000_000)
    p0, p1 = (r == 0).mean(), (r == 1).mean()
    assert p0 == pytest.approx(1 / ycsb.ZETAN, rel=0.03)
    assert p1 == pytest.approx(0.5 ** 0.99 / ycsb.ZETAN, rel=0.05)
    # far from uniform, and the ratio of the two hottest is 2^0.99
    assert p0 / p1 == pytest.approx(2 ** 0.99, rel=0.06)


def test_scrambling_spreads_the_hot_records():
    z = ycsb.ScrambledZipfian(1 << 19)
    d = z.draw(np.random.default_rng(1), 400_000)
    (hot, n_hot), = collections.Counter(d.tolist()).most_common(1)
    assert n_hot / len(d) == pytest.approx(1 / ycsb.ZETAN, rel=0.05)
    assert hot == ycsb.fnvhash64(np.array([0]))[0] % (1 << 19)
    assert hot != 0


def test_fnvhash64_is_fnv1a_over_eight_octets():
    def fnv(v):
        h = ycsb.FNV_OFFSET_BASIS_64
        for _ in range(8):
            h = ((h ^ (v & 0xFF)) * ycsb.FNV_PRIME_64) & (2**64 - 1)
            v >>= 8
        return abs(h - 2**64 if h >= 2**63 else h)

    vals = np.array([0, 1, 255, 2**40 + 3, 10**10], np.int64)
    assert ycsb.fnvhash64(vals).tolist() == [fnv(int(v)) for v in vals]


@pytest.mark.parametrize("mix,reads", [("ycsb-a", 0.5), ("ycsb-b", 0.95),
                                       ("ycsb-c", 1.0)])
def test_op_stream_follows_the_mix_and_the_seed(mix, reads):
    m = ycsb.Mix.load(mix)
    a = ycsb.OpStream(m, 500, 4, seed=2**33 + 1)
    b = ycsb.OpStream(m, 500, 4, seed=2**33 + 1)
    ops_a = [a.next() for _ in range(10_000)]
    ops_b = [b.next() for _ in range(10_000)]
    assert [(k, r) for k, r, _ in ops_a] == [(k, r) for k, r, _ in ops_b]
    assert all((va == vb).all() for (_, _, va), (_, _, vb)
               in zip(ops_a, ops_b))
    kinds = np.array([k for k, _, _ in ops_a])
    assert (kinds == ycsb.READ).mean() == pytest.approx(reads, abs=0.02)
    assert all(0 <= r < 500 for _, r, _ in ops_a)


def test_records_are_distinct_nonzero_24_bit_keys():
    k1, v1 = ycsb.records(5, 4096, 4)
    k2, v2 = ycsb.records(5, 4096, 4)
    assert (k1 == k2).all() and (v1 == v2).all()
    assert len(np.unique(k1)) == 4096
    assert k1.min() >= 1 and k1.max() < 1 << 24
    assert v1.shape == (4096, 4) and v1.dtype == np.int32


def test_every_seed_issues_the_same_kinds_on_other_records():
    m = ycsb.Mix.load("ycsb-a")
    a = ycsb.OpStream(m, 1000, 4, seed=1)
    b = ycsb.OpStream(m, 1000, 4, seed=2**31 + 99)
    ops_a = [a.next() for _ in range(5000)]
    ops_b = [b.next() for _ in range(5000)]
    assert [k for k, _, _ in ops_a] == [k for k, _, _ in ops_b]
    assert [r for _, r, _ in ops_a] != [r for _, r, _ in ops_b]


def test_uniform_requests_spread_evenly_over_the_records(tmp_path,
                                                         monkeypatch):
    spec = json.loads((ycsb.TRAFFIC_DIR / "ycsb-c.json").read_text())
    spec["request_distribution"] = "uniform"
    (tmp_path / "ycsb-c-uniform.json").write_text(json.dumps(spec))
    monkeypatch.setattr(ycsb, "TRAFFIC_DIR", tmp_path)
    m = ycsb.Mix.load("ycsb-c-uniform")
    s = ycsb.OpStream(m, 100, 4, seed=2**33 + 5)
    recs = np.array([s.next()[1] for _ in range(50_000)])
    counts = np.bincount(recs, minlength=100)
    assert recs.min() == 0 and recs.max() == 99
    # 500 draws a record: every count within 5 standard deviations
    assert np.abs(counts - 500).max() < 5 * np.sqrt(500)


def test_an_unknown_request_distribution_is_refused(tmp_path, monkeypatch):
    spec = json.loads((ycsb.TRAFFIC_DIR / "ycsb-c.json").read_text())
    spec["request_distribution"] = "latest"
    (tmp_path / "ycsb-c-latest.json").write_text(json.dumps(spec))
    monkeypatch.setattr(ycsb, "TRAFFIC_DIR", tmp_path)
    with pytest.raises(ValueError, match="latest"):
        ycsb.Mix.load("ycsb-c-latest")
