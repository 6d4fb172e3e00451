"""YCSB core-workload traffic, drawn from a seed.

The key chooser is YCSB's ``ScrambledZipfianGenerator`` (constant 0.99):
a zipfian rank over a fixed 10^10-item space, hashed with FNV-1a 64 and
folded onto the records, so the hot records are spread over the key
space instead of being the lowest record numbers.  The constants and the
arithmetic follow ``site.ycsb.generator.ZipfianGenerator.nextLong`` and
``site.ycsb.Utils.fnvhash64``.  A mix with ``request_distribution``
``uniform`` draws every record alike instead.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

ZIPFIAN_CONSTANT = 0.99
#: ScrambledZipfianGenerator.ITEM_COUNT, and zeta(ITEM_COUNT, 0.99) as
#: YCSB precomputes it (ZETAN)
ITEM_COUNT = 10_000_000_000
ZETAN = 26.46902820178302
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211

READ, UPDATE = 0, 1
KEY_SPACE = 1 << 24        # keys are 24-bit ids; 0 marks an empty bucket

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


def fnvhash64(values: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` on an int64 array: FNV-1a over the 8
    octets, least significant first, then ``Math.abs``."""
    x = np.asarray(values, np.int64).astype(np.uint64)
    h = np.full(x.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= x & np.uint64(0xFF)
            h *= np.uint64(FNV_PRIME_64)
            x >>= np.uint64(8)
    return np.abs(h.view(np.int64))


class ScrambledZipfian:
    """Record numbers in ``[0, n_items)``, scrambled-zipfian distributed."""

    def __init__(self, n_items: int):
        theta = ZIPFIAN_CONSTANT
        items = ITEM_COUNT + 1          # ZipfianGenerator(0, ITEM_COUNT)
        zeta2 = 1.0 + 0.5 ** theta
        self.n_items = int(n_items)
        self.items = items
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = (1 - (2.0 / items) ** (1 - theta)) / (1 - zeta2 / ZETAN)
        self.second = 1.0 + 0.5 ** theta

    def ranks(self, rng: np.random.Generator, size: int) -> np.ndarray:
        """Unscrambled zipfian ranks (0 is the most popular)."""
        u = rng.random(size)
        uz = u * ZETAN
        ret = (self.items
               * (self.eta * u - self.eta + 1) ** self.alpha).astype(np.int64)
        ret = np.where(uz < self.second, 1, ret)
        return np.where(uz < 1.0, 0, ret)

    def draw(self, rng: np.random.Generator, size: int) -> np.ndarray:
        return fnvhash64(self.ranks(rng, size)) % self.n_items


class Uniform:
    """Record numbers in ``[0, n_items)``, each as likely as any other
    (YCSB's ``UniformLongGenerator``)."""

    def __init__(self, n_items: int):
        self.n_items = int(n_items)

    def draw(self, rng: np.random.Generator, size: int) -> np.ndarray:
        return rng.integers(0, self.n_items, size)


#: YCSB's ``requestdistribution`` values this generator knows
CHOOSERS = {"zipfian": ScrambledZipfian, "uniform": Uniform}


@dataclasses.dataclass(frozen=True)
class Mix:
    """One traffic mix, as its file under ``bench/traffic/`` states it."""
    name: str
    read_proportion: float
    update_proportion: float
    clients: int
    request_distribution: str = "zipfian"

    @classmethod
    def load(cls, name: str) -> "Mix":
        spec = json.loads((TRAFFIC_DIR / f"{name}.json").read_text())
        mix = cls(name=name, read_proportion=spec["read_proportion"],
                  update_proportion=spec["update_proportion"],
                  clients=spec["clients"],
                  request_distribution=spec["request_distribution"])
        if abs(mix.read_proportion + mix.update_proportion - 1) > 1e-9:
            raise ValueError(f"mix {name}: proportions do not add up to 1")
        if mix.request_distribution not in CHOOSERS:
            raise ValueError(f"mix {name}: unknown request distribution "
                             f"{mix.request_distribution!r}")
        return mix


def records(seed: int, count: int, val_words: int):
    """The records YCSB's load phase would insert: ``count`` distinct
    24-bit keys (never 0) and their values, in insertion order."""
    rng = np.random.default_rng([seed, 0])
    keys = rng.choice(KEY_SPACE - 1, count, replace=False).astype(np.int32)
    vals = rng.integers(-2**31, 2**31, (count, val_words), dtype=np.int32)
    return keys + 1, vals


#: the stream the operation kinds are drawn from, the same for every seed
KIND_STREAM = 0x59435342


class OpStream:
    """The operations clients issue, in issue order: kind, record number
    and (for an update) the new value, drawn in chunks, so the n-th
    operation is the same whatever the speed of the system.

    Each kind (read or update) is drawn on its own in the mix's
    proportions, as YCSB's ``CoreWorkload`` draws it, from a stream that
    is the same for every seed; the seed draws the record each operation
    names and the values updates write.  So every seed does the same
    work on other keys: the kind order sets how many updates each SET
    call carries, and with it the length of a step."""

    CHUNK = 4096

    def __init__(self, mix: Mix, n_records: int, val_words: int, seed: int):
        self.mix = mix
        self.val_words = val_words
        self.kinds = np.random.default_rng(KIND_STREAM)
        self.rng = np.random.default_rng([seed, 1])
        self.chooser = CHOOSERS[mix.request_distribution](n_records)
        self._buf = None
        self._i = self.CHUNK

    def _refill(self):
        n = self.CHUNK
        kind = np.where(self.kinds.random(n) < self.mix.read_proportion,
                        READ, UPDATE)
        rec = self.chooser.draw(self.rng, n)
        vals = self.rng.integers(-2**31, 2**31, (n, self.val_words),
                                 dtype=np.int32)
        self._buf = (kind, rec, vals)
        self._i = 0

    def next(self):
        """``(kind, record, value)`` of the next operation."""
        if self._i == self.CHUNK:
            self._refill()
        kind, rec, vals = self._buf
        i = self._i
        self._i += 1
        return int(kind[i]), int(rec[i]), vals[i]
