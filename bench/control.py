#!/usr/bin/env python3
"""The control: the reference put in the store's place, breaking one
guarantee the configurations state.  It keeps each value word at 16 bits
instead of the 32 the configuration states (the step below its
precision), so a GET no longer answers the whole value of its key.  A
run with it must come out not correct.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

runs a cell with the control serving, at the cell's own size, and prints
the numbers compared beside their limits.  The benchmark's own runs
never run it.
"""
import pathlib
import sys
from types import SimpleNamespace

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent


class NarrowValues:
    """A dict store whose value words keep only their low 16 bits."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        self.table = {int(k): self._narrow(v) for k, v in zip(keys, vals)}
        self.val_words = vals.shape[1]

    @staticmethod
    def _narrow(value) -> np.ndarray:
        return np.asarray(value).astype(np.int16).astype(np.int32)

    def get_many(self, q):
        q = np.asarray(q)
        found = np.zeros(q.shape, bool)
        values = np.zeros(q.shape + (self.val_words,), np.int32)
        for idx in zip(*np.nonzero(q)):
            v = self.table.get(int(q[idx]))
            if v is not None:
                found[idx], values[idx] = True, v
        return SimpleNamespace(found=found, values=values, ok=q != 0)

    def set_many(self, k, v):
        k = np.asarray(k)
        for idx in zip(*np.nonzero(k)):
            self.table[int(k[idx])] = self._narrow(v[idx])
        return SimpleNamespace(applied=k != 0, ok=k != 0)

    @property
    def keys(self):
        return np.fromiter(self.table, np.int32, len(self.table))[None]

    @property
    def vals(self):
        return np.stack(list(self.table.values()))[None]


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(args.workload)
    out = harness.execute(cell, args.seed, args.seconds,
                          wrap=lambda svc, k, v: NarrowValues(k, v))
    for line in out.lines:
        harness.log(line)
    print(json.dumps({"control": "narrow_values", "workload": cell.name,
                      "seed": args.seed, "correct": out.result["correct"],
                      "checks": out.result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
