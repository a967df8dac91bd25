"""The control: the reference in the program's place, breaking one
stated guarantee, to show that the check fails what it must fail.

The configurations state exact int32 keys (``guarantees.exact``): a
contains answers 1 only for a live key equal in all 32 bits.  The
control keeps the reference's key set at 16 bits (``key & 0xFFFF``),
the step below that a later change might take to shrink the plane.  It
serves the same batches through the harness's own loop and check, in
place of ``splaylist.run_serving``; the harness then reads its state and
plane as it reads the program's.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 --seconds 10

runs the control at the cell's own size (it needs no chip: it is numpy)
and prints, per seed, the numbers compared beside their limits.  The
benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import NamedTuple

import numpy as np

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import reference  # noqa: E402
from bench import run as harness  # noqa: E402

NEG_INF, POS_INF = -(2 ** 31) + 1, 2 ** 31 - 1


class State(NamedTuple):
    key: np.ndarray
    selfhits: np.ndarray
    deleted: np.ndarray
    n_alloc: int


class Plane(NamedTuple):
    keys: np.ndarray
    widths: np.ndarray


class KeyPrecisionControl:
    """A stand-in for ``run_serving`` that holds keys at ``bits`` bits
    (their low bits, as a cast to a narrower integer keeps them)."""

    def __init__(self, width: int, bits: int = 16):
        self.width = width
        self.mask = (1 << bits) - 1
        self.keys = None

    def _state(self):
        live = self.keys.sorted_keys()
        key = np.concatenate([[NEG_INF, POS_INF], live]).astype(np.int64)
        hits = np.array([0, 0] + [self.keys.hits[k] for k in live.tolist()],
                        np.int64)
        row = np.full(self.width, POS_INF, np.int64)
        row[:len(live)] = live[:self.width]
        return (State(key, hits, np.zeros(key.shape, bool), len(key)),
                Plane(row[None], np.array([len(live)])))

    def __call__(self, state, plane, kinds, keys, upd, **_):
        if self.keys is None:
            # keys that collide in the narrow bits merge, and so do
            # their counters
            live, hits = harness.live_keys(state)
            narrow, merged = np.unique(live & self.mask, return_inverse=True)
            self.keys = reference.KeySet(
                narrow, np.bincount(merged, weights=hits).astype(np.int64))
        res = self.keys.apply(kinds[0], np.asarray(keys[0]) & self.mask,
                              upd[0])
        st, pl = self._state()
        zero = np.zeros((1,), np.int32)
        return st, pl, res[None], np.zeros_like(res)[None], zero


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    spec = harness.read_json(harness.ROOT, "BENCHMARK.json")
    _, config, traffic = harness.cell_spec(spec, args.workload)
    for seed in args.seeds:
        result, info = harness.run_cell(
            config, traffic, seed, args.seconds, False,
            serve=KeyPrecisionControl(int(config["width"])))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": "keys at 16 bits",
                          "batches": info["batches"],
                          "correct": result["correct"],
                          "compared": result["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
