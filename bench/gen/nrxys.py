"""The splay-list paper's general n-r-x-y-s workload (arXiv:2008.01009,
Appendix C.3), as a stream of fixed-size op batches.

A copy of ``core/workload.general_workload`` kept with the benchmark, so
that the yardstick does not move when ``src/`` does.  The draws are the
same, made batch by batch instead of all at once:

  * key space ``[0, key_space)``; ``n`` keys are chosen, each is loaded
    with probability ``prepopulate`` (the paper's 90%);
  * ``r`` of the ops are contains, the rest split evenly insert/delete;
  * ``x`` of the contains go to the popular set, the first ``y * n`` of
    the chosen keys; the rest go uniformly to the other chosen keys;
  * inserts and deletes draw uniformly from an ``s * n`` subset;
  * each op carries a Bernoulli(``p``) splay coin (``upd``).

Reads of chosen keys that were not loaded are the misses.
"""

from __future__ import annotations

import numpy as np

OP_CONTAINS, OP_INSERT, OP_DELETE = 0, 1, 2


class Stream:
    """The cell's data and op stream, all drawn from one seeded rng."""

    def __init__(self, config: dict, traffic: dict, rng: np.random.Generator):
        n = int(config["n"])
        self.rng = rng
        self.batch_size = int(traffic["batch"])
        self.r, self.x = float(traffic["r"]), float(traffic["x"])
        self.p = float(config["p"])
        keys_all = rng.permutation(int(config["key_space"]))[:n].astype(
            np.int32)
        self.keys = np.sort(
            keys_all[rng.random(n) < float(config["prepopulate"])])
        n_r = max(int(round(float(traffic["y"]) * n)), 1)
        self.set_r = keys_all[:n_r]
        self.rest = keys_all[n_r:] if n_r < n else keys_all
        n_w = max(int(round(float(traffic["s"]) * n)), 1)
        self.set_w = rng.permutation(keys_all)[:n_w]

    def reads(self, count: int) -> np.ndarray:
        """``count`` keys drawn as the stream's contains lanes draw them."""
        rng = self.rng
        take_pop = rng.random(count) < self.x
        k_pop = self.set_r[rng.integers(0, len(self.set_r), count)]
        k_rest = self.rest[rng.integers(0, len(self.rest), count)]
        return np.where(take_pop, k_pop, k_rest).astype(np.int32)

    def next_batch(self):
        """One batch: ``(kinds int32[B], keys int32[B], upd bool[B])``."""
        rng, b, r = self.rng, self.batch_size, self.r
        u = rng.random(b)
        kinds = np.where(u < r, OP_CONTAINS,
                         np.where(u < r + (1 - r) / 2, OP_INSERT, OP_DELETE)
                         ).astype(np.int32)
        k_reads = self.reads(b)
        k_writes = self.set_w[rng.integers(0, len(self.set_w), b)]
        keys = np.where(kinds == OP_CONTAINS, k_reads, k_writes)
        upd = rng.random(b) < self.p
        return kinds, keys.astype(np.int32), upd
