"""The plain reference: a sorted key set with set semantics, and each
key's hit count.

Independent of the code under test (numpy and the standard library
only).  It replays the served batches in order and, inside a batch, in
lane order:

  * contains -> 1 if the key is live;
  * insert   -> 1 if the key was absent (it is then live);
  * delete   -> 1 if the key was live (it is then absent).

A batch of reads alone is answered at once against the sorted live
keys, which is what lane order gives when nothing in it writes.

Hit counts are the splay-list's per-key access counters (the paper's
``selfhits``): a loaded key starts at its loaded count; every lane
whose splay coin ``upd`` is set adds 1 to its key's count if the key
is live (a contains, or an insert of a live key); an insert of a key
never seen before starts it at 1.  A delete of a live key makes the
key's count depend on when the index reclaims the marked node, so from
then on the key is ``settled = False`` and its count is not compared.
"""

from __future__ import annotations

import numpy as np

OP_CONTAINS, OP_INSERT, OP_DELETE = 0, 1, 2


class KeySet:
    def __init__(self, keys, hits=None):
        keys = np.asarray(keys).tolist()
        self.live = set(keys)
        self.hits = dict(zip(keys, (np.ones(len(keys), np.int64)
                                    if hits is None
                                    else np.asarray(hits)).tolist()))
        self.unsettled = set()
        self._sorted = None

    def sorted_keys(self) -> np.ndarray:
        """The live keys, ascending (int64)."""
        if self._sorted is None:
            self._sorted = np.array(sorted(self.live), np.int64)
        return self._sorted

    def settled_hits(self):
        """``(keys, hits)`` of the live keys whose count is exact,
        ascending by key (int64)."""
        keys = [k for k in self.sorted_keys().tolist()
                if k not in self.unsettled]
        return (np.array(keys, np.int64),
                np.array([self.hits[k] for k in keys], np.int64))

    def apply(self, kinds, keys, upd=None) -> np.ndarray:
        """Answers (int32 [B]) of one batch, applied in lane order;
        ``upd`` (bool [B], default none set) are the lanes' splay
        coins."""
        kinds = np.asarray(kinds).ravel()
        keys = np.asarray(keys, np.int64).ravel()
        upd = (np.zeros(kinds.shape, bool) if upd is None
               else np.asarray(upd, bool).ravel())
        bad = ~np.isin(kinds, (OP_CONTAINS, OP_INSERT, OP_DELETE))
        if bad.any():
            raise ValueError(f"op kind {int(kinds[bad][0])} is not served")
        hits, live = self.hits, self.live
        if (kinds == OP_CONTAINS).all():
            srt = self.sorted_keys()
            if not len(srt):
                return np.zeros(kinds.shape, np.int32)
            i = np.minimum(np.searchsorted(srt, keys), len(srt) - 1)
            out = (srt[i] == keys).astype(np.int32)
            hit, count = np.unique(keys[(out == 1) & upd],
                                   return_counts=True)
            for k, c in zip(hit.tolist(), count.tolist()):
                hits[k] += c
            return out
        out = np.zeros(kinds.shape, np.int32)
        for lane, (kd, k, u) in enumerate(zip(kinds.tolist(), keys.tolist(),
                                              upd.tolist())):
            if kd == OP_CONTAINS:
                out[lane] = k in live
                if u and k in live:
                    hits[k] += 1
            elif kd == OP_INSERT:
                out[lane] = k not in live
                if k in live:
                    hits[k] += u
                else:
                    hits[k] = 1
                    live.add(k)
            else:
                out[lane] = k in live
                if k in live:
                    live.discard(k)
                    self.unsettled.add(k)
        self._sorted = None
        return out
