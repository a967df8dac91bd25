"""``walk_steps_per_op``: the mean ``path_len`` that ``run_serving``
returns for the lanes of the window's batches on the mixed path, where
each lane is answered by a walk of the state (a program count: steps of
the walk).  On the plane path ``path_len`` is the descent's level, not a
walk, so nothing is read there."""

import numpy as np


def read(ctx):
    if ctx.serving.get("plane_search"):
        return None
    lens = [plen for *_, plen in ctx.window_batches()]
    if not lens:
        return None
    return float(np.mean(np.concatenate(lens)))
