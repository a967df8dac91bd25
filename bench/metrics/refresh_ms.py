"""``refresh_ms``: device ms per window batch in the plane refresh
(``core/device_index.refresh_device``, and ``from_state_device`` when a
batch rebuilds the plane), from the trace."""


def read(ctx):
    per = ctx.trace.per_batch_s("refresh")
    if not per or sum(per) <= 0:
        return None
    return 1e3 * sum(per) / len(per)
