"""``refresh_exchange_ms``: device ms per window batch, averaged over
the chips, in the cross-chip collectives of the plane refresh: the
width-sharded refresh's ``splay.redistribute`` work (the boundary
halo, the cross-shard scans, the segment redistribution and the
all_gather of every level row's prefix sum).  Collectives are told
apart as in ``exchange_ms``; a trace with none reads nothing."""

from bench.metrics.exchange_ms import collective_ms


def read(ctx):
    return collective_ms(ctx.trace, refresh=True)
