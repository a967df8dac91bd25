"""``fold_ms``: device ms per window batch in the update fold
(``core/splaylist.run_ops`` on the mixed path, ``run_contains_batch``
on the read-only path), from the trace."""


def read(ctx):
    per = ctx.trace.per_batch_s("fold")
    if not per or sum(per) <= 0:
        return None
    return 1e3 * sum(per) / len(per)
