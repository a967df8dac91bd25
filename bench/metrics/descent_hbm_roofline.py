"""``descent_hbm_roofline``: the least time the chip needs to read what
an exact descent must read, over the time the descent kernel
(``splay_search_tiered``) took, in %.

For each window batch whose entering plane the run kept, the bytes are
512 per distinct ``(row, 128-lane tile)`` that the batch's distinct
keys touch on their way down (``trace_reduce.touched_tiles``); the
least time is those bytes over the chip's HBM bandwidth from
``bench/peaks.json``.  The bytes the kernel itself streams are never
used, so a descent that reads less shows as a gain."""

from bench import trace_reduce


def read(ctx):
    if not ctx.serving.get("plane_search"):
        return None
    per = ctx.trace.per_batch_s("descent")
    run = ctx.run
    need_s = took_s = 0.0
    for idx, rows, widths in run.planes_in:
        j = idx - run.window_first
        if j >= len(per) or per[j] <= 0:
            continue
        tiles = trace_reduce.touched_tiles(rows, widths, run.batches[idx][1])
        need_s += tiles * trace_reduce.TILE_BYTES / ctx.peaks[
            "hbm_bytes_per_s"]
        took_s += per[j]
    if took_s <= 0:
        return None
    return 100.0 * need_s / took_s
