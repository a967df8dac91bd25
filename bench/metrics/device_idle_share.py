"""``device_idle_share``: the share of the traced window in which no op
ran on the device, in %, averaged over the devices.  Busy time is the
union of the device op intervals (``trace_reduce.Reduction``)."""


def read(ctx):
    if ctx.trace.busy_s <= 0:
        return None
    return 100.0 * ctx.trace.idle_share()
