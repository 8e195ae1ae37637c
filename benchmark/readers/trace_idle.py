"""Reader: share of the traced span in which no op ran on the device
(the busiest device where several are used), in percent."""


def read(ctx):
    trace = ctx.get("trace")
    dev = trace.busiest() if trace is not None else None
    if dev is None or trace.window_s <= 0:
        return None
    return 100.0 * trace.idle_share(dev)
