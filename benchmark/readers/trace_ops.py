"""Reader: device time of programs or ops in the traced span.

args: ``line`` — ``XLA Modules`` (whole programs) | ``XLA Ops``;
``match`` — regex over names, durations summed on the busiest device;
``per`` — ``batches`` (those the source handed over while the profiler
ran) or absent for plain seconds;
``scale``."""


def read(ctx, line, match, per=None, scale=1.0):
    trace = ctx.get("trace")
    dev = trace.busiest() if trace is not None else None
    if dev is None:
        return None
    calls, secs = dev.seconds(line, match)
    if not calls:
        return None
    if per == "batches":
        batches = ctx["trace_batches"]
        if not batches:
            return None
        secs /= batches
    return secs * scale
