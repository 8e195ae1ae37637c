"""Reader: host time of the program's own spans in the traced span.

The program holds a ``jax.profiler.TraceAnnotation`` open for each phase
of its ingest loop and drain thread (``flink_tpu/obs/tracing.py``
``PhaseClock``), so each phase is an event of the trace's host plane, on
the device trace's clock. A program without such spans (or a trace
without a match) gives nothing to read, and neither does a trace with no
device plane: that is a CPU run's, and its times are no chip's.

args: ``match`` — regex over host event names, durations summed over
all threads (the program's phases never nest, so a sum counts no time
twice; JAX's own nested events are not meant to be matched);
``per`` — ``batches`` or absent for plain seconds: the batches the source
handed over while the HOST tracer recorded. The profiler starts its host
tracer tens of ms after the traced span opens and stops it before the
device tracer (``host_recorded`` below; on a v5e 3.0 of 3.27 s), so
``ctx["trace_batches"]``, counted over the whole span, is scaled by that
share: batches arrive evenly in every mix there is;
``scale``."""
import re

from benchmark.trace_reduce import HOST_NOISE


def host_recorded(trace):
    """``(start_ns, end_ns)`` of what the host tracer recorded, inside the
    traced span; ``None`` without host events."""
    spans = [(s, s + d) for name, s, d in trace.host
             if not HOST_NOISE.match(name)]
    if not spans:
        return None
    return (max(trace.window[0], min(s for s, _e in spans)),
            min(trace.window[1], max(e for _s, e in spans)))


def read(ctx, match, per=None, scale=1.0):
    trace = ctx.get("trace")
    if trace is None or trace.busiest() is None:
        return None
    rx = re.compile(match)
    durations = [d for name, _start, d in trace.host if rx.search(name)]
    if not durations:
        return None
    secs = sum(durations) / 1e9
    if per == "batches":
        lo, hi = host_recorded(trace)
        batches = ctx["trace_batches"] * (hi - lo) / 1e9 / trace.window_s
        if not batches:
            return None
        secs /= batches
    return secs * scale
