"""Reader: the share of the device's idle time that no host event names,
in percent: the idle seconds ``trace_reduce``'s ``labelled_gaps`` gives to
``host.untraced`` (busiest device) over all idle seconds, within the part
of the traced span in which the host tracer recorded (``trace_host
.host_recorded``: the profiler's device tracer runs on for ~0.2 s after
its host tracer has stopped, and no program could name that). High: the
host was in code that has no span, and the trace cannot say what keeps
the chip waiting."""
from benchmark.readers.trace_host import host_recorded
from benchmark.trace_reduce import Trace


def read(ctx):
    trace = ctx.get("trace")
    dev = trace.busiest() if trace is not None else None
    recorded = host_recorded(trace) if dev is not None else None
    if recorded is None:
        return None
    gaps = dict(Trace([dev], trace.host, recorded).labelled_gaps(dev))
    idle = sum(gaps.values())
    if idle <= 0:
        return None
    return 100.0 * gaps.get("host.untraced", 0.0) / idle
