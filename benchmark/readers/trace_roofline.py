"""Reader: the device step's share of its memory roofline, in percent:
``step_bytes() / peak HBM bytes per s`` over the device time per batch of
the programs matching ``match`` on ``XLA Modules`` (busiest device). The
shapes of a step come from the configuration's module (``step_shapes``);
a module without that hook has no byte model, and nothing is read."""
from benchmark.step_bytes import load_peaks, step_bytes
from benchmark.trace_reduce import MODULES_LINE


def read(ctx, match):
    trace = ctx.get("trace")
    dev = trace.busiest() if trace is not None else None
    state = ctx["job_metrics"].get("memory.hbm_state_bytes")
    batches = ctx["trace_batches"]
    shapes = ctx.get("step_shapes")
    if dev is None or not state or not batches or not shapes:
        return None
    calls, secs = dev.seconds(MODULES_LINE, match)
    if not calls:
        return None
    peaks = load_peaks(ctx["device_kind"])
    need = step_bytes(state_bytes=int(state) // max(1, ctx["chips"]),
                      fires=ctx["fires"] > 0, **shapes)
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / (secs / batches)
