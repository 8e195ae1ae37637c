"""Reader: the share of its memory roofline that the apply program of
an unbounded keyed join reaches, in percent (``join_step_bytes`` has the
byte model).

args: ``match`` — regex over program names on ``XLA Modules``, busiest
device: ``apply_bytes`` over their device time a BATCH (one call a
batch).

Nothing is read without a device plane, a matching program (a program
without the device join has none) or the module's ``step_shapes`` with
``records``, ``keys`` and ``changed``."""
from benchmark.join_step_bytes import apply_bytes
from benchmark.step_bytes import load_peaks
from benchmark.trace_reduce import MODULES_LINE


def read(ctx, match):
    trace = ctx.get("trace")
    dev = trace.busiest() if trace is not None else None
    shapes = ctx.get("step_shapes")
    batches = ctx.get("trace_batches")
    if dev is None or not batches or not shapes \
            or not {"records", "keys", "changed"} <= set(shapes):
        return None
    calls, secs = dev.seconds(MODULES_LINE, match)
    if not calls or secs <= 0:
        return None
    peak = load_peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (apply_bytes(**shapes) / peak) / (secs / batches)
